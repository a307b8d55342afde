"""Ouro through ``nn/transformer.DistributedTransformerLMHead`` with the
kwargs of ``nn/huggingface/ouro.config_to_smp``: one scanned stack of
sandwich-norm layers run ``total_ut_steps`` times over its own output with
one set of parameters, the final norm after every pass, and the head and
the exit gate on every pass's state. The step function asks the model for
the passes' per-token losses (``targets``: the logits of one pass at a
time, made again in the backward pass) and hands them with the gate's
logits to ``smp.nn.exit_gated_loss``. The Hugging Face names of
``benchmark/ouro_weights.py`` (per-layer tensors stacked) are translated to
the module's flat names in ``jax.numpy`` on the stacked tensors with the
translator's own per-tensor functions; ``tests/benchmark`` holds this
against the repo's per-layer numpy translator."""

from benchmark import ouro_weights

STACK = "transformer/seq_layers/layer/"


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import ouro
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **ouro.config_to_smp(ouro_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def train_step(smp, entropy_weight=0.05):
    """The user's step function; the loss's counters (exit shares, entropy,
    the passes' losses) leave the step beside the loss."""

    @smp.step
    def step(model, ids):
        targets = smp.nn.next_token_targets(ids)
        losses, gates = model(ids, targets=targets)
        loss, stats = smp.nn.exit_gated_loss(
            losses, gates, entropy_weight, targets != -100)
        model.backward(loss)
        return loss, stats

    return step


def flat_from_hf(cfg, w):
    """HF-named state dict (layers stacked) -> the module's flat dict."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import ouro

    flat = ouro.globals_from_hf(w.__getitem__)
    layer = ouro.layer_from_hf(
        lambda name: w[ouro_weights.LAYER + name], cfg["head_dim"], xp=jnp)
    flat.update({STACK + key: value for key, value in layer.items()})
    return flat


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    from smdistributed_modelparallel_tpu.nn.huggingface import ouro

    out = ouro.globals_to_hf(flat)
    layer = {k[len(STACK):]: v for k, v in flat.items()
             if k.startswith(STACK)}
    out.update({ouro_weights.LAYER + name: value
                for name, value in ouro.layer_to_hf(layer).items()})
    return out
