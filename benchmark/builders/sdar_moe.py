"""SDAR-MoE through ``nn/transformer.DistributedTransformerLMHead`` with
the kwargs of ``nn/huggingface/sdar.config_to_smp``: the patterned stack
(one kind), grouped KV heads with per-head q/k norms, every layer's
attention under the block-diffusion mask, the dropless expert layer told
which experts it holds. The step function is the family's own: the model
takes the two-copy stream and gives logits for the noisy half, the loss is
the weighted sum over masked positions (``nn/diffusion.py``). The leaves
and their Hugging Face names are Mellum's, so the translation between them
and the module's flat names is ``builders/mellum_moe.py``'s, on a copy of
it that reads this family's layer plan."""

from benchmark import loader, sdar_weights

_names = loader.load_sibling(__file__, "mellum_moe")
_names.mellum_weights = sdar_weights
flat_from_hf, hf_from_flat = _names.flat_from_hf, _names.hf_from_flat


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import sdar
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **sdar.config_to_smp(sdar_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def train_step(smp):
    """The user's step function over a batch that carries its noise
    (``sdar_weights.Batches``' item): both copies through the stack, the
    head over the noisy one, the masked positions' weighted loss; the
    expert layers' counters and the objective's leave the step beside the
    loss."""

    @smp.step
    def step(model, batch):
        clean, noisy = batch["clean"], batch["noisy"]
        logits = model(smp.nn.two_copy_stream(clean, noisy))
        loss, counts = smp.nn.masked_diffusion_loss(
            logits, clean, noisy, batch["rates"], batch["mask_id"])
        model.backward(loss)
        return loss, model.moe_stats(), counts

    return step
