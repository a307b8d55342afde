"""Mellum through ``nn/transformer.DistributedTransformerLMHead`` with the
kwargs of ``nn/huggingface/mellum.config_to_smp``: the patterned stack,
grouped KV heads with per-head q/k norms, the dropless expert layer told
which experts it holds, no shared expert. The step function is Laguna's
(``builders/laguna_moe.py``); the Hugging Face names of
``benchmark/mellum_weights.py`` (tensors stacked by kind of layer) are
translated to the module's flat names in ``jax.numpy`` on the stacked
tensors with the translator's own per-tensor functions;
``tests/benchmark`` holds this against the repo's per-layer numpy
translator."""

from benchmark import loader, mellum_weights

train_step = loader.load_sibling(__file__, "laguna_moe").train_step

STACK = "transformer"
GLOBALS = {
    "model.embed_tokens.weight": "word_embedding/embedding",
    "model.norm.weight": "ln_f/scale",
}


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import mellum
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **mellum.config_to_smp(mellum_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def _runs(cfg):
    """``[(flat path prefix, kind, lead shape, kind-local layer indices)]``
    of the patterned stack's parameter groups."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, _ = mellum_weights.plan(cfg)
    local = {layer: j
             for layers in mellum_weights.layers_of(pattern).values()
             for j, layer in enumerate(layers)}
    groups = {}
    for layer, (path, index) in enumerate(pattern_layer_paths(pattern)):
        groups.setdefault((path, pattern[layer]), []).append(
            (index, local[layer]))
    out = []
    for (path, kind), members in groups.items():
        members.sort()
        lead = tuple(max(i[a] for i, _ in members) + 1
                     for a in range(len(members[0][0])))
        out.append((f"{STACK}/{path}", kind, lead, [j for _, j in members]))
    return out


def flat_from_hf(cfg, w):
    """HF-named state dict (stacked by kind) -> the module's flat dict."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import laguna

    flat = {ours: w[theirs] for theirs, ours in GLOBALS.items()}
    flat["lm_head/kernel"] = w["lm_head.weight"].T
    for path, kind, lead, members in _runs(cfg):
        p = f"model.layers.{kind}."
        take = lambda name: w[p + name][jnp.asarray(members)]  # noqa: E731
        a, m = "self_attn.", "mlp."
        layer = laguna.attention_from_hf(
            take(a + "q_proj.weight"), take(a + "k_proj.weight"),
            take(a + "v_proj.weight"), take(a + "o_proj.weight"), None,
            cfg["head_dim"], xp=jnp)
        layer.update({ours: take(theirs)
                      for theirs, ours in laguna.LAYER_VECTORS.items()})
        layer["output/router/kernel"] = take(
            m + "gate.weight").swapaxes(-1, -2)
        layer.update(laguna.experts_from_hf(
            take(m + "experts.gate_proj.weight"),
            take(m + "experts.up_proj.weight"),
            take(m + "experts.down_proj.weight"), xp=jnp))
        for key, value in layer.items():
            flat[f"{path}/{key}"] = value.reshape(lead + value.shape[1:])
    return flat


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import laguna

    out = {theirs: flat[ours] for theirs, ours in GLOBALS.items()}
    out["lm_head.weight"] = flat["lm_head/kernel"].T
    pieces = {}
    for path, kind, lead, members in _runs(cfg):
        layer = {k[len(path) + 1:]: v.reshape((-1,) + v.shape[len(lead):])
                 for k, v in flat.items() if k.startswith(path + "/")}
        q, k, v, o, _ = laguna.attention_to_hf(layer)
        gate, up, down = laguna.experts_to_hf(layer)
        named = {
            "self_attn.q_proj.weight": q, "self_attn.k_proj.weight": k,
            "self_attn.v_proj.weight": v, "self_attn.o_proj.weight": o,
            "mlp.gate.weight": layer["output/router/kernel"].swapaxes(-1, -2),
            "mlp.experts.gate_proj.weight": gate,
            "mlp.experts.up_proj.weight": up,
            "mlp.experts.down_proj.weight": down,
            **{theirs: layer[ours]
               for theirs, ours in laguna.LAYER_VECTORS.items()},
        }
        for name, value in named.items():
            pieces.setdefault(f"model.layers.{kind}.{name}", []).append(
                (members, value))
    for name, parts in pieces.items():
        order = jnp.argsort(jnp.asarray([j for m, _ in parts for j in m]))
        out[name] = jnp.concatenate([v for _, v in parts])[order]
    return out
