"""Xing4.0 through ``nn/transformer.DistributedTransformerLMHead`` with the
kwargs of ``nn/huggingface/xing4.config_to_smp``: the patterned stack whose
every kind's attention is latent attention, four residual streams mixed
round each sub-layer by hyper-connections, a dense lead MLP, the dropless
expert layer under the sigmoid law with its selection bias and a shared
expert, told which experts it holds, and an untied head. The step function
is Laguna's (``builders/laguna_moe.py``); the Hugging Face names of
``benchmark/xing4_weights.py`` (tensors stacked by kind of layer) are
translated to the module's flat names in ``jax.numpy`` on the stacked
tensors with the translator's own per-tensor functions;
``tests/benchmark`` holds this against the repo's per-layer numpy
translator."""

from benchmark import loader, xing4_weights

_laguna = loader.load_sibling(__file__, "laguna_moe")
train_step = _laguna.train_step

STACK = "transformer"
GLOBALS = {
    "model.embed_tokens.weight": "word_embedding/embedding",
    "model.norm.weight": "ln_f/scale",
}
A, M = "self_attn.", "mlp."
ATTENTION = ("q_a_proj", "q_b_proj", "kv_a_proj_with_mqa", "kv_b_proj",
             "o_proj")


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import xing4
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **xing4.config_to_smp(xing4_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def _runs(cfg):
    """``[(flat path prefix, kind, lead shape, kind-local layer indices)]``
    of the patterned stack's parameter groups."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, _ = xing4_weights.plan(cfg)
    local = {layer: j
             for layers in xing4_weights.layers_of(pattern).values()
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

    from smdistributed_modelparallel_tpu.nn.huggingface import laguna, xing4

    flat = {ours: w[theirs] for theirs, ours in GLOBALS.items()}
    flat["lm_head/kernel"] = w["lm_head.weight"].T
    for path, kind, lead, members in _runs(cfg):
        p = f"model.layers.{kind}."
        take = lambda name: w[p + name][jnp.asarray(members)]  # noqa: E731
        layer = xing4.attention_from_hf(
            *(take(f"{A}{name}.weight") for name in ATTENTION), cfg, xp=jnp)
        layer.update({ours: take(theirs)
                      for theirs, ours in xing4.NORMS.items()})
        for theirs, site in xing4.CONNECTIONS.items():
            layer.update(xing4.connection_from_hf(
                *(take(f"{theirs}.{leaf}")
                  for leaf in xing4.CONNECTION_LEAVES),
                site, cfg["hc_mult"]))
        if p + M + "gate.weight" in w:
            layer["output/router/kernel"] = take(
                M + "gate.weight").swapaxes(-1, -2)
            layer["output/router/selection_bias"] = take(
                M + "gate.e_score_correction_bias")
            layer.update(laguna.experts_from_hf(
                *(take(f"{M}experts.{name}.weight")
                  for name in ("gate_proj", "up_proj", "down_proj")),
                xp=jnp))
            layer.update(laguna.gated_mlp_from_hf(
                *(take(f"{M}shared_experts.{name}.weight")
                  for name in ("gate_proj", "up_proj", "down_proj")),
                "output/shared"))
        else:
            layer.update(laguna.gated_mlp_from_hf(
                *(take(f"{M}{name}.weight")
                  for name in ("gate_proj", "up_proj", "down_proj")),
                "output"))
        for key, value in layer.items():
            flat[f"{path}/{key}"] = value.reshape(lead + value.shape[1:])
    return flat


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import laguna, xing4

    out = {theirs: flat[ours] for theirs, ours in GLOBALS.items()}
    out["lm_head.weight"] = flat["lm_head/kernel"].T
    pieces = {}
    for path, kind, lead, members in _runs(cfg):
        layer = {k[len(path) + 1:]: v.reshape((-1,) + v.shape[len(lead):])
                 for k, v in flat.items() if k.startswith(path + "/")}
        t = lambda x: x.swapaxes(-1, -2)                     # noqa: E731
        named = {f"{A}{name}.weight": value for name, value in zip(
            ATTENTION, xing4.attention_to_hf(layer, cfg, xp=jnp))}
        named.update({theirs: layer[ours]
                      for theirs, ours in xing4.NORMS.items()})
        for theirs, site in xing4.CONNECTIONS.items():
            named.update({f"{theirs}.{leaf}": value for leaf, value in zip(
                xing4.CONNECTION_LEAVES,
                xing4.connection_to_hf(layer, site))})

        def gated(ours, theirs):
            named.update({
                theirs + "gate_proj.weight": t(layer[f"{ours}/gate/kernel"]),
                theirs + "up_proj.weight": t(layer[f"{ours}/fc/kernel"]),
                theirs + "down_proj.weight": t(layer[f"{ours}/proj/kernel"]),
            })

        if "output/router/kernel" in layer:
            gate, up, down = laguna.experts_to_hf(layer)
            named.update({
                M + "gate.weight": t(layer["output/router/kernel"]),
                M + "gate.e_score_correction_bias":
                    layer["output/router/selection_bias"],
                M + "experts.gate_proj.weight": gate,
                M + "experts.up_proj.weight": up,
                M + "experts.down_proj.weight": down})
            gated("output/shared", M + "shared_experts.")
        else:
            gated("output", M)
        for name, value in named.items():
            pieces.setdefault(f"model.layers.{kind}.{name}", []).append(
                (members, value))
    for name, parts in pieces.items():
        order = jnp.argsort(jnp.asarray([j for m, _ in parts for j in m]))
        out[name] = jnp.concatenate([v for _, v in parts])[order]
    return out
