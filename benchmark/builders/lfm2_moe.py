"""LFM2-MoE through ``nn/transformer.DistributedTransformerLMHead`` with
the kwargs of ``nn/huggingface/lfm2_moe.config_to_smp``: the patterned
stack whose kinds name their mixer (the gated short convolution or grouped
KV attention with per-head q/k norms), a dense lead MLP, the dropless
expert layer under the sigmoid law with its selection bias, told which
experts it holds, and the head tied to the input table. The step function
is Laguna's (``builders/laguna_moe.py``); the Hugging Face names of
``benchmark/lfm2_weights.py`` (tensors stacked by kind of layer) are
translated to the module's flat names in ``jax.numpy`` on the stacked
tensors with the translator's own per-tensor functions;
``tests/benchmark`` holds this against the repo's per-layer numpy
translator."""

from benchmark import lfm2_weights, loader

train_step = loader.load_sibling(__file__, "laguna_moe").train_step

STACK = "transformer"
GLOBALS = {
    "model.embed_tokens.weight": "word_embedding/embedding",
    "model.embedding_norm.weight": "ln_f/scale",
}
A, M = "self_attn.", "feed_forward."


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import lfm2_moe
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **lfm2_moe.config_to_smp(lfm2_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def _runs(cfg):
    """``[(flat path prefix, kind, lead shape, kind-local layer indices)]``
    of the patterned stack's parameter groups."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, _ = lfm2_weights.plan(cfg)
    local = {layer: j
             for layers in lfm2_weights.layers_of(pattern).values()
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


def _vectors(mixer):
    """A layer's vectors under the mixer's name: HF name -> the module's."""
    from smdistributed_modelparallel_tpu.nn.huggingface import lfm2_moe

    names = {theirs: ours.format(mixer=mixer)
             for theirs, ours in lfm2_moe.NORMS.items()}
    return dict(names, **lfm2_moe.QK_NORMS) if mixer == "attention" \
        else names


def flat_from_hf(cfg, w):
    """HF-named state dict (stacked by kind) -> the module's flat dict."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import (
        laguna,
        lfm2_moe,
    )

    flat = {ours: w[theirs] for theirs, ours in GLOBALS.items()}
    for path, kind, lead, members in _runs(cfg):
        p = f"model.layers.{kind}."
        take = lambda name: w[p + name][jnp.asarray(members)]  # noqa: E731
        if p + "conv.in_proj.weight" in w:
            mixer = "conv"
            layer = lfm2_moe.conv_from_hf(
                take("conv.in_proj.weight"), take("conv.conv.weight"),
                take("conv.out_proj.weight"))
        else:
            mixer = "attention"
            layer = laguna.attention_from_hf(
                take(A + "q_proj.weight"), take(A + "k_proj.weight"),
                take(A + "v_proj.weight"), take(A + "out_proj.weight"),
                None, cfg["head_dim"], xp=jnp)
        layer.update({ours: take(theirs)
                      for theirs, ours in _vectors(mixer).items()})
        if p + M + "gate.weight" in w:
            layer["output/router/kernel"] = take(
                M + "gate.weight").swapaxes(-1, -2)
            layer["output/router/selection_bias"] = take(M + "expert_bias")
            layer.update(laguna.experts_from_hf(
                take(M + "experts.w1.weight"), take(M + "experts.w3.weight"),
                take(M + "experts.w2.weight"), xp=jnp))
        else:
            layer.update(laguna.gated_mlp_from_hf(
                take(M + "w1.weight"), take(M + "w3.weight"),
                take(M + "w2.weight"), "output"))
        for key, value in layer.items():
            flat[f"{path}/{key}"] = value.reshape(lead + value.shape[1:])
    return flat


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import (
        laguna,
        lfm2_moe,
    )

    out = {theirs: flat[ours] for theirs, ours in GLOBALS.items()}
    pieces = {}
    for path, kind, lead, members in _runs(cfg):
        layer = {k[len(path) + 1:]: v.reshape((-1,) + v.shape[len(lead):])
                 for k, v in flat.items() if k.startswith(path + "/")}
        t = lambda x: x.swapaxes(-1, -2)                     # noqa: E731
        if "conv/in_proj/kernel" in layer:
            mixer = "conv"
            in_proj, taps, out_proj = lfm2_moe.conv_to_hf(layer)
            named = {"conv.in_proj.weight": in_proj,
                     "conv.conv.weight": taps,
                     "conv.out_proj.weight": out_proj}
        else:
            mixer = "attention"
            q, k, v, o, _ = laguna.attention_to_hf(layer)
            named = {A + "q_proj.weight": q, A + "k_proj.weight": k,
                     A + "v_proj.weight": v, A + "out_proj.weight": o}
        named.update({theirs: layer[ours]
                      for theirs, ours in _vectors(mixer).items()})
        if "output/router/kernel" in layer:
            gate, up, down = laguna.experts_to_hf(layer)
            named.update({
                M + "gate.weight": t(layer["output/router/kernel"]),
                M + "expert_bias": layer["output/router/selection_bias"],
                M + "experts.w1.weight": gate, M + "experts.w3.weight": up,
                M + "experts.w2.weight": down})
        else:
            named.update({
                M + "w1.weight": t(layer["output/gate/kernel"]),
                M + "w3.weight": t(layer["output/fc/kernel"]),
                M + "w2.weight": t(layer["output/proj/kernel"])})
        for name, value in named.items():
            pieces.setdefault(f"model.layers.{kind}.{name}", []).append(
                (members, value))
    for name, parts in pieces.items():
        order = jnp.argsort(jnp.asarray([j for m, _ in parts for j in m]))
        out[name] = jnp.concatenate([v for _, v in parts])[order]
    return out
