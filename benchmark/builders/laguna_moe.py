"""Laguna through ``nn/transformer.DistributedTransformerLMHead`` with the
kwargs of ``nn/huggingface/laguna.config_to_smp``: a stack built from the
configuration's static per-layer pattern, grouped KV heads with per-head
gates, the dropless expert layer told which experts it holds. The Hugging
Face names of ``benchmark/laguna_weights.py`` (tensors stacked by kind of
layer) are translated to the module's flat names here in ``jax.numpy`` on
the stacked tensors, with the translator's own per-tensor functions
(``laguna.attention_from_hf`` ...); ``tests/benchmark`` holds this against
the repo's per-layer numpy translator."""

from benchmark import laguna_weights

STACK = "transformer"


def module(cfg):
    from smdistributed_modelparallel_tpu.nn.huggingface import laguna
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )

    return DistributedTransformerLMHead(
        **laguna.config_to_smp(laguna_weights.hf_view(cfg)),
        **cfg.get("module", {}))


def train_step(smp):
    """The user's step function: logits out of the model, next-token
    cross-entropy in float32, mean over the predictions; the expert
    layers' counters leave the step beside the loss."""
    import jax
    import jax.numpy as jnp

    @smp.step
    def step(model, ids):
        logits = model(ids)[:, :-1].astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
        loss = jnp.mean(lse - tgt)
        model.backward(loss)
        return loss, model.moe_stats()

    return step


def _runs(cfg):
    """``[(flat path prefix, kind, lead shape, kind-local layer indices)]``
    of the patterned stack's parameter groups."""
    from smdistributed_modelparallel_tpu.nn.transformer import (
        pattern_layer_paths,
    )

    pattern, _ = laguna_weights.plan(cfg)
    local = {}
    for kind, layers in laguna_weights.layers_of(pattern).items():
        local.update({layer: j for j, layer in enumerate(layers)})
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

    flat = {
        "word_embedding/embedding": w["model.embed_tokens.weight"],
        "ln_f/scale": w["model.norm.weight"],
        "lm_head/kernel": w["lm_head.weight"].T,
    }
    for path, kind, lead, members in _runs(cfg):
        p = f"model.layers.{kind}."
        take = lambda name: w[p + name][jnp.asarray(members)]  # noqa: E731
        a = "self_attn."
        layer = laguna.attention_from_hf(
            take(a + "q_proj.weight"), take(a + "k_proj.weight"),
            take(a + "v_proj.weight"), take(a + "o_proj.weight"),
            take(a + "g_proj.weight"), cfg["head_dim"], xp=jnp)
        layer["attention/layernorm/scale"] = take("input_layernorm.weight")
        layer["output/layernorm/scale"] = take(
            "post_attention_layernorm.weight")
        m = "mlp."
        if p + m + "gate.weight" in w:
            layer["output/router/kernel"] = take(
                m + "gate.weight").swapaxes(-1, -2)
            layer.update(laguna.experts_from_hf(
                take(m + "experts.gate_proj.weight"),
                take(m + "experts.up_proj.weight"),
                take(m + "experts.down_proj.weight"), xp=jnp))
            s = m + "shared_expert."
            layer.update(laguna.gated_mlp_from_hf(
                take(s + "gate_proj.weight"), take(s + "up_proj.weight"),
                take(s + "down_proj.weight"), "output/shared"))
        else:
            layer.update(laguna.gated_mlp_from_hf(
                take(m + "gate_proj.weight"), take(m + "up_proj.weight"),
                take(m + "down_proj.weight"), "output"))
        for key, value in layer.items():
            flat[f"{path}/{key}"] = value.reshape(lead + value.shape[1:])
    return flat


def hf_from_flat(cfg, flat):
    """The module's flat dict (or one shaped like it) -> HF names."""
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.huggingface import laguna

    out = {
        "model.embed_tokens.weight": flat["word_embedding/embedding"],
        "model.norm.weight": flat["ln_f/scale"],
        "lm_head.weight": flat["lm_head/kernel"].T,
    }
    pieces = {}
    for path, kind, lead, members in _runs(cfg):
        layer = {k[len(path) + 1:]: v.reshape((-1,) + v.shape[len(lead):])
                 for k, v in flat.items() if k.startswith(path + "/")}
        q, k, v, o, g = laguna.attention_to_hf(layer)
        named = {"self_attn.q_proj.weight": q, "self_attn.k_proj.weight": k,
                 "self_attn.v_proj.weight": v, "self_attn.o_proj.weight": o,
                 "self_attn.g_proj.weight": g,
                 "input_layernorm.weight": layer["attention/layernorm/scale"],
                 "post_attention_layernorm.weight":
                     layer["output/layernorm/scale"]}

        def gated(ours, theirs):
            t = lambda x: x.swapaxes(-1, -2)                 # noqa: E731
            named[theirs + "gate_proj.weight"] = t(layer[ours + "/gate/kernel"])
            named[theirs + "up_proj.weight"] = t(layer[ours + "/fc/kernel"])
            named[theirs + "down_proj.weight"] = t(
                layer[ours + "/proj/kernel"])

        if "output/router/kernel" in layer:
            named["mlp.gate.weight"] = layer[
                "output/router/kernel"].swapaxes(-1, -2)
            gate, up, down = laguna.experts_to_hf(layer)
            named["mlp.experts.gate_proj.weight"] = gate
            named["mlp.experts.up_proj.weight"] = up
            named["mlp.experts.down_proj.weight"] = down
            gated("output/shared", "mlp.shared_expert.")
        else:
            gated("output", "mlp.")
        for name, value in named.items():
            pieces.setdefault(f"model.layers.{kind}.{name}", []).append(
                (members, value))
    for name, parts in pieces.items():
        order = jnp.argsort(jnp.asarray([j for m, _ in parts for j in m]))
        out[name] = jnp.concatenate([v for _, v in parts])[order]
    return out
