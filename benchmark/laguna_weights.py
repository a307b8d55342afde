"""Seeded weights and token batches of the Laguna family (``model_type``
"laguna"), as ``weights.py`` makes them for the dense families: every leaf
random from ``--seed``, made on the device inside one jitted call, under
Hugging Face names (the convention ``nn/huggingface/laguna.py`` assumes).

Layers of this family differ in shape (query heads by layer, a dense MLP
or routed experts), so per-layer tensors are stacked **by kind of layer**:
``model.layers.<kind>.self_attn.q_proj.weight`` holds that tensor of every
layer of the kind, in layer order, over a leading axis; the routed experts
held here are stacked over a second axis (``...mlp.experts.gate_proj.weight``
[layers, held, F, D]). Kinds are ``plan(cfg)``'s names: ``lead_dense``,
``window``, ``full``. All matrices N(0, initializer_range), RMSNorm scales
1 + N(0, initializer_range); linear weights [out, in], as torch keeps them.

The configuration file holds the chip's share as it is run: ``num_experts``
is the count held here (``num_experts_published`` the router's width),
``vocab_size`` the slice, ``num_key_value_heads`` and
``num_attention_heads_per_layer`` the heads held, ``layer_types`` the
layers kept.
"""

import math

from benchmark import weights


def hf_view(cfg):
    """The configuration as ``nn/huggingface/laguna.py`` reads it: the
    router at its published width with the range of experts held here."""
    view = {k: v for k, v in cfg.items()}
    view["num_hidden_layers"] = len(cfg["layer_types"])
    view["num_experts"] = cfg["num_experts_published"]
    view["experts_held"] = (cfg.get("experts_held_first", 0),
                            cfg["num_experts"])
    return view


def plan(cfg):
    """``(pattern, kinds)`` of ``laguna.layer_plan`` for this file."""
    from smdistributed_modelparallel_tpu.nn.huggingface import laguna

    return laguna.layer_plan(hf_view(cfg))


def layers_of(pattern):
    """``{kind: [layer indices]}`` in layer order."""
    out = {}
    for i, kind in enumerate(pattern):
        out.setdefault(kind, []).append(i)
    return out


def spec_for(cfg):
    """``{name: (shape, kind, std)}``."""
    pattern, kinds = plan(cfg)
    D, hd, V = cfg["hidden_size"], cfg["head_dim"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    spec = {
        "model.embed_tokens.weight": ((V, D), "normal", std),
        "model.norm.weight": ((D,), "scale", std),
        "lm_head.weight": ((V, D), "normal", std),
    }
    for kind, layers in layers_of(pattern).items():
        n, kw = len(layers), kinds[kind]
        H, Hkv = kw["num_attention_heads"], kw["num_key_value_heads"]
        p = f"model.layers.{kind}."
        spec.update({
            p + "input_layernorm.weight": ((n, D), "scale", std),
            p + "post_attention_layernorm.weight": ((n, D), "scale", std),
            p + "self_attn.q_proj.weight": ((n, H * hd, D), "normal", std),
            p + "self_attn.k_proj.weight": ((n, Hkv * hd, D), "normal", std),
            p + "self_attn.v_proj.weight": ((n, Hkv * hd, D), "normal", std),
            p + "self_attn.o_proj.weight": ((n, D, H * hd), "normal", std),
            p + "self_attn.g_proj.weight": ((n, H, D), "normal", std),
        })
        F = kw["intermediate_size"]
        if not kw["num_experts"]:
            gated, lead = p + "mlp.", (n,)
        else:
            held = kw["moe_held"][1]
            spec[p + "mlp.gate.weight"] = (
                (n, kw["num_experts"], D), "normal", std)
            gated, lead = p + "mlp.experts.", (n, held)
            Fs = kw["moe_shared_intermediate_size"]
            s = p + "mlp.shared_expert."
            spec[s + "gate_proj.weight"] = ((n, Fs, D), "normal", std)
            spec[s + "up_proj.weight"] = ((n, Fs, D), "normal", std)
            spec[s + "down_proj.weight"] = ((n, D, Fs), "normal", std)
        spec[gated + "gate_proj.weight"] = (lead + (F, D), "normal", std)
        spec[gated + "up_proj.weight"] = (lead + (F, D), "normal", std)
        spec[gated + "down_proj.weight"] = (lead + (D, F), "normal", std)
    return spec


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word."""
    return {name: weights.make_leaf(seed, name, *entry)
            for name, entry in spec_for(cfg).items()}


def token_batches(seed, count, batch, seq, vocab, offset):
    """``count`` batches [count, batch, seq] of ids with p(k) ~ 1 / (k +
    offset) over ``vocab`` ids (Zipf-Mandelbrot, drawn through the inverse
    of its cumulative sum: id = offset ((vocab + offset) / offset)^u -
    offset). At offset 1000 over 12,544 ids the commonest id has 0.04% of
    the tokens and 13.5 times the rarest's share: a unigram to learn, and
    no id whose experts carry a tenth of a batch."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), 0x7A69)
    u = jax.random.uniform(key, (count, batch, seq), jnp.float32)
    ids = offset * jnp.exp(u * math.log((vocab + offset) / offset)) - offset
    return jnp.clip(ids.astype(jnp.int32), 0, vocab - 1)
