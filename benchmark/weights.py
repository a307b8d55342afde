"""Weights from ``--seed``, made on the device in one jitted call, under
Hugging Face parameter names.

The benchmark makes the weights, not the program: the program under test is
handed them through a builder (``builders/``), and the plain reference makes
the same ones again from the same seed after the program's are freed. So the
reference takes nothing the program has made.

Per-layer tensors are stacked over a leading [n_layer] axis and named as in
a Hugging Face state dict without the layer index: ``h.attn.c_attn.weight``
holds ``h.{i}.attn.c_attn.weight`` for every i, and
``gpt_neox.layers.mlp.dense_4h_to_h.weight`` holds
``gpt_neox.layers.{i}.mlp.dense_4h_to_h.weight``.

Every leaf is random, biases and LayerNorm scales too (the published inits
leave them at 0 and 1, where a wrong handling would not show): matrices
N(0, 0.02), residual output projections N(0, 0.02 / sqrt(2 n_layer)) as
GPT-2 does, biases N(0, 0.02), LayerNorm scales 1 + N(0, 0.02).
"""

import math
import zlib


def gpt2_spec(cfg):
    """``{name: (shape, kind, std)}`` for a GPT-2 ``config.json``."""
    d, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    ff = cfg.get("n_inner") or 4 * d
    P = cfg["n_positions"]
    std = cfg.get("initializer_range", 0.02)
    res = std / math.sqrt(2 * L)
    return {
        "wte.weight": ((V, d), "normal", std),
        "wpe.weight": ((P, d), "normal", std),
        "h.ln_1.weight": ((L, d), "scale", std),
        "h.ln_1.bias": ((L, d), "normal", std),
        "h.attn.c_attn.weight": ((L, d, 3 * d), "normal", std),
        "h.attn.c_attn.bias": ((L, 3 * d), "normal", std),
        "h.attn.c_proj.weight": ((L, d, d), "normal", res),
        "h.attn.c_proj.bias": ((L, d), "normal", std),
        "h.ln_2.weight": ((L, d), "scale", std),
        "h.ln_2.bias": ((L, d), "normal", std),
        "h.mlp.c_fc.weight": ((L, d, ff), "normal", std),
        "h.mlp.c_fc.bias": ((L, ff), "normal", std),
        "h.mlp.c_proj.weight": ((L, ff, d), "normal", res),
        "h.mlp.c_proj.bias": ((L, d), "normal", std),
        "ln_f.weight": ((d,), "scale", std),
        "ln_f.bias": ((d,), "normal", std),
    }


def gpt_neox_spec(cfg):
    """``{name: (shape, kind, std)}`` for a GPT-NeoX ``config.json``.
    Linear weights are [out, in], as torch keeps them; the fused
    query_key_value's output dim is [head, 3, head_size]-interleaved."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    ff = cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    res = std / math.sqrt(2 * L)
    p = "gpt_neox.layers."
    return {
        "gpt_neox.embed_in.weight": ((V, d), "normal", std),
        p + "input_layernorm.weight": ((L, d), "scale", std),
        p + "input_layernorm.bias": ((L, d), "normal", std),
        p + "post_attention_layernorm.weight": ((L, d), "scale", std),
        p + "post_attention_layernorm.bias": ((L, d), "normal", std),
        p + "attention.query_key_value.weight": ((L, 3 * d, d), "normal", std),
        p + "attention.query_key_value.bias": ((L, 3 * d), "normal", std),
        p + "attention.dense.weight": ((L, d, d), "normal", res),
        p + "attention.dense.bias": ((L, d), "normal", std),
        p + "mlp.dense_h_to_4h.weight": ((L, ff, d), "normal", std),
        p + "mlp.dense_h_to_4h.bias": ((L, ff), "normal", std),
        p + "mlp.dense_4h_to_h.weight": ((L, d, ff), "normal", res),
        p + "mlp.dense_4h_to_h.bias": ((L, d), "normal", std),
        "gpt_neox.final_layer_norm.weight": ((d,), "scale", std),
        "gpt_neox.final_layer_norm.bias": ((d,), "normal", std),
        "embed_out.weight": ((V, d), "normal", std),
    }


SPECS = {"gpt2": gpt2_spec, "gpt_neox": gpt_neox_spec}


def spec_for(cfg):
    return SPECS[cfg["model_type"]](cfg)


def seed_word(seed):
    """``--seed`` (any whole number up to a little over 2**31) as the
    uint32 the jitted makers take as an *argument*: a seed baked into a
    program would make every new seed a new program to compile."""
    import numpy as np

    return np.uint32(int(seed) % (2 ** 32))


def make_leaf(seed, name, shape, kind, std):
    """One leaf; ``seed`` is a uint32 scalar, traced or not."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), zlib.crc32(name.encode()))
    x = std * jax.random.normal(key, shape, jnp.float32)
    return 1.0 + x if kind == "scale" else x


def make_weights(cfg, seed):
    """The whole fp32 state dict as a traceable function of the seed word:
    call it under ``jax.jit`` with the seed as an argument (one program, on
    the device, the same program for every seed)."""
    return {
        name: make_leaf(seed, name, *entry)
        for name, entry in spec_for(cfg).items()
    }


def token_batches(seed, count, batch, seq, vocab):
    """``count`` training batches [count, batch, seq] of token ids, every
    row different. Ids are log-uniform over the vocabulary (p(k) ~ 1/k, the
    shape of real token frequencies), so there is something to learn and
    the loss falls from the first step."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), 0x7061)
    u = jax.random.uniform(key, (count, batch, seq), jnp.float32)
    ids = jnp.exp(u * math.log(vocab)).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, vocab - 1)
