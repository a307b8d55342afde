"""One plain decoder-only transformer, written against Hugging Face names.

``forward(cfg, w, ids)`` -> logits [B, T, V] in float32. ``w`` is the state
dict of ``benchmark/weights.py`` (per-layer tensors stacked over a leading
layer axis). Covers ``model_type`` "gpt2" (learned positions, sequential
residual, tied head, ``gelu_new``) and "gpt_neox" (partial NeoX rotary,
parallel residual through two LayerNorms, untied head, exact ``gelu``).
Straightforward on purpose:
full [T, T] scores, a Python-visible layer body under ``lax.scan``, no
kernel and no cache.

``precision`` chooses how every matrix product is computed, and is how the
control (the lower precision a later PR would be tempted by) is made:

- ``"float32"``: float32 operands, ``Precision.HIGHEST``: the reference.
- ``"bfloat16"``: operands rounded to bfloat16, float32 accumulation: what
  the configurations state (``bf16: true``).
- ``"float8"``: operands scaled per tensor to the range of float8_e4m3fn,
  rounded to it, float32 accumulation: the nearest precision below bfloat16.
"""

import functools
import math

import jax
import jax.numpy as jnp

F8_MAX = 448.0      # largest finite float8_e4m3fn


def _rounded(x, precision):
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_operand(x, precision):
    """``x`` as the chosen precision holds it. The gradient passes straight
    through (the rounding is of the forward operands only): the mildest
    form a lower-precision path can take, so the control is not flattered
    by gradients that underflow in the narrow type."""
    if precision == "float32":
        return x
    if precision in ("bfloat16", "float8"):
        return _rounded(x, precision)
    raise ValueError(f"unknown precision {precision!r}")


def _round_fwd(x, precision):
    return _round_operand(x, precision), None


def _round_bwd(precision, _, g):
    return (g,)


_round_operand.defvjp(_round_fwd, _round_bwd)


def product(spec, a, b, precision):
    """``einsum`` with both operands in the chosen precision."""
    return jnp.einsum(
        spec, _round_operand(a, precision), _round_operand(b, precision),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def causal_attention(q, k, v, precision):
    """q, k, v: [B, T, H, hd] -> [B, T, H, hd], full causal softmax."""
    T, hd = q.shape[1], q.shape[-1]
    scores = product("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return product("bhqk,bkhd->bqhd", probs, v, precision)


def gpt2_layer(cfg, x, lw, precision):
    """One GPT-2 block; ``lw`` holds this layer's slice of the ``h.*``
    tensors under their names without the ``h.`` prefix."""
    B, T, d = x.shape
    H = cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]
    h = layer_norm(x, lw["ln_1.weight"], lw["ln_1.bias"], eps)
    qkv = product("btd,de->bte", h, lw["attn.c_attn.weight"], precision)
    qkv = qkv + lw["attn.c_attn.bias"]
    q, k, v = (t.reshape(B, T, H, d // H) for t in jnp.split(qkv, 3, -1))
    a = causal_attention(q, k, v, precision).reshape(B, T, d)
    a = product("btd,de->bte", a, lw["attn.c_proj.weight"], precision)
    x = x + a + lw["attn.c_proj.bias"]
    h = layer_norm(x, lw["ln_2.weight"], lw["ln_2.bias"], eps)
    h = product("btd,df->btf", h, lw["mlp.c_fc.weight"], precision)
    h = gelu_new(h + lw["mlp.c_fc.bias"])
    h = product("btf,fd->btd", h, lw["mlp.c_proj.weight"], precision)
    return x + h + lw["mlp.c_proj.bias"]


def gpt2_forward(cfg, w, ids, precision="float32", remat=False):
    T = ids.shape[1]
    x = w["wte.weight"][ids] + w["wpe.weight"][jnp.arange(T)][None]
    stacked = {k[2:]: v for k, v in w.items() if k.startswith("h.")}

    def body(x, lw):
        return gpt2_layer(cfg, x, lw, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    x = layer_norm(x, w["ln_f.weight"], w["ln_f.bias"],
                   cfg["layer_norm_epsilon"])
    return product("btd,vd->btv", x, w["wte.weight"], precision)


def neox_rotary(x, rotary_dim, base):
    """NeoX-style rotary on the first ``rotary_dim`` of each head of
    x [B, T, H, hd]: halves are rotated, not interleaved pairs."""
    T = x.shape[1]
    inv_freq = 1.0 / (base ** (
        jnp.arange(0, rotary_dim, 2, dtype=jnp.float32) / rotary_dim))
    freqs = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)          # [T, rd]
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    rotated = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate([rot * cos + rotated * sin, rest], axis=-1)


def gpt_neox_layer(cfg, x, lw, precision):
    """One GPT-NeoX block with the parallel residual: attention and MLP
    both read the layer's input, each through its own LayerNorm."""
    B, T, d = x.shape
    H = cfg["num_attention_heads"]
    hd = d // H
    eps = cfg["layer_norm_eps"]
    rotary_dim = int(hd * cfg["rotary_pct"])
    base = cfg.get("rotary_emb_base", 10000)
    h = layer_norm(x, lw["input_layernorm.weight"],
                   lw["input_layernorm.bias"], eps)
    qkv = product("btd,ed->bte", h, lw["attention.query_key_value.weight"],
                  precision) + lw["attention.query_key_value.bias"]
    qkv = qkv.reshape(B, T, H, 3 * hd)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k = neox_rotary(q, rotary_dim, base), neox_rotary(k, rotary_dim, base)
    a = causal_attention(q, k, v, precision).reshape(B, T, d)
    a = product("btd,ed->bte", a, lw["attention.dense.weight"], precision)
    a = a + lw["attention.dense.bias"]
    h = layer_norm(x, lw["post_attention_layernorm.weight"],
                   lw["post_attention_layernorm.bias"], eps)
    h = product("btd,fd->btf", h, lw["mlp.dense_h_to_4h.weight"], precision)
    h = jax.nn.gelu(h + lw["mlp.dense_h_to_4h.bias"], approximate=False)
    h = product("btf,df->btd", h, lw["mlp.dense_4h_to_h.weight"], precision)
    h = h + lw["mlp.dense_4h_to_h.bias"]
    if cfg.get("use_parallel_residual", True):
        return x + a + h
    raise NotImplementedError("sequential-residual GPT-NeoX")


def gpt_neox_forward(cfg, w, ids, precision="float32", remat=False):
    prefix = "gpt_neox.layers."
    x = w["gpt_neox.embed_in.weight"][ids]
    stacked = {k[len(prefix):]: v for k, v in w.items()
               if k.startswith(prefix)}

    def body(x, lw):
        return gpt_neox_layer(cfg, x, lw, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    x = layer_norm(x, w["gpt_neox.final_layer_norm.weight"],
                   w["gpt_neox.final_layer_norm.bias"], cfg["layer_norm_eps"])
    return product("btd,vd->btv", x, w["embed_out.weight"], precision)


FORWARDS = {"gpt2": gpt2_forward, "gpt_neox": gpt_neox_forward}


def forward(cfg, w, ids, precision="float32", remat=False):
    return FORWARDS[cfg["model_type"]](cfg, w, ids, precision, remat)
