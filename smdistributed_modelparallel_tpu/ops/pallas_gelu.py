"""Fused bias+GELU Pallas kernel.

The MLP's ``fc`` epilogue is ``h + bias`` followed by tanh-GELU — two
elementwise HBM passes over the [*, intermediate] activation when XLA
declines to fuse them into the matmul. This kernel computes
``gelu(x + b)`` in one VMEM-resident pass; the backward kernel
recomputes the pre-activation from the saved (x, b) and emits
``dpre = g * gelu'(x + b)`` in one pass (db is the row-sum of dpre,
done host-side) — the same recompute-over-materialize trade as the
flash/CE kernels, at elementwise cost.

Parity: the reference's ``fused_bias_gelu`` knob (``torch/nn/gelu.py``,
a hand-written CUDA bias-gelu pair) — the ``DistributedTransformerOutput
Layer`` field now actually dispatches here. The tanh approximation IS
the reference's bias_gelu polynomial (HF "gelu_new"); the exact-erf
variant stays on the jnp path. Interpret-mode fallback on CPU mirrors
``pallas_ce.py`` (``FORCE_INTERPRET`` test hook). Under tensor
parallelism the activation arrives sharded on its feature dim — callers
wrap the call in a tp manual region (``nn/transformer.py``) so the
kernel always sees a local block.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Testing hook, mirroring pallas_ce.FORCE_INTERPRET.
FORCE_INTERPRET = False

_SQRT_2_OVER_PI = float(np.sqrt(2.0 / np.pi))
_COEFF = 0.044715

# Rows per grid step.
_BLOCK_ROWS = 256
# Widest feature tile. Whole rows do not fit the chip's scoped VMEM at real
# MLP widths (v5e compiler: 25-48 MiB asked at F=6400 / 16384 against a
# 16 MiB limit), so the feature dim is tiled too: a (256, 1024) tile is at
# most 1 MiB per fp32 buffer whatever F is.
_BLOCK_COLS = 1024
# An F with no 128-multiple divisor cannot be tiled (a block's last dim is
# a multiple of 128 lanes or the whole dim) and keeps whole rows; it is
# refused when a tile of them would not fit. 32 bytes per element is the
# most the v5e compiler was seen to allocate for the backward kernel
# (double-buffered x, g, dpre plus fp32 temporaries).
_VMEM_BUDGET = 12 * 2**20
_BYTES_PER_ELEMENT = 32


def _feature_block(F):
    """Widest tile of the feature dim: the largest divisor of ``F`` that is
    a multiple of 128 and at most ``_BLOCK_COLS``; ``F`` itself when there
    is none."""
    if F % 128:
        return F
    return max(
        d for d in range(128, min(F, _BLOCK_COLS) + 1, 128) if F % d == 0
    )


def _fits(F):
    return 8 * _feature_block(F) * _BYTES_PER_ELEMENT <= _VMEM_BUDGET


def _row_block(N, bf):
    """Rows per tile: ``_BLOCK_ROWS``, shrunk (in multiples of 8) until a
    tile fits the VMEM budget — only an untileable wide F ever shrinks
    it."""
    fit = _VMEM_BUDGET // (bf * _BYTES_PER_ELEMENT) // 8 * 8
    return max(8, min(_BLOCK_ROWS, fit, -(-N // 8) * 8))


def _gelu_tanh(u):
    inner = _SQRT_2_OVER_PI * (u + _COEFF * u * u * u)
    return 0.5 * u * (1.0 + jnp.tanh(inner))


def _dgelu_tanh(u):
    inner = _SQRT_2_OVER_PI * (u + _COEFF * u * u * u)
    t = jnp.tanh(inner)
    sech2 = 1.0 - t * t
    dinner = _SQRT_2_OVER_PI * (1.0 + 3.0 * _COEFF * u * u)
    return 0.5 * (1.0 + t) + 0.5 * u * sech2 * dinner


def _fwd_kernel(x_ref, b_ref, y_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _gelu_tanh(u).astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, g_ref, dpre_ref):
    u = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dpre_ref[...] = (
        g_ref[...].astype(jnp.float32) * _dgelu_tanh(u)
    ).astype(dpre_ref.dtype)


def _pad_rows(x, n):
    if x.shape[0] == n:
        return x
    return jnp.pad(x, ((0, n - x.shape[0]), (0, 0)))


def _call_tiled(kernel, name, outs_dtype, interpret, x2d, b, *extra):
    N, F = x2d.shape
    bf = _feature_block(F)
    bn = _row_block(N, bf)
    n_pad = -(-N // bn) * bn
    tile = pl.BlockSpec((bn, bf), lambda i, j: (i, j))
    args = [_pad_rows(x2d, n_pad), b.reshape(1, F)]
    in_specs = [tile, pl.BlockSpec((1, bf), lambda i, j: (0, j))]
    for e in extra:
        args.append(_pad_rows(e, n_pad))
        in_specs.append(tile)
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn, F // bf),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_pad, F), outs_dtype),
        name=name,
        interpret=interpret or FORCE_INTERPRET,
    )(*args)
    return out[:N]


def _bias_gelu_impl(x, b, interpret):
    lead = x.shape[:-1]
    x2d = x.reshape(-1, x.shape[-1])
    return _call_tiled(
        _fwd_kernel, "smp_bias_gelu_fwd", x.dtype, interpret, x2d, b
    ).reshape(lead + (x.shape[-1],))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def bias_gelu(x, b, interpret=False):
    """``gelu(x + b)`` (tanh approximation) over ``x [..., F]`` and
    ``b [F]`` in one fused pass. Differentiable in x and b."""
    return _bias_gelu_impl(x, b, interpret)


def _bg_fwd(x, b, interpret):
    return _bias_gelu_impl(x, b, interpret), (x, b)


def _bg_bwd(interpret, res, g):
    x, b = res
    lead = x.shape[:-1]
    F = x.shape[-1]
    dpre = _call_tiled(
        _bwd_kernel, "smp_bias_gelu_bwd", jnp.float32, interpret,
        x.reshape(-1, F), b, g.reshape(-1, F),
    )
    dx = dpre.astype(x.dtype).reshape(lead + (F,))
    db = jnp.sum(dpre, axis=0).astype(b.dtype)
    return dx, db


bias_gelu.defvjp(_bg_fwd, _bg_bwd)


def bias_gelu_ok(activation, features=None):
    """Dispatch precondition: the tanh-GELU family (the reference's
    fused bias_gelu polynomial), a feature dim (``features``, the LOCAL
    width under tp) whose tiles fit VMEM, and the kernel's target backend
    (TPU, or interpret-mode testing)."""
    if activation not in ("gelu", "gelu_new"):
        return False
    if features is not None and not _fits(features):
        return False
    return jax.default_backend() == "tpu" or FORCE_INTERPRET


def reference_bias_gelu(x, b):
    """jnp reference: same math, unfused — the parity oracle (matches
    ``nn.gelu(x + b, approximate=True)`` bit-for-tolerance)."""
    u = x.astype(jnp.float32) + b.astype(jnp.float32)
    return _gelu_tanh(u).astype(x.dtype)
