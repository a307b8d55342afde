"""Fused QKV projection Pallas kernel: matmul with the bias folded into
the epilogue.

The attention layers already express QKV as ONE einsum against a
concatenated [in, 3, heads, head_dim] kernel, but XLA still emits the
bias add as a separate HBM pass over the [*, 3*H*hd] result on shapes it
declines to fuse. This kernel computes ``y = x @ w + b`` tile-by-tile on
the MXU with the bias added while the tile is VMEM-resident — one pass
over the output. Under ``tp_overlap: ring`` the same kernel runs INSIDE
the ring's partial matmuls (``ops/collective_matmul._chunk_mm``), so the
"ring + fusions" rung stacks both wins; on the GSPMD tp path the sharded
weight cannot enter a plain ``pallas_call`` without a gather, so
dispatch there keeps the einsum (``fused_qkv_ok``).

Backward is the standard dense triple (dx = dy @ w^T, dw = x^T @ dy,
db = sum(dy)) as plain XLA matmuls — exact, no recompute trade — behind
a ``custom_vjp`` so the forward kernel never gets differentiated
through. Interpret-mode fallback on CPU mirrors ``pallas_ce.py``
(``FORCE_INTERPRET`` test hook); dispatch off-TPU without it falls back
to the jnp path with a counted decision
(``smp_fused_kernel_dispatch_total``).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Testing hook, mirroring pallas_ce.FORCE_INTERPRET.
FORCE_INTERPRET = False

_VMEM_BUDGET = 12 * 2**20

# (rows, cols) tile candidates, large-first; shrink cols before rows so
# wide contractions (large D) keep a fitting configuration.
_BLOCK_CANDIDATES = (
    (256, 512), (256, 256), (128, 256), (128, 128), (64, 128), (32, 128),
)


def _step_bytes(D, bn, bf):
    # fp32 in-kernel copies: x tile + w tile + y tile (+ bias row).
    return 4 * (bn * D + bf * D + bn * bf + bf)


def _auto_blocks(D):
    for bn, bf in _BLOCK_CANDIDATES:
        if _step_bytes(D, bn, bf) <= _VMEM_BUDGET:
            return bn, bf
    return None


def _mm_bias_kernel(*refs, has_bias):
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    y_ref = next(it)
    x = x_ref[...].astype(jnp.float32)                   # [bn, D]
    w = w_ref[...].astype(jnp.float32)                   # [D, bf]
    y = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if has_bias:
        y = y + b_ref[...].astype(jnp.float32)           # [1, bf]
    y_ref[...] = y.astype(y_ref.dtype)


def _pad_to(x, n, axis):
    if x.shape[axis] == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pads)


def _matmul_bias_impl(x, w, b, interpret):
    N, D = x.shape
    F = w.shape[1]
    blocks = _auto_blocks(D)
    if blocks is None:
        # No tile fits VMEM at this contraction width (fused_qkv_ok
        # steers dispatch away; direct callers get the same math unfused
        # rather than an unpack crash).
        y = x.astype(jnp.float32) @ w.astype(jnp.float32)
        if b is not None:
            y = y + b.astype(jnp.float32)
        return y.astype(x.dtype)
    bn, bf = blocks
    # Few-row calls (decode steps: N = batch) must not pad to the full
    # row tile — cap bn at N rounded to the 32-sublane granule (valid
    # for every dtype's TPU tiling) so a batch-8 decode QKV runs 32
    # rows, not 256.
    bn = min(bn, max(32, -(-N // 32) * 32))
    n_pad = -(-N // bn) * bn
    f_pad = -(-F // bf) * bf
    xp = _pad_to(x, n_pad, 0)
    wp = _pad_to(w, f_pad, 1)
    has_bias = b is not None
    args = [xp, wp]
    in_specs = [
        pl.BlockSpec((bn, D), lambda i, j: (i, 0)),
        pl.BlockSpec((D, bf), lambda i, j: (0, j)),
    ]
    if has_bias:
        args.append(_pad_to(b.reshape(1, F), f_pad, 1))
        in_specs.append(pl.BlockSpec((1, bf), lambda i, j: (0, j)))
    y = pl.pallas_call(
        functools.partial(_mm_bias_kernel, has_bias=has_bias),
        grid=(n_pad // bn, f_pad // bf),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), x.dtype),
        name="smp_matmul_bias",
        interpret=interpret or FORCE_INTERPRET,
    )(*args)
    return y[:N, :F]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _matmul_bias(x, w, b, interpret):
    return _matmul_bias_impl(x, w, b, interpret)


def _mb_fwd(x, w, b, interpret):
    return _matmul_bias_impl(x, w, b, interpret), (x, w, b is not None)


def _mb_bwd(interpret, res, dy):
    x, w, had_bias = res
    dyf = dy.astype(jnp.float32)
    dx = (dyf @ w.astype(jnp.float32).T).astype(x.dtype)
    dw = (x.astype(jnp.float32).T @ dyf).astype(w.dtype)
    db = jnp.sum(dyf, axis=0).astype(dy.dtype) if had_bias else None
    return dx, dw, db


_matmul_bias.defvjp(_mb_fwd, _mb_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_nobias(x, w, interpret):
    return _matmul_bias_impl(x, w, None, interpret)


_matmul_nobias.defvjp(
    lambda x, w, interpret: (_matmul_bias_impl(x, w, None, interpret),
                             (x, w)),
    lambda interpret, res, dy: _mb_bwd(interpret, res + (False,), dy)[:2],
)


def matmul_bias(x, w, b=None, *, interpret=False):
    """``x [N, D] @ w [D, F] (+ b [F])`` through the fused Pallas kernel
    (bias in the matmul epilogue, one output pass). Differentiable in
    x/w/b; the backward is exact plain-XLA matmuls."""
    if b is not None:
        return _matmul_bias(x, w, b.reshape(-1), interpret)
    return _matmul_nobias(x, w, interpret)


def _mm_fp8_kernel(x_ref, w_ref, y_ref):
    # Operands stay f8 INTO the dot — the MXU consumes them natively on
    # f8-capable TPUs; preferred_element_type pins the f32 accumulator.
    y_ref[...] = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def matmul_bias_fp8(x8, w8, *, interpret=False):
    """The fp8 rung of this kernel ladder (matmul_precision: fp8):
    ``x8 [N, D] @ w8 [D, F] -> f32`` with e4m3 operand refs — the
    delayed-scaling dequant multiply and the bias add stay in the XLA
    epilogue (``quant._fp8_mm2d``), keeping the kernel a pure f8 MXU
    pass. Not differentiable on its own: the caller's custom_vjp owns
    the e5m2 backward. The (32, 128) floor of ``_BLOCK_CANDIDATES``
    satisfies the f8 minimum tile; an unfittable contraction width
    falls back to the plain f8 dot (same operands, XLA-tiled)."""
    N, D = x8.shape
    F = w8.shape[1]
    blocks = _auto_blocks(D)
    if blocks is None:
        return jax.lax.dot_general(
            x8, w8, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    bn, bf = blocks
    bn = min(bn, max(32, -(-N // 32) * 32))
    n_pad = -(-N // bn) * bn
    f_pad = -(-F // bf) * bf
    xp = _pad_to(x8, n_pad, 0)
    wp = _pad_to(w8, f_pad, 1)
    y = pl.pallas_call(
        _mm_fp8_kernel,
        grid=(n_pad // bn, f_pad // bf),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i, j: (i, 0)),
            pl.BlockSpec((D, bf), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        name="smp_matmul_fp8",
        interpret=interpret or FORCE_INTERPRET,
    )(xp, wp)
    return y[:N, :F]


def fused_qkv_ok(D, ring=False, tp=1):
    """Dispatch precondition for the fused QKV projection: the knob's
    target backend (TPU, or interpret-mode testing), a fitting tile
    configuration, and — at tp > 1 — the ring path (a tp-sharded weight
    cannot enter a plain ``pallas_call``; the ring's manual region hands
    the kernel its local shard)."""
    if jax.default_backend() != "tpu" and not FORCE_INTERPRET:
        return False
    if _auto_blocks(D) is None:
        return False
    if tp > 1 and not ring:
        return False
    return True


def reference_matmul_bias(x, w, b=None):
    """jnp reference: same math, materialized — the parity oracle."""
    y = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if b is not None:
        y = y + b.reshape(-1).astype(jnp.float32)
    return y.astype(x.dtype)
