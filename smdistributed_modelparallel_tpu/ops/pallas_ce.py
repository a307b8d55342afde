"""Fused LM-head cross-entropy Pallas kernels.

TPU extension targeting the known single-chip MFU gap: with a tied LM
head, ``loss = CE(hidden @ emb^T, targets)`` materializes an [N, V]
logits tensor in HBM (GPT-2-124M at B*T=8k tokens: ~800 MB bf16, plus
fp32 casts) that is written once and read twice per step — XLA cannot
eliminate an explicit intermediate. These kernels tile BOTH the row and
the vocab dimension into the Pallas grid (vocab is the inner, sequential
grid axis, so per-row online-softmax state accumulates in revisited
output blocks that stay VMEM-resident) and never materialize logits:

- forward: per (row-block, vocab-block) grid step, one
  ``x_blk @ W_blk^T`` MXU matmul feeding an online max/sum-exp and a
  one-hot-free target-logit pick; outputs per-row (running max, sum-exp,
  target logit), finalized to lse on the host side.
- backward: the standard softmax-minus-one-hot cotangent, recomputed
  blockwise from the saved per-row lse and contracted immediately into
  dx (rows outer, vocab inner) and dW (vocab outer, rows inner) — +1
  recompute matmul pass in exchange for eliminating all [N, V] HBM
  traffic, the same trade the flash attention kernels make.

VMEM per grid step is O(block_n*D + block_v*D + block_n*block_v), NOT
O(V*D) — the full embedding table is never staged (GPT-2's table alone
is ~5x VMEM).

No reference counterpart (SURVEY §2.1 N8 covers fused softmax only);
this is a new-capability op. Layout: x [N, D], W [V, D] (embedding-table
layout; the tied head computes x @ W^T), targets int32 [N].
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl


NEG_INF = -1e30

# Testing hook, mirroring pallas_attention.FORCE_INTERPRET.
FORCE_INTERPRET = False


def _fwd_kernel(*refs, block_v, v_total, smoothing):
    it = iter(refs)
    x_ref, w_ref, t_ref = next(it), next(it), next(it)
    m_ref, l_ref, tgt_ref = next(it), next(it), next(it)
    sum_ref = next(it) if smoothing else None
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        tgt_ref[...] = jnp.zeros(tgt_ref.shape, jnp.float32)
        if smoothing:
            sum_ref[...] = jnp.zeros(sum_ref.shape, jnp.float32)

    x = x_ref[...].astype(jnp.float32)                  # [bn, D]
    w = w_ref[...].astype(jnp.float32)                  # [bv, D]
    tids = t_ref[...].reshape(-1, 1)                    # [bn, 1]
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # [bn, bv]
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(cols < v_total, logits, NEG_INF)

    m_prev = m_ref[...].reshape(-1, 1)
    l_prev = l_ref[...].reshape(-1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(
        jnp.exp(logits - m_new), axis=-1, keepdims=True
    )
    # Target pick: at most one column of this block matches each row's
    # target id; a masked row-sum extracts it without a gather.
    hit = cols == tids
    tgt_add = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
    m_ref[...] = m_new.reshape(m_ref.shape)
    l_ref[...] = l_new.reshape(l_ref.shape)
    tgt_ref[...] = tgt_ref[...] + tgt_add.reshape(tgt_ref.shape)
    if smoothing:
        # Valid-column logit row-sums feed the label-smoothing term
        # (loss += eps * (lse - mean(logits))); padded columns hold
        # NEG_INF and are excluded.
        valid = cols < v_total
        sum_ref[...] = sum_ref[...] + jnp.sum(
            jnp.where(valid, logits, 0.0), axis=-1
        ).reshape(sum_ref.shape)


def _bwd_dx_kernel(x_ref, w_ref, t_ref, lse_ref, g_ref, dx_ref, *, block_v,
                   v_total, smoothing, smooth_denom=None):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros(dx_ref.shape, dx_ref.dtype)

    x = x_ref[...].astype(jnp.float32)                  # [bn, D]
    w = w_ref[...].astype(jnp.float32)                  # [bv, D]
    tids = t_ref[...].reshape(-1, 1)
    lse = lse_ref[...].reshape(-1, 1)
    g = g_ref[...].reshape(-1, 1)
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid = cols < v_total
    p = jnp.where(valid, jnp.exp(logits - lse), 0.0)
    target_mass = (cols == tids).astype(jnp.float32)
    if smoothing:
        # dloss/dlogit = p - (1-eps)*onehot - eps/V on valid columns.
        # Under vocab sharding (tp) the denominator is the GLOBAL vocab
        # while the valid mask covers only the local shard.
        target_mass = (1.0 - smoothing) * target_mass + jnp.where(
            valid, smoothing / (smooth_denom or v_total), 0.0
        )
    dlog = (p - target_mass) * g
    dx_ref[...] = dx_ref[...] + jax.lax.dot_general(
        dlog, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dx_ref.dtype)


def _bwd_dw_kernel(x_ref, w_ref, t_ref, lse_ref, g_ref, dw_ref, *, block_n,
                   block_v, n_total, v_total, smoothing, smooth_denom=None):
    j = pl.program_id(0)                                # vocab block (outer)
    i = pl.program_id(1)                                # row block (inner)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    x = x_ref[...].astype(jnp.float32)                  # [bn, D]
    w = w_ref[...].astype(jnp.float32)                  # [bv, D]
    tids = t_ref[...].reshape(-1, 1)
    lse = lse_ref[...].reshape(-1, 1)
    g = g_ref[...].reshape(-1, 1)
    rows = i * block_n + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], 1), 0
    )
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_v), 1
    )
    logits = jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                   # [bn, bv]
    p = jnp.exp(logits - lse)
    target_mass = (cols == tids).astype(jnp.float32)
    if smoothing:
        # All columns of a dW program's block are valid (v_pad slicing
        # happens host-side), but guard like the dx kernel for symmetry.
        target_mass = (1.0 - smoothing) * target_mass + jnp.where(
            cols < v_total, smoothing / (smooth_denom or v_total), 0.0
        )
    dlog = (p - target_mass) * g
    # Padded rows carry g=0 already (their loss cotangent is zero), but
    # guard anyway: their lse is a filler value.
    dlog = jnp.where(rows < n_total, dlog, 0.0)
    dw_ref[...] = dw_ref[...] + jax.lax.dot_general(
        dlog, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dw_ref.dtype)


def _pad_to(x, n, axis, value=0):
    if x.shape[axis] == n:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pads, constant_values=value)


def _blocks(N, V, block_n, block_v):
    block_n = min(block_n, max(8, N))
    block_v = min(block_v, V)
    n_pad = -(-N // block_n) * block_n
    v_pad = -(-V // block_v) * block_v
    return block_n, block_v, n_pad, v_pad


def _fused_ce_fwd_impl(x, w, targets, block_n, block_v, interpret,
                       smoothing=0.0):
    N, D = x.shape
    V = w.shape[0]
    block_n, block_v, n_pad, v_pad = _blocks(N, V, block_n, block_v)
    xp = _pad_to(x, n_pad, 0)
    wp = _pad_to(w, v_pad, 0)
    tp = _pad_to(targets.astype(jnp.int32), n_pad, 0)[None, :]
    kern = functools.partial(_fwd_kernel, block_v=block_v, v_total=V,
                             smoothing=smoothing)
    row = pl.BlockSpec((1, block_n), lambda i, j: (0, i))
    n_out = 4 if smoothing else 3
    outs = pl.pallas_call(
        kern,
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, D), lambda i, j: (j, 0)),
            row,
        ],
        out_specs=[row] * n_out,
        out_shape=[
            jax.ShapeDtypeStruct((1, n_pad), jnp.float32)
            for _ in range(n_out)
        ],
        name="smp_ce_fwd",
        interpret=interpret or FORCE_INTERPRET,
    )(xp, wp, tp)
    m, l, tgt = outs[0], outs[1], outs[2]
    lse = m[0, :N] + jnp.log(jnp.maximum(l[0, :N], 1e-30))
    logit_sum = outs[3][0, :N] if smoothing else None
    return lse, tgt[0, :N], logit_sum


def _fused_ce_bwd_impl(x, w, targets, lse, g, block_n, block_v, interpret,
                       smoothing=0.0, smooth_denom=None):
    N, D = x.shape
    V = w.shape[0]
    block_n, block_v, n_pad, v_pad = _blocks(N, V, block_n, block_v)
    xp = _pad_to(x, n_pad, 0)
    wp = _pad_to(w, v_pad, 0)
    tp = _pad_to(targets.astype(jnp.int32), n_pad, 0)[None, :]
    # Padded rows: lse filler keeps exp() finite; g = 0 kills their grads.
    lsep = _pad_to(lse.astype(jnp.float32), n_pad, 0, value=1.0)[None, :]
    gp = _pad_to(g.astype(jnp.float32), n_pad, 0)[None, :]
    interp = interpret or FORCE_INTERPRET
    row_i = pl.BlockSpec((1, block_n), lambda i, j: (0, i))

    dx = pl.pallas_call(
        functools.partial(_bwd_dx_kernel, block_v=block_v, v_total=V,
                          smoothing=smoothing, smooth_denom=smooth_denom),
        grid=(n_pad // block_n, v_pad // block_v),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda i, j: (i, 0)),
            pl.BlockSpec((block_v, D), lambda i, j: (j, 0)),
            row_i, row_i, row_i,
        ],
        out_specs=pl.BlockSpec((block_n, D), lambda i, j: (i, 0)),
        # fp32 accumulator: the block is revisited across the vocab sweep;
        # accumulating ~V/block_v partial sums in bf16 would round.
        out_shape=jax.ShapeDtypeStruct((n_pad, D), jnp.float32),
        name="smp_ce_bwd_dx",
        interpret=interp,
    )(xp, wp, tp, lsep, gp)

    # dW grid: vocab outer, rows inner — the dW block is revisited across
    # the inner row sweep.
    row_j = pl.BlockSpec((1, block_n), lambda j, i: (0, i))
    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_n=block_n, block_v=block_v,
                          n_total=N, v_total=V, smoothing=smoothing,
                          smooth_denom=smooth_denom),
        grid=(v_pad // block_v, n_pad // block_n),
        in_specs=[
            pl.BlockSpec((block_n, D), lambda j, i: (i, 0)),
            pl.BlockSpec((block_v, D), lambda j, i: (j, 0)),
            row_j, row_j, row_j,
        ],
        out_specs=pl.BlockSpec((block_v, D), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((v_pad, D), jnp.float32),
        name="smp_ce_bwd_dw",
        interpret=interp,
    )(xp, wp, tp, lsep, gp)
    return dx[:N].astype(x.dtype), dw[:V].astype(w.dtype)


def _assemble_loss(lse, tgt, logit_sum, V, smoothing):
    if not smoothing:
        return lse - tgt
    # loss = (1-eps)*(lse - tgt) + eps*(lse - mean(logits))
    #      = lse - (1-eps)*tgt - (eps/V)*sum(logits)
    return lse - (1.0 - smoothing) * tgt - (smoothing / V) * logit_sum


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def fused_lm_head_ce(x, w, targets, block_n=256, block_v=1024,
                     interpret=False, label_smoothing=0.0):
    """Per-token CE of ``x @ w^T`` against ``targets`` without
    materializing logits. x: [N, D]; w: [V, D]; targets: [N] int.
    ``label_smoothing``: HF/T5-convention uniform smoothing
    (eps * mean-over-vocab NLL mixed in). Returns fp32 [N] losses.
    Differentiable in x and w.
    """
    lse, tgt, ls = _fused_ce_fwd_impl(
        x, w, targets, block_n, block_v, interpret, label_smoothing
    )
    return _assemble_loss(lse, tgt, ls, w.shape[0], label_smoothing)


def _fce_fwd(x, w, targets, block_n, block_v, interpret, label_smoothing):
    lse, tgt, ls = _fused_ce_fwd_impl(
        x, w, targets, block_n, block_v, interpret, label_smoothing
    )
    loss = _assemble_loss(lse, tgt, ls, w.shape[0], label_smoothing)
    return loss, (x, w, targets, lse)


def _fce_bwd(block_n, block_v, interpret, label_smoothing, res, g):
    x, w, targets, lse = res
    dx, dw = _fused_ce_bwd_impl(
        x, w, targets, lse, g, block_n, block_v, interpret, label_smoothing
    )
    return dx, dw, None


fused_lm_head_ce.defvjp(_fce_fwd, _fce_bwd)


@functools.lru_cache(maxsize=32)
def make_vocab_parallel_fused_ce(mesh, v_global, block_n, block_v,
                                 interpret, smoothing, axis_name="tp"):
    """Vocab-parallel fused CE (the Megatron composition of
    ``nn/cross_entropy.py``, fused): returns ``ce(x, w, targets)`` for a
    [V, D] table sharded over ``axis_name`` on the given mesh.

    Each shard runs the blockwise kernels on its LOCAL [V/tp, D] table
    slice with targets shifted into local coordinates (out-of-range
    targets simply never hit). The custom_vjp lives at GSPMD level;
    shard_map appears only INSIDE its fwd/bwd implementations (the
    manual regions are never differentiated through, so no dependence on
    shard_map's replicated-cotangent transpose rules):

    - fwd: a tp manual region emits per-shard (lse, target-logit,
      smoothing-sum) stacked on a leading shard axis; the stable
      log-sum-exp merge and loss assembly happen outside (small GSPMD
      collectives) — exactly the allreduce(max)/allreduce(sum) pair the
      materialized path codes (reference ``torch/nn/cross_entropy.py:
      28-112``).
    - bwd: a second manual region recomputes logit blocks per shard from
      the GLOBAL lse, contracting immediately into a psum'd dx
      (replicated out) and a vocab-sharded dW. Smoothing's eps/V term
      uses the GLOBAL vocab; the valid-column mask is local.
    """
    from jax.sharding import PartitionSpec as P

    def _shift(t, v_local):
        me = jax.lax.axis_index(axis_name)
        return t.astype(jnp.int32) - me * v_local

    def stats_body(x, w_local, t):
        lse_l, tgt_l, sum_l = _fused_ce_fwd_impl(
            x, w_local, _shift(t, w_local.shape[0]),
            block_n, block_v, interpret, smoothing,
        )
        if sum_l is None:
            sum_l = jnp.zeros_like(lse_l)
        return lse_l[None], tgt_l[None], sum_l[None]   # [1, N] per shard

    stats_fn = jax.shard_map(
        stats_body, mesh=mesh,
        in_specs=(P(), P(axis_name, None), P()),
        out_specs=(P(axis_name, None),) * 3,
        axis_names={axis_name},
        check_vma=False,
    )

    def bwd_body(x, w_local, t, lse_g, g):
        dx_l, dw_l = _fused_ce_bwd_impl(
            x, w_local, _shift(t, w_local.shape[0]), lse_g, g,
            block_n, block_v, interpret, smoothing,
            smooth_denom=v_global,
        )
        # dx sums vocab-shard contributions -> identical across the axis,
        # so the unmapped out_spec is sound; dW stays vocab-sharded.
        dx = jax.lax.psum(dx_l.astype(jnp.float32), axis_name)
        return dx, dw_l

    bwd_fn = jax.shard_map(
        bwd_body, mesh=mesh,
        in_specs=(P(), P(axis_name, None), P(), P(), P()),
        out_specs=(P(), P(axis_name, None)),
        axis_names={axis_name},
        check_vma=False,
    )

    def fwd_impl(x, w, t):
        lse_s, tgt_s, sum_s = stats_fn(x, w, t)        # [tp, N]
        m_g = jnp.max(lse_s, axis=0)
        z = jnp.sum(jnp.exp(lse_s - m_g[None]), axis=0)
        lse_g = m_g + jnp.log(jnp.maximum(z, 1e-30))
        tgt_g = jnp.sum(tgt_s, axis=0)
        sum_g = jnp.sum(sum_s, axis=0) if smoothing else None
        loss = _assemble_loss(lse_g, tgt_g, sum_g, v_global, smoothing)
        return loss, (x, w, t, lse_g)

    @jax.custom_vjp
    def ce(x, w, t):
        return fwd_impl(x, w, t)[0]

    def bwd(res, g):
        x, w, t, lse_g = res
        dx, dw = bwd_fn(x, w, t, lse_g, g.astype(jnp.float32))
        return dx.astype(x.dtype), dw.astype(w.dtype), None

    ce.defvjp(fwd_impl, bwd)
    return jax.jit(ce)


def _step_bytes(D, block_n, block_v):
    # fp32 in-kernel copies: x_blk + w_blk + logits + dx/dw accumulator.
    return 4 * (block_n * D + block_v * D + block_n * block_v
                + max(block_n, block_v) * D)


# Budget for ``_step_bytes``, held against the chip's compiler: the v5e
# allows a kernel 16 MiB of scoped VMEM, and the dw backward kernel asks
# about 1.45x what ``_step_bytes`` counts (16.6-17.8 MiB where the count
# was 11.5-12.0). 10.5 MiB keeps the largest accepted blocks near 15 MiB.
_VMEM_BUDGET = int(10.5 * 2**20)

# Preference order: large vocab blocks amortize the row re-reads; shrink
# block_v first (it multiplies D in three of the four VMEM terms), then
# block_n, so wide models (large D) still get a fitting configuration
# instead of losing the kernel entirely. block_n stops at 128: the
# per-row vectors (targets, lse) are [1, N] blocks whose last dim must be
# a multiple of 128 lanes, so narrower row blocks do not lower on the TPU.
_BLOCK_CANDIDATES = (
    (256, 1024), (256, 512), (128, 512), (128, 256), (128, 128),
)


def auto_blocks(D, block_n=None, block_v=None):
    """Pick (block_n, block_v) whose working set fits the VMEM budget.

    Explicit ``block_n``/``block_v`` are honored when they fit; a
    partially-specified call pins the given dimension and picks the other
    from the candidate list. Returns None when nothing fits
    (pathologically wide D) — callers treat that as "kernel
    unavailable"."""
    if block_n is not None and block_v is not None:
        return (
            (block_n, block_v)
            if _step_bytes(D, block_n, block_v) <= _VMEM_BUDGET else None
        )
    for bn, bv in _BLOCK_CANDIDATES:
        bn = block_n if block_n is not None else bn
        bv = block_v if block_v is not None else bv
        if _step_bytes(D, bn, bv) <= _VMEM_BUDGET:
            return bn, bv
    return None


def fused_ce_ok(x, w, block_n=None, block_v=None):
    """Dispatch precondition: TPU backend (or interpret-mode testing) and
    a block configuration whose working set fits VMEM (``auto_blocks``
    shrinks blocks for wide D); the caller guards vocab sharding.
    SMP_DISABLE_FUSED_CE=1 is the operator escape hatch."""
    import os

    if os.environ.get("SMP_DISABLE_FUSED_CE", "0") == "1":
        return False
    if jax.default_backend() != "tpu" and not FORCE_INTERPRET:
        return False
    return auto_blocks(x.shape[-1], block_n, block_v) is not None


def reference_lm_head_ce(x, w, targets):
    """jnp reference: same math through materialized logits (the fallback
    path and the parity oracle)."""
    logits = (x.astype(jnp.float32) @ w.astype(jnp.float32).T)
    m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[:, 0]
    tgt = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return lse - tgt
