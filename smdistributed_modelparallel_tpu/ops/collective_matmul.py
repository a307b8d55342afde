"""Ring-decomposed collective matmuls — overlapped tensor parallelism.

The GSPMD tp layers (``nn/linear.py``, ``nn/transformer.py``) express the
Megatron collectives as sharding constraints and let XLA insert
synchronous all-gather / reduce-scatter / all-reduce instructions around
the matmuls (GSPMD, arXiv 2105.04663). Those collectives sit on the
critical path: the matmul cannot start until the gather completes, and
the reduce cannot start until the matmul does. The pjit/TPUv4 paper
(arXiv 2204.06514, §3.3 "overlapping communication with computation")
attributes a large fraction of its MFU headroom to DECOMPOSING exactly
these collectives into per-shard steps whose transfers hide under the
partial matmuls — the "collective matmul" transformation.

``tp_overlap: "ring"`` (env alias ``SMP_TP_OVERLAP``) applies that
transformation here, with the same building blocks the repo already
trusts:

- the ``ops/context_parallel.py`` ring pattern: a full-manual
  ``shard_map`` region over the tp axis whose body rotates blocks with
  ``lax.ppermute`` (point-to-point ICI neighbor traffic);
- the PR-5/PR-12 transfer-register trick: each ring hop is issued
  BEFORE the partial matmul on the block already in hand, tied together
  with an ``optimization_barrier`` (wrapped in a ``custom_vjp`` identity
  so the scheduling pin never enters the transpose program) and parked
  in the loop carry — the X-ray's ``tp_ring_evidence`` proves the hop
  feeds only data movement into the next step's matmul operand;
- GSPMD-level ``custom_vjp`` (the ``pallas_ce.py`` composition): the
  manual regions appear only inside the fwd/bwd implementations and are
  never differentiated through — the backward ring runs the mirrored
  decomposition explicitly.

Two primitives cover the transformer block family:

- ``ring_ag_matmul`` — column-parallel layer consuming a
  SEQUENCE-sharded input: ``y = allgather_seq(x) @ W`` with W sharded on
  an output dim. The ring rotates x's sequence blocks; each step matmuls
  the block in hand against the local weight shard while the next hop is
  in flight. Backward: one ring rotating x re-derives dW per block while
  a second accumulator ring reduce-scatters dx — the mirrored
  decomposition, two permutes per step like the forward's one plus the
  saved gather.
- ``ring_rs_matmul`` — row-parallel layer producing a SEQUENCE-sharded
  output: ``y = reduce_scatter_seq(x @ W)`` with x sharded on a
  contraction dim. The ring rotates the accumulator; each step adds the
  local partial for the chunk in transit. Backward: one ring rotating dy
  blocks computes dx (all-gather-matmul) and dW per block.

Together a [col -> elementwise -> row] block (attention QKV..proj, MLP
fc..proj) runs with ZERO tp-axis all-gather/reduce-scatter instructions
— only tp-attributed collective-permutes — which is exactly what the
``tp_overlap`` fingerprint block gates.

Hop-count note: each ring's fori_loop issues tp hops where the ring
algorithm needs tp-1 — the final iteration's hop is parked in the carry
and dropped at loop exit. That last transfer rides under the final
partial matmul like every other hop, so it costs ICI bandwidth during
that matmul, never latency (tp/(tp-1) extra permute bytes; 2x at tp=2).
It is deliberate: hoisting the last chunk into a loop epilogue would
drop the trip count to tp-1, and at the gated tp=2 tier XLA's
trip-count-1 while-loop simplifier then inlines the body — erasing the
very loop-carry structure ``tp_ring_evidence`` proves double-buffering
by. Revisit if a tp>4 profile shows the tail hop contending.

Multi-axis caveat: the ring regions currently own ONLY the tp axis —
the entry constraints spec tp alone (non-tp dims pinned replicated) and
the in/out specs name no batch axes, so on a dp x tp mesh activations
replicate over dp around every ring matmul. On a pure-tp mesh (the tp=2
parity/golden tier) this is exact and free; on multi-axis meshes it is
semantically correct but pays dp gather traffic + replicated activation
memory — making the rings batch-sharded (lead-dim axes in the specs and
axis_names) is the ROADMAP follow-up before ring defaults on for dp x tp
jobs. The CPU tier additionally serializes the ring hops, so CPU A/B
timings only prove plumbing; the rings have not been measured on the chip.
"""

import functools

import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import TP_AXIS
from smdistributed_modelparallel_tpu.utils.logger import get_logger

from smdistributed_modelparallel_tpu.parallel.sharding import (
    manual_axes,
    single_axis_spec,
)

logger = get_logger()

OVERLAP_ENV = "SMP_TP_OVERLAP"

# One warning per distinct (reason, detail) when the ring path is
# requested but cannot engage and dispatch falls back to GSPMD.
_FALLBACK_WARNED = set()


def _warn_once(key, msg, *args):
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    logger.warning(msg, *args)


def tp_overlap_mode(cfg=None):
    """The effective tp_overlap mode: the config knob, canonicalized to
    "off" whenever it cannot change the program (tp degree 1, cp > 1 —
    the ring owns the sequence axis and does not compose with cp's
    sequence sharding). Keyed into the step cache / exec-cache knob
    facts in this canonical form so an idle knob never moves a key."""
    cfg = cfg if cfg is not None else state.cfg
    if cfg is None:
        return "off"
    mode = getattr(cfg, "tp_overlap", "off") or "off"
    if mode == "off":
        return "off"
    if getattr(cfg, "tensor_parallel_degree", 1) <= 1:
        return "off"
    if getattr(cfg, "context_parallel_degree", 1) > 1:
        _warn_once(
            ("cp", mode),
            "tp_overlap=%s requested with context_parallel_degree > 1; "
            "the ring owns the sequence axis and does not compose with "
            "cp — keeping the GSPMD tp path.", mode,
        )
        return "off"
    return mode


def fused_qkv_effective(cfg=None):
    """The cache-key-canonical fused_qkv knob: the config flag,
    canonicalized to False whenever CONFIG alone proves it cannot change
    the program — ``use_pallas_kernels`` disabled, or tp > 1 without the
    ring (``pallas_qkv.fused_qkv_ok`` never passes there; the GSPMD tp
    path keeps the einsum). Same discipline as ``tp_overlap_mode``: an
    idle knob never moves a key. Deliberately config-only: the kernel's
    backend/VMEM preconditions stay OUT of the canonicalization so keys
    never depend on the live backend."""
    cfg = cfg if cfg is not None else state.cfg
    if cfg is None or not bool(getattr(cfg, "fused_qkv", False)):
        return False
    if not bool(getattr(cfg, "use_pallas_kernels", True)):
        return False
    tp = getattr(cfg, "tensor_parallel_degree", 1) or 1
    return tp <= 1 or tp_overlap_mode(cfg) == "ring"


def tp_overlap_active():
    """Whether the tp layers should take the ring path right now: knob
    resolved to "ring" and an initialized mesh with a nontrivial tp
    axis."""
    if tp_overlap_mode() != "ring":
        return False
    if not state.initialized or state.mesh is None:
        return False
    return state.mesh.shape.get(TP_AXIS, 1) > 1


# ----------------------------------------------------------------------
# The transfer-register barrier (PR-5 / PR-12 trick): ties the in-flight
# hop to the operand of the current partial matmul so XLA cannot sink
# the ppermute below the compute it should overlap. Identity on both
# operands; custom_vjp keeps the pin out of the transpose program (the
# backward builds its own mirrored rings with their own pins).
# ----------------------------------------------------------------------


@jax.custom_vjp
def _issue_before(nxt, cur):
    return jax.lax.optimization_barrier((nxt, cur))


def _issue_fwd(nxt, cur):
    return _issue_before(nxt, cur), None


def _issue_bwd(_, ct):
    return ct


_issue_before.defvjp(_issue_fwd, _issue_bwd)


def _chunk_mm(a, w2d, bias, use_pallas, interpret):
    """One partial matmul of the ring: ``a @ w2d (+ bias)`` contracting
    a's last dim. ``use_pallas`` routes through the fused matmul+bias
    kernel (``ops/pallas_qkv.py``) — the "ring + fusions" rung."""
    lead = a.shape[:-1]
    if use_pallas:
        from smdistributed_modelparallel_tpu.ops.pallas_qkv import (
            matmul_bias,
        )

        out = matmul_bias(
            a.reshape(-1, a.shape[-1]), w2d, bias, interpret=interpret
        )
        return out.reshape(lead + (w2d.shape[-1],))
    out = jnp.matmul(a, w2d)
    if bias is not None:
        out = out + bias
    return out


def _maybe_fp8_operands(x, w, site):
    """The ring's fp8 seam (matmul_precision: fp8): round both operands
    to the fp8 grid with the site's delayed scales at the RING BOUNDARY
    — inside, the shard_map/fori_loop bodies trace separately, so amax
    observations recorded there could never escape to the step's
    QuantState. Operand-level fp8: the partial matmuls consume the
    e4m3-gridded values (exactly the values a native-f8 MXU pass would
    see), the f32 ring accumulators and the mirrored backward stay as
    built. No-op outside a quant step trace."""
    from smdistributed_modelparallel_tpu import quant

    if not quant.fp8_trace_active():
        return x, w
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_quant_dispatch,
    )

    record_quant_dispatch(site, "fp8")
    return (
        quant.fake_quant(x, site + ".x"),
        quant.fake_quant(w, site + ".w"),
    )


# ----------------------------------------------------------------------
# ring all-gather matmul (column-parallel)
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _build_ag(mesh, tp, x_ndim, w_ndim, w_tp_dim, has_bias, use_pallas,
              interpret, manual, axis_name=TP_AXIS):
    """custom_vjp ``allgather_seq(x) @ w`` with the gather decomposed
    into a tp-step ring. x: [*lead, S, D] sequence-sharded over tp;
    w: [D, *out] with tp on ``w_tp_dim``; bias (optional): w.shape[1:]
    with tp on ``w_tp_dim - 1``. Output [*lead, S, *out], tp on the out
    dim. See module docstring for the decomposition."""
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    seq_dim = x_ndim - 2

    def fwd_body(x, w, b):
        # Local blocks: x [*lead, Sl, D]; w has its tp dim divided.
        Sl = x.shape[seq_dim]
        wl = w.reshape(w.shape[0], -1)                   # [D, Fl]
        bl = b.reshape(-1) if b is not None else None
        me = jax.lax.axis_index(axis_name)
        y0 = jnp.zeros(
            x.shape[:seq_dim] + (Sl * tp, wl.shape[1]), x.dtype
        )

        def body(i, carry):
            y, x_cur = carry
            # Issue the hop FIRST, then pin it next to the matmul operand
            # so the transfer rides under the partial matmul. The hopped
            # block is PARKED in the carry — consumed only by the next
            # iteration's matmul (tp_ring_evidence proves this
            # structurally in the compiled program).
            x_nxt = jax.lax.ppermute(x_cur, axis_name, perm)
            x_nxt, x_cur = _issue_before(x_nxt, x_cur)
            chunk = _chunk_mm(x_cur, wl, bl, use_pallas, interpret)
            src = (me - i) % tp           # whose sequence block we hold
            y = jax.lax.dynamic_update_slice_in_dim(
                y, chunk.astype(y.dtype), src * Sl, axis=seq_dim
            )
            return (y, x_nxt)

        y, _ = jax.lax.fori_loop(0, tp, body, (y0, x))
        return y.reshape(
            x.shape[:seq_dim] + (Sl * tp,) + w.shape[1:]
        )

    def bwd_body(x, w, dy):
        # The mirrored decomposition: x blocks re-rotate to accumulate
        # dW per sequence block while a second ring reduce-scatters dx.
        Sl = x.shape[seq_dim]
        D = x.shape[-1]
        wl = w.reshape(D, -1)
        dyl = dy.reshape(dy.shape[:seq_dim] + (Sl * tp, wl.shape[1]))
        me = jax.lax.axis_index(axis_name)
        dw0 = jnp.zeros(wl.shape, jnp.float32)
        dx0 = jnp.zeros(x.shape, jnp.float32)

        def body(i, carry):
            x_cur, dacc, dwl = carry
            x_nxt = jax.lax.ppermute(x_cur, axis_name, perm)
            x_nxt, x_cur = _issue_before(x_nxt, x_cur)
            src = (me - i) % tp
            dy_src = jax.lax.dynamic_slice_in_dim(
                dyl, src * Sl, Sl, axis=seq_dim
            )
            dwl = dwl + jnp.matmul(
                x_cur.reshape(-1, D).T.astype(jnp.float32),
                dy_src.reshape(-1, wl.shape[1]).astype(jnp.float32),
            )
            # dx reduce-scatter ring: the accumulator hops first (chunk
            # (me - i - 1) is in transit), then gains this device's
            # partial for it.
            dacc = jax.lax.ppermute(dacc, axis_name, perm)
            c = (me - i - 1) % tp
            dy_c = jax.lax.dynamic_slice_in_dim(
                dyl, c * Sl, Sl, axis=seq_dim
            )
            dacc = dacc + jnp.matmul(dy_c, wl.T).astype(jnp.float32)
            return (x_nxt, dacc, dwl)

        _, dx, dwl = jax.lax.fori_loop(0, tp, body, (x, dx0, dw0))
        dw = dwl.reshape(w.shape).astype(w.dtype)
        grads = (dx.astype(x.dtype), dw)
        if has_bias:
            db = jnp.sum(
                dyl.astype(jnp.float32),
                axis=tuple(range(dyl.ndim - 1)),
            )
            grads = grads + (db.reshape(w.shape[1:]).astype(dy.dtype),)
        return grads

    x_spec = single_axis_spec(x_ndim, seq_dim, axis_name)
    w_spec = single_axis_spec(w_ndim, w_tp_dim, axis_name)
    # Output dims: [*lead(seq_dim), S, *w.shape[1:]] — w dim k lands at
    # output dim seq_dim + k.
    out_spec = single_axis_spec(
        seq_dim + w_ndim, seq_dim + w_tp_dim, axis_name
    )
    b_spec = single_axis_spec(w_ndim - 1, w_tp_dim - 1, axis_name)

    fwd_specs = (x_spec, w_spec) + ((b_spec,) if has_bias else ())
    fwd_fn = jax.shard_map(
        (lambda x, w, b: fwd_body(x, w, b)) if has_bias
        else (lambda x, w: fwd_body(x, w, None)),
        mesh=mesh, in_specs=fwd_specs, out_specs=out_spec,
        axis_names=manual, check_vma=False,
    )
    bwd_out = (x_spec, w_spec) + ((b_spec,) if has_bias else ())
    bwd_fn = jax.shard_map(
        bwd_body, mesh=mesh, in_specs=(x_spec, w_spec, out_spec),
        out_specs=bwd_out, axis_names=manual, check_vma=False,
    )

    if has_bias:
        @jax.custom_vjp
        def ag(x, w, b):
            return fwd_fn(x, w, b)

        ag.defvjp(
            lambda x, w, b: (fwd_fn(x, w, b), (x, w)),
            lambda res, dy: bwd_fn(res[0], res[1], dy),
        )
    else:
        @jax.custom_vjp
        def ag(x, w):
            return fwd_fn(x, w)

        ag.defvjp(
            lambda x, w: (fwd_fn(x, w), (x, w)),
            lambda res, dy: bwd_fn(res[0], res[1], dy),
        )
    # Staged under jit so eager callers (init/trace passes) compile once
    # instead of rejecting the manual region (same as _build_cp_call).
    return jax.jit(ag)


def ring_ag_matmul(x, w, bias=None, *, w_tp_dim=1, fused=False):
    """Column-parallel ``allgather_seq(x) @ w (+ bias)`` as a ring, or
    None when the decomposition cannot apply (caller keeps the GSPMD
    einsum). x: [*lead, S, D]; w: [D, *out] with tp on ``w_tp_dim``;
    bias: w.shape[1:]. ``fused`` routes the partial matmuls through the
    Pallas fused matmul+bias kernel."""
    mesh = state.mesh
    tp = mesh.shape.get(TP_AXIS, 1)
    S = x.shape[-2]
    if S % tp != 0:
        _warn_once(("ag", S, tp),
                   "tp_overlap: sequence length %d not divisible by tp=%d"
                   " — GSPMD path for this matmul.", S, tp)
        return None
    if w.shape[w_tp_dim] % tp != 0:
        _warn_once(("ag_feature", w.shape[w_tp_dim], tp),
                   "tp_overlap: output-feature dim %d not divisible by "
                   "tp=%d — GSPMD path for this column-parallel matmul.",
                   w.shape[w_tp_dim], tp)
        return None
    from smdistributed_modelparallel_tpu.nn.utils import shard_activation

    x, w = _maybe_fp8_operands(x, w, "ring_ag")
    x = shard_activation(
        x, *([None] * (x.ndim - 2) + [TP_AXIS, None])
    )
    interpret = jax.default_backend() != "tpu"
    fn = _build_ag(mesh, tp, x.ndim, w.ndim, w_tp_dim,
                   bias is not None, bool(fused), interpret,
                   manual_axes(TP_AXIS))
    return fn(x, w, bias) if bias is not None else fn(x, w)


# ----------------------------------------------------------------------
# ring reduce-scatter matmul (row-parallel)
# ----------------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _build_rs(mesh, tp, x_ndim, n_contract, x_tp_dim, w_ndim,
              interpret, manual, axis_name=TP_AXIS):
    """custom_vjp ``reduce_scatter_seq(x @ w)`` with the reduction
    decomposed into a tp-step accumulator ring. x: [*lead, S, *contract]
    with tp on ``x_tp_dim`` (a contract dim); w: [*contract, *out] with
    tp on the matching dim. Output [*lead, S, *out] sequence-sharded
    over tp."""
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    seq_dim = x_ndim - n_contract - 1
    w_tp_dim = x_tp_dim - seq_dim - 1      # position inside w's contract dims

    def fwd_body(x, w):
        # Local blocks: contract dims divided on the tp one; S full.
        S = x.shape[seq_dim]
        Sl = S // tp
        lead = x.shape[:seq_dim]
        xl = x.reshape(lead + (S, -1))                   # [*lead, S, Kl]
        wl = w.reshape(xl.shape[-1], -1)                 # [Kl, Fo]
        me = jax.lax.axis_index(axis_name)
        acc0 = jnp.zeros(lead + (Sl, wl.shape[1]), x.dtype)

        def body(i, acc):
            # The accumulator hops first (the chunk in transit), then the
            # local partial for it is computed and added — hop hidden
            # under the partial matmul.
            acc = jax.lax.ppermute(acc, axis_name, perm)
            c = (me - i - 1) % tp
            x_c = jax.lax.dynamic_slice_in_dim(
                xl, c * Sl, Sl, axis=seq_dim
            )
            acc, x_c = _issue_before(acc, x_c)
            acc = acc + jnp.matmul(x_c, wl).astype(acc.dtype)
            return acc

        acc = jax.lax.fori_loop(0, tp, body, acc0)
        return acc.reshape(lead + (Sl,) + w.shape[n_contract:])

    def bwd_body(x, w, dy):
        # Mirrored: dy blocks ride the ring; each step derives dx rows
        # for the block's owner (all-gather-matmul of dy @ w^T) and that
        # block's dW contribution.
        S = x.shape[seq_dim]
        Sl = S // tp
        lead = x.shape[:seq_dim]
        xl = x.reshape(lead + (S, -1))
        wl = w.reshape(xl.shape[-1], -1)
        dyl = dy.reshape(lead + (Sl, wl.shape[1]))
        me = jax.lax.axis_index(axis_name)
        dx0 = jnp.zeros(xl.shape, jnp.float32)
        dw0 = jnp.zeros(wl.shape, jnp.float32)

        def body(i, carry):
            dy_cur, dx, dwl = carry
            dy_nxt = jax.lax.ppermute(dy_cur, axis_name, perm)
            dy_nxt, dy_cur = _issue_before(dy_nxt, dy_cur)
            src = (me - i) % tp           # whose dy block we hold
            dx = jax.lax.dynamic_update_slice_in_dim(
                dx, jnp.matmul(dy_cur, wl.T).astype(jnp.float32),
                src * Sl, axis=seq_dim,
            )
            x_src = jax.lax.dynamic_slice_in_dim(
                xl, src * Sl, Sl, axis=seq_dim
            )
            dwl = dwl + jnp.matmul(
                x_src.reshape(-1, xl.shape[-1]).T.astype(jnp.float32),
                dy_cur.reshape(-1, wl.shape[1]).astype(jnp.float32),
            )
            return (dy_nxt, dx, dwl)

        _, dx, dwl = jax.lax.fori_loop(0, tp, body, (dyl, dx0, dw0))
        return (
            dx.reshape(x.shape).astype(x.dtype),
            dwl.reshape(w.shape).astype(w.dtype),
        )

    x_spec = single_axis_spec(x_ndim, x_tp_dim, axis_name)
    w_spec = single_axis_spec(w_ndim, w_tp_dim, axis_name)
    out_ndim = seq_dim + 1 + (w_ndim - n_contract)
    out_spec = single_axis_spec(out_ndim, seq_dim, axis_name)

    fwd_fn = jax.shard_map(
        fwd_body, mesh=mesh, in_specs=(x_spec, w_spec),
        out_specs=out_spec, axis_names=manual, check_vma=False,
    )
    bwd_fn = jax.shard_map(
        bwd_body, mesh=mesh, in_specs=(x_spec, w_spec, out_spec),
        out_specs=(x_spec, w_spec), axis_names=manual,
        check_vma=False,
    )

    @jax.custom_vjp
    def rs(x, w):
        return fwd_fn(x, w)

    rs.defvjp(
        lambda x, w: (fwd_fn(x, w), (x, w)),
        lambda res, dy: bwd_fn(res[0], res[1], dy),
    )
    return jax.jit(rs)


def ring_rs_matmul(x, w, *, n_contract=1, x_tp_dim=None):
    """Row-parallel ``reduce_scatter_seq(x @ w)`` as a ring, or None
    when the decomposition cannot apply. x: [*lead, S, *contract] with
    tp on ``x_tp_dim`` (default: the first contract dim); w:
    [*contract, *out]; output [*lead, S, *out] sequence-sharded over tp.
    The row-parallel bias is NOT folded here — it must be added once,
    after the reduction, by the caller."""
    mesh = state.mesh
    tp = mesh.shape.get(TP_AXIS, 1)
    seq_dim = x.ndim - n_contract - 1
    if x_tp_dim is None:
        x_tp_dim = seq_dim + 1
    S = x.shape[seq_dim]
    if S % tp != 0:
        _warn_once(("rs", S, tp),
                   "tp_overlap: sequence length %d not divisible by tp=%d"
                   " — GSPMD path for this matmul.", S, tp)
        return None
    if x.shape[x_tp_dim] % tp != 0:
        _warn_once(("rs_contract", x.shape[x_tp_dim], tp),
                   "tp_overlap: contract dim %d not divisible by tp=%d — "
                   "GSPMD all-reduce for this row-parallel matmul.",
                   x.shape[x_tp_dim], tp)
        return None
    from smdistributed_modelparallel_tpu.nn.utils import shard_activation

    x, w = _maybe_fp8_operands(x, w, "ring_rs")
    x = shard_activation(
        x, *[TP_AXIS if d == x_tp_dim else None for d in range(x.ndim)]
    )
    interpret = jax.default_backend() != "tpu"
    fn = _build_rs(mesh, tp, x.ndim, n_contract, x_tp_dim, w.ndim,
                   interpret, manual_axes(TP_AXIS))
    return fn(x, w)
