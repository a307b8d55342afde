"""1F1B ("interleaved") pipeline schedule with bounded in-flight microbatches.

Parity target: reference ``torch/pipeline.py:136-145``
(``InterleavedPipeline.get_next_microbatch`` prioritizes ready-backwards over
new forwards) and ``torch/server_queue.py:629-676`` (the
``active_microbatches`` in-flight cap). The reference gets 1F1B behavior
dynamically from its server event loop; here the schedule is computed
statically in Python and baked into ONE ``lax.scan`` over ticks:

- each tick has a forward sub-step and a backward sub-step; per stage the
  static schedule says which microbatch (if any) to process in each;
- a sub-step that no stage uses is not executed: the backward sub-step
  of the leading ticks with no backward anywhere and the forward sub-step
  of the trailing ticks with no forward anywhere (the bounds are
  ``interleaved_phase_bounds``) sit behind a ``lax.cond`` on the tick
  index. On the chip a masked sub-step costs what a busy one does
  (PERF.md, PR 25);
- stage inputs are stashed into a ring buffer of ``active_microbatches + 1``
  slots; backward re-runs the stage forward from the stash under ``jax.vjp``
  (activation recomputation, Megatron-style 1F1B-with-remat) — peak live
  carries are O(S * active_microbatches) instead of the fill-drain
  executor's O(num_microbatches * S) saved scan carries;
- stage-to-stage transfers (forward activations and backward cotangents)
  move through pp-sharded buffers via ``jnp.roll`` on the stage axis, which
  GSPMD lowers to a collective-permute over ICI;
- the last stage's forward OUTPUT is stashed in its own ring; its backward
  tick runs only the cheap head + user-loss VJP on that stashed output to
  get (replicated/head param grads, the stage-output cotangent), and the
  uniform vmapped stage backward then treats the last stage like any other
  — no stage forward is ever executed twice, and the only replicated
  (non-stage-parallel) work per tick is the head/loss VJP itself;
  embedding gradients are applied after the tick loop from the collected
  stage-0 input cotangents.

The executor returns (mean_loss-scaled grads, stacked user outputs, stacked
losses); the step engine (``step.py``) divides out the loss scale exactly as
in the fill-drain path so the two schedules are numerically interchangeable.

Four executors share this module and one scaffold. ``pipeline_1f1b``
only dispatches: it reads the schedule, the virtual degree and the
recompute mode off the config, runs the set-up once (``_setup_run``: the
staged layer views, the embedded microbatch queue, the layer / head
applications, the backward seeds; one frozen ``_Run`` record) and hands
the record to

- ``_pipeline_1f1b_plain``: v=1, one loop with conditional sub-steps
  (setting the knobs to their defaults compiles the program that leaving
  them unset does);
- ``_pipeline_1f1b_virtual``: (chunk, microbatch) units over ``pp*v``
  chunks, one loop per phase;
- ``_pipeline_zero_bubble``: ZB-H1, (chunk, microbatch, pass) units — the
  backward split into an input-grad pass and a deferred weight-grad pass
  that fills the cooldown bubble (``pipeline: "zero_bubble"``);
- ``_pipeline_zero_bubble_stash``: the same schedule with the W pass fed
  from stashed vjp residuals (``recompute`` other than ``full``).

Each executor owns its schedule tables, its ring buffers, its tick body
and its loop(s), and ends in ``_finish_run`` (embedding backward, layer
gradients back to ``[L, ...]``, the gradient tree in the parameters'
dtypes). The ``smp/pipeline/*`` scope names round the sub-steps and the
loops are read by ``utils/hlo_audit.op_index`` and the benchmark's phase
shares: they are an interface, not decoration.
"""

import dataclasses
import functools
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.nn.auto_distribute import unwrap_hooks
from smdistributed_modelparallel_tpu.nn.utils import half_cast
from smdistributed_modelparallel_tpu.parallel import remat_plan
from smdistributed_modelparallel_tpu.parallel.memory import (
    recompute_ring_plan,
    remat_policy,
    zero_bubble_ring_plan,
)
from smdistributed_modelparallel_tpu.parallel.pipeline import (
    _get_subtree,
    _mk_rngs,
    _scan_map,
    apply_collecting_aux,
    chunk_layout,
    make_layer_apply,
    pin_stage_axis as _pin_stage_axis,
    stage_layout,
    stage_vmap,
    staged_chunk_views,
    staged_layer_views,
)
from smdistributed_modelparallel_tpu.utils import health
from smdistributed_modelparallel_tpu.utils.exceptions import PartitionError
from smdistributed_modelparallel_tpu.utils.flight_recorder import (
    flight_recorder,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.profiling import named_region
from smdistributed_modelparallel_tpu.utils.telemetry import (
    record_pipeline_occupancy,
    telemetry,
)

logger = get_logger()


def build_1f1b_schedule(num_stages, num_microbatches, window):
    """Static lockstep 1F1B schedule.

    Returns (fwd, bwd): int arrays [n_ticks, S]; entry = microbatch index the
    stage processes in that tick's sub-step, or -1 for idle. Invariants: a
    stage's forward of microbatch m runs only after stage s-1's forward of m
    (strictly earlier tick); a stage's backward of m runs only after its own
    forward of m (same tick allowed on the last stage — cotangent comes from
    the loss, not a neighbor) and after stage s+1's backward of m; at most
    ``window`` microbatches are in flight (forwarded, not yet backwarded)
    per stage at any tick.
    """
    S, M, W = num_stages, num_microbatches, window
    if W < 1:
        raise PartitionError(f"active_microbatches must be >= 1, got {W}")
    fwd_next = [0] * S
    bwd_next = [0] * S
    fwd_tick = {}
    bwd_tick = {}
    fwd_rows, bwd_rows = [], []
    t = 0
    limit = 4 * (M + S) * max(1, (S + W - 1) // W) + 16
    while any(b < M for b in bwd_next):
        frow, brow = [-1] * S, [-1] * S
        for s in range(S):
            m = fwd_next[s]
            if m < M and (fwd_next[s] - bwd_next[s]) < W:
                if s == 0 or fwd_tick.get((s - 1, m), limit) < t:
                    frow[s] = m
        for s in range(S):
            if frow[s] >= 0:
                fwd_tick[(s, frow[s])] = t
                fwd_next[s] += 1
        for s in range(S):
            m = bwd_next[s]
            if m < M and fwd_tick.get((s, m), limit) <= t:
                if s == S - 1 or bwd_tick.get((s + 1, m), limit) < t:
                    brow[s] = m
        for s in range(S):
            if brow[s] >= 0:
                bwd_tick[(s, brow[s])] = t
                bwd_next[s] += 1
        fwd_rows.append(frow)
        bwd_rows.append(brow)
        t += 1
        if t > limit:
            raise PartitionError(
                f"1F1B schedule did not converge (S={S}, M={M}, W={W})"
            )
    return np.asarray(fwd_rows, np.int32), np.asarray(bwd_rows, np.int32)


def schedule_occupancy(fwd, bwd, fwd_ticks=None, bwd_ticks=None, wgt=None,
                       wgt_ticks=None):
    """(busy_slots, total_slots) of a static 1F1B schedule.

    Each tick has a forward and a backward sub-step per stage; a sub-slot
    is busy when its schedule entry is a microbatch index (>= 0). The
    compiled program executes exactly this schedule, so this IS the
    measured occupancy. Under virtual pipeline stages the entries are
    (chunk, microbatch) units, so busy counts CHUNK sub-steps (busy ==
    2*S*V*M) and stays comparable across ``virtual_pipeline_degree``
    values; ``fwd_ticks``/``bwd_ticks`` restrict the denominator to the
    ticks whose sub-step actually executes (the virtual executor's
    warmup ticks are forward-only and its cooldown ticks backward-only —
    idle sub-steps that are never compiled are not bubble). The plain v=1
    executor keeps one loop and skips the same sub-steps at run time, so
    it passes the same bounds; the defaults give the accounting of
    rigidly paired ticks.

    Zero-bubble schedules split the backward into input-grad (B) and
    weight-grad (W) passes: ``bwd`` then carries the B pass, ``wgt`` the
    W pass (with its own ``wgt_ticks`` executed-span bound), and busy
    counts (chunk, microbatch, pass) sub-steps — 3*S*V*M when every unit
    ran exactly once.
    """
    busy = int((fwd >= 0).sum()) + int((bwd >= 0).sum())
    if fwd_ticks is None:
        fwd_ticks = int(fwd.shape[0])
    if bwd_ticks is None:
        bwd_ticks = int(bwd.shape[0])
    total_ticks = fwd_ticks + bwd_ticks
    if wgt is not None:
        busy += int((wgt >= 0).sum())
        total_ticks += int(wgt.shape[0]) if wgt_ticks is None else wgt_ticks
    total = int(fwd.shape[1]) * total_ticks
    return busy, total


def build_interleaved_1f1b_schedule(num_stages, num_microbatches, window,
                                    virtual):
    """Static lockstep 1F1B schedule over ``virtual`` chunks per stage.

    Megatron-style virtual pipeline stages: the model is cut into
    ``C = num_stages * virtual`` chunks; global chunk ``c`` lives on stage
    ``c % num_stages`` as that stage's local chunk ``k = c // num_stages``.
    Returns ``(fwd_chunk, fwd_mb, bwd_chunk, bwd_mb)``: int32 arrays
    ``[n_ticks, S]``; per tick each stage processes at most one
    (chunk, microbatch) unit per direction (-1 = idle).

    Invariants (generalizing the v=1 schedule's):
      - every (chunk, microbatch) is forwarded and backwarded exactly once;
      - fwd of chunk c, mb m runs strictly after fwd of chunk c-1, mb m;
      - bwd of chunk c, mb m runs strictly after bwd of chunk c+1, mb m,
        and not before its own fwd (same tick allowed only on the LAST
        chunk, whose cotangent comes from the loss, not a neighbor);
      - per (stage, chunk), at most ``window`` microbatches are in flight
        (forwarded, not yet backwarded) at any tick.

    Greedy policy: each stage picks the highest eligible chunk in both
    directions (depth-first fwd pushes microbatches toward the loss so
    backwards start sooner; highest-chunk bwd drains cotangents down the
    chunk chain). At ``virtual=1`` this reduces EXACTLY to
    ``build_1f1b_schedule`` (one chunk per stage, identical arrays).

    Bubble: with ``window >= 2*num_stages`` the schedule achieves the
    interleaved floor — occupancy over executed sub-steps (forward-only
    warmup ticks + paired ticks + backward-only cooldown ticks, see
    ``interleaved_phase_bounds``) equals
    ``1 - (pp-1)/(v*mb + pp-1)``. The default ``active_microbatches``
    (pp+2) reaches it at pp=2; deeper pipelines trade the last bubble
    fraction against in-flight activation memory.
    """
    S, M, W, V = num_stages, num_microbatches, window, virtual
    if W < 1:
        raise PartitionError(f"active_microbatches must be >= 1, got {W}")
    if V < 1:
        raise PartitionError(f"virtual degree must be >= 1, got {V}")
    C = S * V
    fwd_next = [[0] * V for _ in range(S)]
    bwd_next = [[0] * V for _ in range(S)]
    fwd_tick = {}
    bwd_tick = {}
    fk_rows, fm_rows, bk_rows, bm_rows = [], [], [], []
    t = 0
    limit = 4 * V * (M + S) * max(1, (S + W - 1) // W) + 16 * V

    def fwd_candidate(s):
        """Highest eligible local chunk for stage s's fwd sub-step."""
        for k in range(V - 1, -1, -1):
            c = k * S + s
            m = fwd_next[s][k]
            if m < M and (fwd_next[s][k] - bwd_next[s][k]) < W:
                if c == 0 or fwd_tick.get((c - 1, m), limit) < t:
                    return k, m
        return -1, -1

    def bwd_candidate(s):
        for k in range(V - 1, -1, -1):
            c = k * S + s
            m = bwd_next[s][k]
            if m < M and fwd_tick.get((c, m), limit) <= t:
                if c == C - 1 or bwd_tick.get((c + 1, m), limit) < t:
                    return k, m
        return -1, -1

    while any(n < M for row in bwd_next for n in row):
        fk, fm = zip(*(fwd_candidate(s) for s in range(S)))
        for s in range(S):
            if fm[s] >= 0:
                fwd_tick[(fk[s] * S + s, fm[s])] = t
                fwd_next[s][fk[s]] += 1
        bk, bm = zip(*(bwd_candidate(s) for s in range(S)))
        for s in range(S):
            if bm[s] >= 0:
                bwd_tick[(bk[s] * S + s, bm[s])] = t
                bwd_next[s][bk[s]] += 1
        fk_rows.append(fk)
        fm_rows.append(fm)
        bk_rows.append(bk)
        bm_rows.append(bm)
        t += 1
        if t > limit:
            raise PartitionError(
                f"interleaved 1F1B schedule did not converge "
                f"(S={S}, M={M}, W={W}, V={V})"
            )
    return (np.asarray(fk_rows, np.int32), np.asarray(fm_rows, np.int32),
            np.asarray(bk_rows, np.int32), np.asarray(bm_rows, np.int32))


def interleaved_phase_bounds(fwd_mb, bwd_mb):
    """(t_bwd_start, t_fwd_end) of a ``pipeline: "interleaved"`` (1F1B)
    schedule, plain or virtual: any ``[n_ticks, S]`` pair of arrays.

    Ticks ``[0, t_bwd_start)`` have no backward work anywhere (warmup:
    the virtual executor compiles them as forward-only sub-steps, the
    plain one skips their backward sub-step) and ticks
    ``[t_fwd_end, n_ticks)`` no forward work (cooldown: backward-only).
    This phase split is what realizes the interleaved bubble win: the
    rigidly paired tick (one fwd + one bwd sub-step) would idle a full
    sub-step per warmup/cooldown tick, making the sub-slot bubble
    independent of the virtual degree.
    """
    n_ticks = int(fwd_mb.shape[0])
    bwd_any = (bwd_mb >= 0).any(axis=1)
    fwd_any = (fwd_mb >= 0).any(axis=1)
    t_b0 = int(np.argmax(bwd_any)) if bwd_any.any() else n_ticks
    t_fe = n_ticks - int(np.argmax(fwd_any[::-1])) if fwd_any.any() else 0
    return t_b0, t_fe


def build_zero_bubble_schedule(num_stages, num_microbatches, window,
                               virtual=1):
    """ZB-H1 zero-bubble schedule: (chunk, microbatch, pass) units.

    Splits the backward into an input-gradient pass (B, on the critical
    path: it feeds the upstream stage's cotangent) and a weight-gradient
    pass (W, deferrable: it depends only on the stage's own B), and packs
    the deferred Ws into ticks that the F/B schedule would otherwise
    leave idle — cooldown first. Returns
    ``(fwd_chunk, fwd_mb, bwd_chunk, bwd_mb, wgt_chunk, wgt_mb)``: int32
    arrays ``[n_ticks, S]``, one (chunk, microbatch) unit per stage per
    pass per tick (-1 = idle).

    Invariants (on top of the interleaved schedule's for F and B):
      - every (chunk, microbatch) runs each of F, B, W exactly once;
      - B(c, m) depends on F(c, m) and the downstream B(c+1, m) exactly
        as the interleaved schedule's monolithic backward does — the
        (F, B) sub-schedule here IS ``build_interleaved_1f1b_schedule``'s
        output tick-for-tick (fusing W back into B reproduces it);
      - W(c, m) depends ONLY on B(c, m); the same tick is legal because
        the executor orders sub-steps F -> B -> W within a tick;
      - per stage, at most one W per tick (it is a real compute slot).

    Packing policy: per stage, Ws run FIFO in B-completion order, shifted
    so no stage starts its W run before the LAST stage has started
    backwards (``w_lo = max_s first_B_tick(s)``). Early stages therefore
    defer weight grads into the B-drain cooldown — the ticks where their
    B slot idles waiting for upstream cotangents — instead of fusing them
    into warm B ticks and idling cold ones. At (pp=2, mb >= pp, default
    window) every stage's W run is gapless and the sub-slot bubble over
    executed pass spans reaches

        2*(pp-1) / (3*v*mb + 2*(pp-1))

    strictly below the interleaved floor (pp-1)/(v*mb + pp-1) for every
    v, mb (the F and B ramps keep their pp-1 idle sub-slots; the W pass
    contributes zero). The deferral depth this costs is bounded — the
    W-queue ring is accounted by ``parallel/memory.py::
    zero_bubble_ring_plan`` and stays within the existing ``window + 1``
    stash ring at the default window.
    """
    S, M, V = num_stages, num_microbatches, virtual
    fwd_k, fwd_m, bwd_k, bwd_m = build_interleaved_1f1b_schedule(
        S, M, window, V
    )
    n_fb = int(fwd_m.shape[0])
    # Per-stage B completions in tick order (== microbatch FIFO per
    # (stage, chunk): bwd_next only ever increments).
    per_stage = [[] for _ in range(S)]
    for t in range(n_fb):
        for s in range(S):
            if bwd_m[t, s] >= 0:
                per_stage[s].append((t, int(bwd_k[t, s]), int(bwd_m[t, s])))
    firsts = [rows[0][0] for rows in per_stage if rows]
    w_lo = max(firsts) if firsts else 0
    n_ticks = n_fb
    assign = [[] for _ in range(S)]
    for s in range(S):
        prev = -1
        for i, (bt, k, m) in enumerate(per_stage[s]):
            wt = max(w_lo + i, bt, prev + 1)
            prev = wt
            assign[s].append((wt, k, m))
            n_ticks = max(n_ticks, wt + 1)

    def pad(a):
        if n_ticks == a.shape[0]:
            return a
        tail = np.full((n_ticks - a.shape[0], S), -1, np.int32)
        return np.concatenate([a, tail])

    fwd_k, fwd_m, bwd_k, bwd_m = (pad(a) for a in (fwd_k, fwd_m,
                                                   bwd_k, bwd_m))
    wgt_k = np.full((n_ticks, S), -1, np.int32)
    wgt_m = np.full((n_ticks, S), -1, np.int32)
    for s in range(S):
        for wt, k, m in assign[s]:
            wgt_k[wt, s] = k
            wgt_m[wt, s] = m
    return fwd_k, fwd_m, bwd_k, bwd_m, wgt_k, wgt_m


def zero_bubble_phase_bounds(fwd_mb, bwd_mb, wgt_mb):
    """Executed-tick span ``(lo, hi)`` per pass: F, B, W.

    Generalizes ``interleaved_phase_bounds`` to three passes: ticks
    outside a pass's span never compile that pass's sub-step (the ZB
    executor scans per contiguous segment of active passes), so only
    in-span idle sub-slots are bubble. ``(0, 0)`` marks a pass with no
    work (degenerate schedules).
    """

    def span(arr):
        busy = (arr >= 0).any(axis=1)
        if not busy.any():
            return (0, 0)
        lo = int(np.argmax(busy))
        hi = int(arr.shape[0] - np.argmax(busy[::-1]))
        return (lo, hi)

    return span(fwd_mb), span(bwd_mb), span(wgt_mb)


def zero_bubble_theoretical_bubble(num_stages, num_microbatches, virtual=1):
    """ZB-H1 sub-slot bubble bound: 2*(pp-1)/(3*v*mb + 2*(pp-1)).

    Denominator: 3 passes of v*mb busy sub-slots per stage plus the F and
    B ramps' pp-1 extra span ticks each; numerator: those two ramps' idle
    sub-slots (the W pass packs gapless). Strictly below the interleaved
    bound (pp-1)/(v*mb + pp-1) whenever v*mb > 0.
    """
    S, M, V = num_stages, num_microbatches, virtual
    denom = 3 * V * M + 2 * (S - 1)
    return 2 * (S - 1) / denom if denom > 0 else 0.0


def _zb_segments(f_span, b_span, w_span, n_ticks):
    """Contiguous tick segments [a, b) with static per-pass flags
    (do_fwd, do_bwd, do_wgt) — the ZB executor compiles one scan per
    segment, so out-of-span sub-steps never enter the program (same
    trick as the interleaved warmup/steady/cooldown split, generalized
    to three passes)."""
    cuts = sorted({0, n_ticks, *f_span, *b_span, *w_span})
    segs = []
    for a, b in zip(cuts, cuts[1:]):
        if a >= b:
            continue
        flags = (f_span[0] <= a < f_span[1],
                 b_span[0] <= a < b_span[1],
                 w_span[0] <= a < w_span[1])
        if any(flags):
            segs.append((a, b, flags))
    return segs


def _zb_segment_region(do_fwd, do_bwd, do_wgt):
    """Profiler region name for a ZB schedule segment (``_zb_segments``
    keeps none without a pass, so the last is the weight-only drain)."""
    if do_fwd and not do_bwd:
        return "smp/pipeline/warmup"
    if do_fwd:
        return "smp/pipeline/steady"
    if do_bwd:
        return "smp/pipeline/cooldown"
    return "smp/pipeline/cooldown_weight"


def _run_in_span(in_span, spans_all_ticks, substep, ops):
    """``substep(ops)`` on the ticks of its span; ``ops`` as they are on
    the others, where no stage has such a slot and the sub-step would
    compute a result whose every write is masked.

    ``spans_all_ticks`` is static: a span that covers the whole loop
    emits no conditional. ``in_span`` derives from the tick index, the
    same on every device, so the collectives inside a branch stay
    matched. One loop body with conditionals, not one loop per phase: a
    phase of its own compiles a second copy of the sub-step, and a second
    backward sub-step holds a second stack of per-layer residuals, which
    the compiler paid for with rematerialization (PERF.md, PR 25).
    """
    if spans_all_ticks:
        return substep(ops)
    return jax.lax.cond(in_span, substep, lambda unchanged: unchanged, ops)


def _inexact_leaves(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    idx = [i for i, l in enumerate(leaves)
           if jnp.issubdtype(jnp.result_type(l), jnp.inexact)]
    return leaves, treedef, idx


# ---- ring/scatter primitives. All are pure in their arguments: ring
# geometry rides in the buffers themselves. ``_stage_ring_*`` index
# [S, R, ...] rings (the plain executor's, and every executor's last-stage
# output ring); ``_chunk_ring_*`` the chunk-generalized [S, V, R, ...]
# ones; the ``_chunk_scatter_*`` write one microbatch row of an [M, ...]
# collection buffer.


def _stage_ring_set(buf, row_slots, row_vals, row_active):
    """buf[s, row_slots[s]] = row_vals[s] where row_active[s]."""

    def upd(b, v):
        def one(bs, slot, vs, act):
            new = jax.lax.dynamic_update_index_in_dim(
                bs, vs.astype(bs.dtype), slot, 0
            )
            return jnp.where(act, new, bs)

        return jax.vmap(one)(b, row_slots, v, row_active)

    return jax.tree_util.tree_map(upd, buf, row_vals)


def _stage_ring_get(buf, row_slots):
    return jax.tree_util.tree_map(
        lambda b: jax.vmap(
            lambda bs, slot: jax.lax.dynamic_index_in_dim(
                bs, slot, 0, keepdims=False
            )
        )(b, row_slots),
        buf,
    )


def _chunk_ring_set(buf, row_chunks, row_slots, row_vals, row_active):
    """buf[s, row_chunks[s], row_slots[s]] = row_vals[s] where active."""

    def upd(b, v):
        def one(bs, k, slot, vs, act):   # bs: [V, R, ...]
            sub = jax.lax.dynamic_index_in_dim(bs, k, 0, keepdims=False)
            new = jax.lax.dynamic_update_index_in_dim(
                sub, vs.astype(bs.dtype), slot, 0
            )
            new = jnp.where(act, new, sub)
            return jax.lax.dynamic_update_index_in_dim(bs, new, k, 0)

        return jax.vmap(one)(b, row_chunks, row_slots, v, row_active)

    return jax.tree_util.tree_map(upd, buf, row_vals)


def _chunk_ring_get(buf, row_chunks, row_slots):
    def one(bs, k, slot):
        sub = jax.lax.dynamic_index_in_dim(bs, k, 0, keepdims=False)
        return jax.lax.dynamic_index_in_dim(sub, slot, 0, keepdims=False)

    return jax.tree_util.tree_map(
        lambda b: jax.vmap(one)(b, row_chunks, row_slots), buf
    )


def _chunk_scatter_add_mb(buf, m, val, active):
    def upd(b, v):
        cur = jax.lax.dynamic_index_in_dim(b, m, 0, keepdims=False)
        new = cur + jnp.where(active, v.astype(b.dtype), jnp.zeros_like(cur))
        return jax.lax.dynamic_update_index_in_dim(b, new, m, 0)

    return jax.tree_util.tree_map(upd, buf, val)


def _chunk_scatter_set_mb(buf, m, val, active):
    def upd(b, v):
        cur = jax.lax.dynamic_index_in_dim(b, m, 0, keepdims=False)
        new = jnp.where(active, v.astype(b.dtype), cur)
        return jax.lax.dynamic_update_index_in_dim(b, new, m, 0)

    return jax.tree_util.tree_map(upd, buf, val)


def _chunk_scatter_add_leaf(buf, m, val, active):
    cur = jax.lax.dynamic_index_in_dim(buf, m, 0, keepdims=False)
    new = cur + jnp.where(active, val.astype(buf.dtype), jnp.zeros_like(cur))
    return jax.lax.dynamic_update_index_in_dim(buf, new, m, 0)


def _chunk_scatter_stat(acc, krow, vals, act, op):
    """acc[s, krow[s]] = op(acc[s, krow[s]], vals[s]) where act[s];
    acc is [S, V] (per-stage per-chunk health stats)."""

    def one(av, k, vv, m):
        cur = jax.lax.dynamic_index_in_dim(av, k, 0, keepdims=False)
        new = jnp.where(m, op(cur, vv), cur)
        return jax.lax.dynamic_update_index_in_dim(av, new, k, 0)

    return jax.vmap(one)(acc, krow, vals, act)


def _chunk_acc_rows(acc, rows, krow, act):
    """Accumulate [S, ...] grad rows into the per-(stage, chunk) slot."""

    def upd(a, r):
        def one(av, k, rv, m):
            cur = jax.lax.dynamic_index_in_dim(av, k, 0, keepdims=False)
            new = cur + jnp.where(m, rv.astype(av.dtype), 0)
            return jax.lax.dynamic_update_index_in_dim(av, new, k, 0)

        return jax.vmap(one)(a, krow, r, act)

    return jax.tree_util.tree_map(upd, acc, rows)


def _select_chunk(tree, krow):
    """Per-stage view of one chunk: [S, V, ...] -> [S, ...] at krow[s]."""
    return jax.tree_util.tree_map(
        lambda a: jax.vmap(
            lambda av, k: jax.lax.dynamic_index_in_dim(av, k, 0, keepdims=False)
        )(a, krow),
        tree,
    )


def _gather_mb(tree, m):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, m, 0, keepdims=False),
        tree,
    )


def _gather_sides_rows(sides, ms):
    """Per-stage side tuples for a [S] vector of microbatch indices."""
    if sides is None:
        return None
    return tuple(
        jax.tree_util.tree_map(
            lambda a: jax.vmap(
                lambda i: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            )(ms),
            s,
        )
        for s in sides
    )


def _zeros_chunk_ring(run, n):
    """[S, V, n, ...] of one microbatch's hidden carry, zeroed."""
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((run.S, run.V, n) + a.shape, a.dtype),
        run.carry_aval,
    )


def _zeros_stage_ring(run, n):
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((run.S, n) + a.shape, a.dtype), run.carry_aval
    )


def _zeros_stage_rows(run):
    return jax.tree_util.tree_map(
        lambda a: jnp.zeros((run.S,) + a.shape, a.dtype), run.carry_aval
    )


def _zeros_health_grids(S, V):
    """(bad count, max |x|, first bad microbatch) per (stage, chunk)."""
    return (
        jnp.zeros((S, V), jnp.float32), jnp.zeros((S, V), jnp.float32),
        jnp.full((S, V), -1.0, jnp.float32),
    )


def _rows_to_layers(idx_np, active_np, num_layers):
    """``to_layers`` for grads accumulated in a padded layout: the
    [S, maxp, ...] / [S, V, maxp, ...] rows scatter-add back to [L, ...]
    through the layout's index grid (``stage_layout`` / ``chunk_layout``),
    padded slots masked."""
    flat_idx = jnp.asarray(idx_np.reshape(-1))
    flat_mask = active_np.reshape(-1)
    lead = idx_np.ndim

    def to_layers(g):
        gf = g.reshape((idx_np.size,) + g.shape[lead:])
        gf = gf * flat_mask.reshape((-1,) + (1,) * (gf.ndim - 1))
        zeros = jnp.zeros((num_layers,) + g.shape[lead:], g.dtype)
        return zeros.at[flat_idx].add(gf)

    return to_layers


def _chunk_slots(S, passes):
    """Busy slots of a chunked schedule for the flight recorder, in tick
    order. ``passes``: ``(direction, chunk table, microbatch table[, pass
    tag])`` each. Slot events carry the GLOBAL chunk (boundary) index
    k*S + s: stage says where the work ran, chunk identifies the layers —
    the same coordinates the fill-drain executor records for chunked
    specs."""
    n_ticks = passes[0][2].shape[0]
    return (
        (t, s, d, int(m_arr[t, s]), int(k_arr[t, s]) * S + s, *tag)
        for t in range(n_ticks) for s in range(S)
        for d, k_arr, m_arr, *tag in passes
        if m_arr[t, s] >= 0
    )


def _make_residual_split(run):
    """Per-layer vjp split of one chunk application, for the recompute
    planner's stash modes (``parallel/remat_plan.py``).

    The fused executors differentiate the whole chunk under one
    ``jax.vjp``, so the deferred weight-grad pass must re-run the chunk
    forward to rebuild the vjp's residuals. Here the chunk forward is
    instead run with a PER-LAYER ``jax.vjp`` whose function output is
    returned as flattened pytree leaves (`jax.vjp`'s vjp function is a
    ``tree_util.Partial`` — its leaves ARE the saved residuals), so a
    later pass can rebuild each layer's vjp with ``tree_unflatten`` and
    the treedef captured at trace time:

    - ``capture_fwd``: the chunk forward, additionally returning the
      per-layer residual leaves stacked over the layer axis;
    - ``bwd_from_res``: the input-grad sweep from residuals — reverse
      per-layer vjp chain seeded by the chunk-output cotangent, returning
      (input cotangent, side cotangent leaves, per-layer OUTPUT
      cotangents). The per-layer weight cotangents are never used here,
      so XLA dead-code-eliminates their matmuls;
    - ``wgt_from_res``: the weight-grad pass — per-layer vjp calls from
      (residuals, stashed per-layer cotangents), keeping only the weight
      cotangents (the input-grad matmuls are dead and eliminated). No
      forward, no cotangent chain: weight-grad FLOPs only.

    The captured treedef (``captured["treedef"]``) comes from whichever
    trace runs first (the executors probe with ``jax.eval_shape``); the
    embedded backward is jaxpr-closed and trace-independent, so leaves
    written by one compiled segment reconstruct in another.
    """
    apply_one_layer, cast_half = run.apply_one_layer, run.cast_half
    rng, maxp, aux_seed = run.rng, run.maxp, run.aux_seed
    has_sides = run.sides is not None
    side_leaf_avals = (
        [run.side_leaves[i] for i in run.side_idx] if has_sides else []
    )
    captured = {}

    def capture_fwd(chunk_lp, chunk_lxs, x, side, c_idx, m_idx, act_row):
        base = jax.random.fold_in(jax.random.fold_in(rng, c_idx), m_idx)

        def body(c, xs):
            lp, lxs, i, act = xs

            def one(lp_, c_, side_):
                new_c, aux = apply_one_layer(
                    cast_half(lp_), c_, lxs, jax.random.fold_in(base, i),
                    side_,
                )
                out_c = jax.tree_util.tree_map(
                    lambda n, o: jnp.where(act, n, o), new_c, c_
                )
                return out_c, jnp.where(act, aux, 0.0)

            if has_sides:
                (out_c, aux), lvjp = jax.vjp(one, lp, c, side)
            else:
                (out_c, aux), lvjp = jax.vjp(
                    lambda lp_, c_: one(lp_, c_, None), lp, c
                )
            leaves, treedef = jax.tree_util.tree_flatten(lvjp)
            captured.setdefault("treedef", treedef)
            return out_c, (aux, tuple(leaves))

        idx = jnp.arange(maxp)
        out, (auxs, res) = jax.lax.scan(
            body, x, (chunk_lp, chunk_lxs, idx, act_row)
        )
        return out, jnp.sum(auxs), res

    def _unflatten(res_layer):
        return jax.tree_util.tree_unflatten(
            captured["treedef"], list(res_layer)
        )

    def bwd_from_res(res, cot):
        side_zeros = [
            jnp.zeros(a.shape, jnp.float32) for a in (side_leaf_avals or [])
        ]

        def body(carry, res_layer):
            cbar, side_acc = carry
            lvjp = _unflatten(res_layer)
            outs = lvjp((cbar, aux_seed))
            if has_sides:
                _d_lp, d_c, d_side = outs
                leaves, _, idx = _inexact_leaves(d_side)
                side_acc = [
                    a + leaves[i].astype(a.dtype)
                    for a, i in zip(side_acc, idx)
                ]
            else:
                _d_lp, d_c = outs
            # ys: this layer's OUTPUT cotangent — what its weight-grad
            # vjp call needs later. _d_lp is unused: dead code.
            return (d_c, side_acc), cbar

        (d_x, side_acc), cot_stack = jax.lax.scan(
            body, (cot, side_zeros), res, reverse=True
        )
        return d_x, side_acc, cot_stack

    def bwd_full_from_res(res, cot):
        """Monolithic backward from residuals (the interleaved/1F1B
        executors' B pass under ``stash_all``): one reverse sweep
        producing weight grads AND the input cotangent — no forward."""
        side_zeros = [
            jnp.zeros(a.shape, jnp.float32) for a in (side_leaf_avals or [])
        ]

        def body(carry, res_layer):
            cbar, side_acc = carry
            lvjp = _unflatten(res_layer)
            outs = lvjp((cbar, aux_seed))
            if has_sides:
                d_lp, d_c, d_side = outs
                leaves, _, idx = _inexact_leaves(d_side)
                side_acc = [
                    a + leaves[i].astype(a.dtype)
                    for a, i in zip(side_acc, idx)
                ]
            else:
                d_lp, d_c = outs
            return (d_c, side_acc), d_lp

        (d_x, side_acc), d_lp_stack = jax.lax.scan(
            body, (cot, side_zeros), res, reverse=True
        )
        return d_lp_stack, d_x, side_acc

    def wgt_from_res(res, cot_stack):
        def body(_, xs):
            res_layer, cot_layer = xs
            lvjp = _unflatten(res_layer)
            outs = lvjp((cot_layer, aux_seed))
            # Keep only the weight cotangent; d_c / d_side are dead.
            return (), outs[0]

        _, d_lp_stack = jax.lax.scan(body, (), (res, cot_stack))
        return d_lp_stack

    return capture_fwd, bwd_from_res, bwd_full_from_res, wgt_from_res, captured


def _stash_slot_bytes(avals):
    """Bytes one (stage, chunk, ring-slot) stash entry costs per device:
    the probe avals carry a leading stage axis (vmapped rows), which the
    ring shards over pp — drop it."""
    return int(sum(
        a.dtype.itemsize * int(np.prod(a.shape[1:], dtype=np.int64))
        for a in jax.tree_util.tree_leaves(avals)
    ))


def _probe_stash_avals(run, capture_fwd, bwd_from_res=None):
    """Abstract-trace one vmapped chunk-row capture to learn the stash
    leaf shapes (and capture the per-layer vjp treedef as a side effect
    — this must run before any ``bwd_*_from_res`` trace). Returns the
    residual avals, or ``(res_avals, cot_avals)`` when ``bwd_from_res``
    is given (the zero-bubble executor also stashes the per-layer
    output cotangents)."""
    S, sides = run.S, run.sides

    def row_aval(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((S,) + a.shape[2:], a.dtype),
            tree,
        )

    def stage_rows_aval(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct((S,) + a.shape, a.dtype), tree
        )

    side_row_aval = None
    if sides is not None:
        side_row_aval = tuple(
            jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct((S,) + a.shape[1:], a.dtype),
                s,
            )
            for s in sides
        )

    def probe(ch_params, ch_xs, x, side, c_ids, mrow, act):
        _out, _aux, res = stage_vmap(
            capture_fwd, S,
            in_axes=(0, 0, 0, 0 if sides is not None else None, 0, 0, 0),
        )(ch_params, ch_xs, x, side, c_ids, mrow, act)
        if bwd_from_res is None:
            return res
        cot = jax.tree_util.tree_map(lambda a: jnp.zeros_like(a), x)
        _d_x, _side_acc, cot_stack = stage_vmap(bwd_from_res, S)(res, cot)
        return res, cot_stack

    return jax.eval_shape(
        probe,
        row_aval(run.staged_params), row_aval(run.staged_xs),
        stage_rows_aval(run.carry_aval), side_row_aval,
        jax.ShapeDtypeStruct((S,), jnp.int32),
        jax.ShapeDtypeStruct((S,), jnp.int32),
        row_aval(run.active_rows),
    )


def _stash_chunk_maps(plan, V):
    """Static per-local-chunk maps of a stash plan: ``(stash_of_arr,
    res_col_arr, Vs, all_stash)`` — whether chunk k stashes, and its
    column in the Vs-compressed stash rings."""
    Vs = len(plan.stash_chunks)
    stash_of_np = np.zeros((V,), bool)
    res_col_np = np.zeros((V,), np.int32)
    for col, k in enumerate(plan.stash_chunks):
        stash_of_np[k] = True
        res_col_np[k] = col
    return (jnp.asarray(stash_of_np), jnp.asarray(res_col_np), Vs, Vs == V)


# ---- the scaffold every executor stands on: set-up before its tick
# loop(s), finish after ------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class _Run:
    """What one traced pipeline step's executor reads, built once by
    ``_setup_run`` and dropped with the trace. Arrays are tracers of the
    step's trace; the staged views are in the layout the dispatch asked
    for ([S, maxp, ...] plain, [S, V, maxp, ...] chunked)."""

    model: Any
    module: Any                  # model.module, hooks unwrapped
    spec: Any                    # model._pipeline_spec
    cfg: Any
    S: int                       # pipeline stages
    M: int                       # microbatches
    L: int                       # layers in the pipelined stack
    W: int                       # in-flight window (active_microbatches)
    V: int                       # virtual chunks per stage
    params: Any
    stacked_inputs: Any
    rng: Any
    mb_loss_fn: Callable
    loss_seed_scale: Any
    params_rest: Any             # params with the layer subtree emptied
    with_layers: Callable        # params_rest-shaped tree -> full tree
    staged_params: Any
    staged_xs: Any
    active_rows: Any
    idx_np: np.ndarray           # layer index of every staged slot
    active_np: np.ndarray        # which staged slots hold a layer
    maxp: int                    # layer slots per stage / chunk
    mb_keys: Any                 # [M] PRNG keys
    hidden_q: Any                # [M, ...] embedded microbatches
    sides: Optional[tuple]       # tuple-carry side values, [M, ...] each
    carry_aval: Any              # one microbatch's hidden carry
    apply_one_layer: Callable
    cast_half: Callable
    chunk_fwd: Callable          # one stage's / chunk's layer slots
    head_apply_aux: Callable
    loss_out_aval: Any           # avals of mb_loss_fn's (loss, user_out)
    stage_ids: Any
    aux_w: float                 # moe_aux_loss_weight
    aux_seed: Any                # backward seed of a stage's MoE aux loss
    side_leaves: Optional[list]
    side_idx: Optional[list]     # the inexact leaves among side_leaves
    hc: Any                      # health collector, or None


def _stage_views(spec, layer_params, S, V):
    """The plain layout: one run of layers per stage, [S, maxp, ...]
    (``V`` is 1 and has no axis)."""
    return (*staged_layer_views(spec, layer_params, S),
            *stage_layout(spec, S))


def _chunk_views(spec, layer_params, S, V):
    """The chunked layout: chunk c on stage c % S, [S, V, maxp, ...]."""
    staged_params, staged_xs, active_rows = staged_chunk_views(
        spec, layer_params, S, V
    )
    # Stage-axis sharding pins (the chunked gather breaks GSPMD's
    # propagation; pin ONLY dim 0: ``pipeline.pin_stage_axis``).
    return (_pin_stage_axis(staged_params, S), _pin_stage_axis(staged_xs, S),
            active_rows, *chunk_layout(spec, S, V))


def _setup_run(model, params, stacked_inputs, rng, mb_loss_fn,
               loss_seed_scale, virtual, staged_views):
    """Everything an executor needs before its first tick, once per traced
    step: the staged layer views, the embedded microbatch queue (the
    ``smp/pipeline/embed`` scan), the per-layer / per-chunk / head
    applications, the abstract loss and output shapes and the backward
    seeds. The first six arguments are ``pipeline_1f1b``'s; the layout
    comes in as ``staged_views`` (``_stage_views``, or ``_chunk_views``
    at ``virtual`` chunks a stage)."""
    spec = model._pipeline_spec
    cfg = state.cfg
    S = cfg.pipeline_parallel_degree
    M = cfg.microbatches
    module = unwrap_hooks(model.module)
    half = cfg.half_dtype

    def cast_half(tree):
        return half_cast(tree, half)

    layer_params = _get_subtree(params, spec.layer_path)
    (staged_params, staged_xs, active_rows, idx_np, active_np,
     maxp) = staged_views(spec, layer_params, S, virtual)
    # The head/loss and embed VJPs differentiate only the NON-layer subtree
    # (head, tied/replicated, embedding params): layer gradients come from
    # the per-stage VJPs, so carrying full-tree zero cotangents through the
    # per-tick head VJP would add accumulator traffic proportional to total
    # params on every tick for nothing. Protocol note: embed/head methods
    # must not read the layer-stack subtree (true of every pipelineable
    # module in the package — the stack is applied only via
    # spec.layer_module).
    params_rest = _set_subtree(params, spec.layer_path, {})

    def with_layers(p_rest):
        return _set_subtree(p_rest, spec.layer_path, layer_params)

    mb_keys = jax.random.split(rng, M)

    # ---- embed all microbatches (the input queue) --------------------

    def embed_mb(mb_input, key):
        args, kwargs = mb_input
        if spec.embed_method is None:
            return args[0]
        return module.apply(
            {"params": cast_half(params)}, *args,
            rngs=_mk_rngs(model, key, "embed"),
            method=spec.embed_method, **kwargs,
        )

    with named_region("smp/pipeline/embed"):
        embedded = _scan_map(embed_mb, stacked_inputs, mb_keys)

    if spec.carry_is_tuple:
        hidden_q = embedded[0]
        sides = embedded[1:]
    else:
        hidden_q = embedded
        sides = None

    carry_aval = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), hidden_q
    )

    # ---- per-stage / per-chunk forward (pure in its params and carry) -

    apply_one_layer = make_layer_apply(
        model, spec, spec.layer_module, side_in_carry=False
    )

    if spec.carry_remat:
        apply_one_layer = jax.checkpoint(apply_one_layer, policy=remat_policy())

    def chunk_fwd(chunk_lp, chunk_lxs, x, side, c_idx, m_idx, act_row):
        """Apply one chunk's layer slots (at v=1: one stage's, the global
        chunk id IS the stage id); keys derived from (global chunk, mb) so
        every backward recompute reproduces the forward, dropout included,
        exactly. Padded slots pass the carry through unchanged. Returns
        (carry, summed MoE aux loss of the active slots) — the aux output
        is what lets the backward VJPs seed router load-balancing
        gradients."""
        base = jax.random.fold_in(jax.random.fold_in(rng, c_idx), m_idx)
        chunk_lp = cast_half(chunk_lp)

        def body(c, xs):
            lp, lxs, i, act = xs
            new_c, aux = apply_one_layer(
                lp, c, lxs, jax.random.fold_in(base, i), side
            )
            out_c = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act, n, o), new_c, c
            )
            return out_c, jnp.where(act, aux, 0.0)

        idx = jnp.arange(maxp)
        out, auxs = jax.lax.scan(body, x, (chunk_lp, chunk_lxs, idx, act_row))
        return out, jnp.sum(auxs)

    # ---- head + user loss (last stage, last chunk only) ---------------

    def head_apply_aux(p, carry, key):
        if spec.head_method is None:
            return carry, jnp.zeros((), jnp.float32)
        return apply_collecting_aux(
            module, {"params": cast_half(p)}, carry,
            rngs=_mk_rngs(model, key, "head"), method=spec.head_method,
        )

    # Abstract shapes of (loss, user_out) for the collection buffers.
    loss_out_aval = jax.eval_shape(
        lambda c: mb_loss_fn(
            head_apply_aux(params, c, mb_keys[0])[0], 0, mb_keys[0]
        ),
        jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, a.dtype), carry_aval),
    )

    stage_ids = jnp.arange(S)
    # MoE aux-loss backward seed: d(total_loss)/d(stage_aux) for one
    # microbatch under mean-over-microbatch semantics. loss_seed_scale is
    # loss_scale / num_microbatches, exactly the task-loss seed.
    aux_w = float(getattr(cfg, "moe_aux_loss_weight", 1.0))
    aux_seed = (
        jnp.asarray(aux_w, jnp.float32)
        * jnp.asarray(loss_seed_scale, jnp.float32)
    )

    side_leaves = side_idx = None
    if sides is not None:
        side_leaves, _, side_idx = _inexact_leaves(
            tuple(jax.tree_util.tree_map(lambda a: a[0], s) for s in sides)
        )

    return _Run(
        model=model, module=module, spec=spec, cfg=cfg, S=S, M=M,
        L=spec.num_layers, W=min(cfg.active_microbatches or (S + 1), M),
        V=virtual, params=params, stacked_inputs=stacked_inputs, rng=rng,
        mb_loss_fn=mb_loss_fn, loss_seed_scale=loss_seed_scale,
        params_rest=params_rest, with_layers=with_layers,
        staged_params=staged_params, staged_xs=staged_xs,
        active_rows=active_rows, idx_np=idx_np, active_np=active_np,
        maxp=maxp, mb_keys=mb_keys, hidden_q=hidden_q, sides=sides,
        carry_aval=carry_aval, apply_one_layer=apply_one_layer,
        cast_half=cast_half, chunk_fwd=chunk_fwd,
        head_apply_aux=head_apply_aux, loss_out_aval=loss_out_aval,
        stage_ids=stage_ids, aux_w=aux_w, aux_seed=aux_seed,
        side_leaves=side_leaves, side_idx=side_idx,
        # Health sentinel (utils/health.py): per-stage boundary-activation
        # stats accumulate in the tick carry; the tick loops run in the
        # step trace itself, so the totals feed the collector directly
        # after them.
        hc=health.active(),
    )


def _param_grad_zeros(tree):
    """Zero parameter-gradient accumulators under the fill-drain path's
    policy (step.py::_acc_dtype — fp32 under _fp32_grad_accumulation, else
    the parameter's own dtype, which for master weights is fp32 anyway)."""
    fp32 = state.cfg._fp32_grad_accumulation

    def acc_dtype(dtype):
        if jnp.issubdtype(dtype, jnp.floating) and fp32:
            return jnp.float32
        return dtype

    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, acc_dtype(p.dtype)), tree
    )


def _zero_accumulators(run):
    """``(dlay, drep, dembed, dsides, losses, outs)`` at zero: what every
    tick loop carries beside its rings and hands to ``_finish_run``. The
    intermediate cotangent buffers (dembed/dsides) stay fp32."""
    M = run.M
    dlay0 = _param_grad_zeros(run.staged_params)
    drep0 = _param_grad_zeros(run.params_rest)   # head/tied/replicated
    dembed0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros((M,) + a.shape, jnp.float32), run.carry_aval
    )
    dsides0 = None
    if run.sides is not None:
        dsides0 = [
            jnp.zeros((M,) + run.side_leaves[i].shape, jnp.float32)
            for i in run.side_idx
        ]
    losses0 = jnp.zeros((M,), jnp.float32)
    outs0 = jax.tree_util.tree_map(
        lambda a: jnp.zeros((M,) + a.shape, a.dtype), run.loss_out_aval[1]
    )
    return dlay0, drep0, dembed0, dsides0, losses0, outs0


def _head_loss(run, m_last, key_last, p_rest, out):
    """Head + user loss of microbatch ``m_last`` on the last chunk's
    stashed output: what a backward tick differentiates in (p_rest, out)."""
    final, h_aux = run.head_apply_aux(run.with_layers(p_rest), out, key_last)
    loss, user_out = run.mb_loss_fn(final, m_last, key_last)
    # Head-resident MoE aux joins the differentiated loss with the same
    # weight as the layer-stack aux (parity with pp=1).
    loss = loss + jnp.asarray(run.aux_w, loss.dtype) * h_aux.astype(loss.dtype)
    return loss, user_out


def _run_head(run, m_last, key_last, out_last):
    """The head + loss VJP as the chunked executors run it, a ``lax.cond``
    branch: (loss, replicated/head param grads, the last chunk's output
    cotangent, user_out)."""
    loss_m, head_vjp, user_out = jax.vjp(
        functools.partial(_head_loss, run, m_last, key_last),
        run.params_rest, out_last, has_aux=True,
    )
    seed = jnp.asarray(run.loss_seed_scale, loss_m.dtype)
    d_rep, d_out_last = head_vjp(seed)
    return loss_m.astype(jnp.float32), d_rep, d_out_last, user_out


def _chunk_bwd(run, lp, lxs, x, side, cot, c_idx, m_idx, act_row):
    """Monolithic backward of one chunk (at v=1: one stage): re-run its
    forward from the stashed input under ``jax.vjp``. Both outputs are
    seeded: the downstream cotangent for the hidden carry, and the MoE
    aux-loss seed (same mean-loss scaling as the task loss; idle rows'
    contributions are masked when accumulated)."""

    def f(lp_, x_, side_):
        return run.chunk_fwd(lp_, lxs, x_, side_, c_idx, m_idx, act_row)

    _, vjp = jax.vjp(f, lp, x, side)
    return vjp((cot, run.aux_seed))


def _chunk_bwd_weight(run, lp, lxs, x, side, cot, c_idx, m_idx, act_row):
    """Weight-grad pass by recompute: VJP w.r.t. the chunk params only,
    re-running the forward from the stashed input and the retained
    chunk-output cotangent."""

    def f(lp_):
        return run.chunk_fwd(lp_, lxs, x, side, c_idx, m_idx, act_row)

    _, vjp = jax.vjp(f, lp)
    (d_lp,) = vjp((cot, run.aux_seed))
    return d_lp


def _add_side_cotangents(dsides, d_side_rows, side_idx, mrow, act):
    """Side cotangents: every active stage's row adds to the [M, ...] slot
    of the microbatch it ran (``mrow[s]``)."""
    for s in range(act.shape[0]):
        row_leaves, _, _ = _inexact_leaves(
            jax.tree_util.tree_map(lambda r: r[s], d_side_rows)
        )
        dsides = [
            _chunk_scatter_add_leaf(d, mrow[s], row_leaves[i], act[s])
            for d, i in zip(dsides, side_idx)
        ]
    return dsides


def _finish_run(run, to_layers, dlay, drep, dembed, dsides, losses, outs):
    """After the last tick: the embedding backward from the collected
    stage-0 input cotangents, the stage-accumulated layer gradients back
    to [L, ...] (``to_layers``: the executor's, per leaf), and the full
    gradient tree in the parameters' dtypes, all under the scope
    ``smp/pipeline/finish``. Returns what ``pipeline_1f1b`` does."""
    with named_region("smp/pipeline/finish"):
        return _finish(run, to_layers, dlay, drep, dembed, dsides, losses,
                       outs)


def _finish(run, to_layers, dlay, drep, dembed, dsides, losses, outs):
    model, module, spec = run.model, run.module, run.spec

    def embed_bwd(acc, xs):
        mb_input, key, dcarry, dside_row = xs

        def embed_inexact(p_rest):
            args, kwargs = mb_input
            out, aux = apply_collecting_aux(
                module, {"params": run.cast_half(run.with_layers(p_rest))},
                *args, rngs=_mk_rngs(model, key, "embed"),
                method=spec.embed_method, **kwargs,
            )
            leaves, _, idx = _inexact_leaves(out)
            # The embed's own MoE aux (0.0 for dense embeds) rides along as
            # a final output so its balancing gradient is seeded below.
            return [leaves[i] for i in idx] + [aux]

        out_aval = jax.eval_shape(embed_inexact, run.params_rest)
        # Cotangent list: hidden cotangent (+ side cotangents for tuples),
        # then the aux seed.
        if run.sides is not None:
            cots = list(jax.tree_util.tree_leaves(dcarry)) + list(dside_row)
        else:
            cots = jax.tree_util.tree_leaves(dcarry)
        cots = cots + [run.aux_seed]
        cots = [c.astype(a.dtype) for c, a in zip(cots, out_aval)]
        _, vjp = jax.vjp(embed_inexact, run.params_rest)
        (dp,) = vjp(cots)
        acc = jax.tree_util.tree_map(
            lambda a, g: a + g.astype(a.dtype), acc, dp
        )
        return acc, None

    demb_params = None
    if spec.embed_method is not None:
        dside_stack = tuple(dsides) if dsides is not None else ()
        demb_params, _ = jax.lax.scan(
            embed_bwd, _param_grad_zeros(run.params_rest),
            (run.stacked_inputs, run.mb_keys, dembed, dside_stack),
        )
    layer_grads = jax.tree_util.tree_map(to_layers, dlay)
    if demb_params is not None:
        # Embedding contributions (a rest-tree like drep; the layer
        # subtree never appears in either).
        drep = jax.tree_util.tree_map(
            lambda a, b: a + b.astype(a.dtype), drep, demb_params
        )
    # Install the stage-accumulated layer grads into the rest-tree: the
    # result has the full parameter structure.
    grads = _set_subtree(drep, spec.layer_path, layer_grads)
    grads = jax.tree_util.tree_map(
        lambda g, p: g.astype(jnp.result_type(p)), grads, run.params
    )
    return grads, losses, outs


def pipeline_1f1b(model, params, stacked_inputs, rng, mb_loss_fn,
                  loss_seed_scale):
    """Run the full 1F1B forward+backward for all microbatches.

    Args:
      model: DistributedModel with ``_pipeline_spec`` installed.
      params: master parameter tree (layer subtree leaves lead with [L]).
      stacked_inputs: pytree with leading [num_microbatches] — captured
        inputs of the user's single ``model(...)`` call.
      rng: PRNG key (dropout etc.; folded per stage/microbatch so backward
        recompute reproduces the forward exactly).
      mb_loss_fn(out, mb_index, key) -> (loss, user_out): the user step
        function re-run with the model call forced to ``out``.
      loss_seed_scale: scalar multiplied into the backward seed (the step
        engine passes loss_scale / num_microbatches so grads come out as
        d(mean(losses) * loss_scale)).

    Returns: (grads_tree, stacked_losses [M], stacked_user_outs [M, ...]).
    """
    cfg = state.cfg
    schedule = getattr(cfg, "pipeline", "interleaved")
    zero_bubble = schedule == "zero_bubble"
    virtual = int(getattr(cfg, "virtual_pipeline_degree", 1) or 1)
    rmode = remat_plan.resolve(cfg)
    if rmode == "stash_weight" and not zero_bubble:
        # No deferred weight-grad pass to stash for on the fused
        # schedules: the SCHEDULE-level stash is inert here (the knob
        # still maps onto the jax.checkpoint policy in
        # memory.remat_policy for models that rematerialize, and the
        # fingerprint config snapshot keeps recording the knob).
        logger.warning(
            "recompute: 'stash_weight' targets the zero_bubble schedule's "
            "W pass; pipeline: %r has none — no schedule-level stash "
            "(use 'stash_all' to remove this schedule's B recompute).",
            schedule,
        )
        rmode = "full"
    # Only the default program (1F1B, v=1, recompute as the model has it)
    # is the plain executor's, whether its knobs are unset or spelled out
    # at their defaults. The stash modes route v=1 through the chunked
    # executors too: their plans need the chunked ring layout.
    chunked = zero_bubble or virtual > 1 or rmode != "full"
    run = _setup_run(
        model, params, stacked_inputs, rng, mb_loss_fn, loss_seed_scale,
        virtual, _chunk_views if chunked else _stage_views,
    )
    if not chunked:
        return _pipeline_1f1b_plain(run)
    if not zero_bubble:
        # An auto plan that degrades every chunk stays on this executor
        # (numerically identical, chunk-ring program).
        return _pipeline_1f1b_virtual(run, rmode)
    if rmode != "full":
        result = _pipeline_zero_bubble_stash(run, rmode)
        if result is not None:
            return result
        # The plan degraded every chunk (auto under a tight budget): the
        # untouched recompute executor IS the plan.
    return _pipeline_zero_bubble(run)


def _pipeline_1f1b_plain(run):
    """The plain v=1 executor, the module docstring's program: one run of
    layers per stage, [S, W+1] rings, and ONE tick loop whose forward and
    backward sub-steps sit behind conditionals on the tick index
    (``_run_in_span``)."""
    S, M, sides, hc = run.S, run.M, run.sides, run.hc
    W1 = run.W + 1

    fwd_np, bwd_np = build_1f1b_schedule(S, M, run.W)
    n_ticks = fwd_np.shape[0]
    t_b0, t_fe = interleaved_phase_bounds(fwd_np, bwd_np)
    busy, total = schedule_occupancy(
        fwd_np, bwd_np, fwd_ticks=t_fe, bwd_ticks=n_ticks - t_b0
    )
    record_pipeline_occupancy("1f1b", S, M, busy_slots=busy, total_slots=total)
    # Busy schedule slots (with microbatch ids) into the flight recorder,
    # once per trace — see pipeline.py for why.
    flight_recorder.record_schedule(
        "1f1b",
        ((t, s, d, int(sched[t, s]))
         for t in range(n_ticks) for s in range(S)
         for d, sched in (("fwd", fwd_np), ("bwd", bwd_np))
         if sched[t, s] >= 0),
    )
    fwd_sched = jnp.asarray(fwd_np)
    bwd_sched = jnp.asarray(bwd_np)

    # ---- buffers ------------------------------------------------------

    # All [S, W1, ...], slot m % W1: inbuf the input for stage s's fwd of
    # m; stash the input that fwd consumed; cotbuf the cotangent for stage
    # s's output of m; outbuf the last stage's fwd output of m (only row
    # S-1 is ever written; keeping the [S] axis keeps the buffer pp-sharded
    # like its siblings instead of replicated).
    inbuf0 = _zeros_stage_ring(run, W1)
    stash0 = _zeros_stage_ring(run, W1)
    cotbuf0 = _zeros_stage_ring(run, W1)
    outbuf0 = _zeros_stage_ring(run, W1)
    dlay0, drep0, dembed0, dsides0, losses0, outs0 = _zero_accumulators(run)

    def tick(carry, t):
        """One schedule tick: a forward and a backward sub-step. Each is
        skipped (``_run_in_span``) on the ticks where the schedule has no
        such slot on ANY stage; there its every write would be masked, so
        the results are those of always running both."""
        (inbuf, stash, cotbuf, outbuf, dlay, drep, dembed, dsides,
         losses, outs) = carry[:10]
        hstats = carry[10] if hc is not None else None

        # ---------------- forward sub-step ----------------
        def fwd_substep(ops):
            inbuf, stash, outbuf, hstats = ops
            fm = fwd_sched[t]                       # [S]; -1 idle
            f_active = fm >= 0
            fmc = jnp.maximum(fm, 0)
            f_slots = fmc % W1
            # Stage 0 reads from the embedded queue; others from inbuf.
            from_q = _gather_mb(run.hidden_q, fmc[0])
            buf_in = _stage_ring_get(inbuf, f_slots)
            x_in = jax.tree_util.tree_map(
                lambda q, b: b.at[0].set(q), from_q, buf_in
            )
            f_sides = _gather_sides_rows(sides, fmc)
            with named_region("smp/pipeline/tick_fwd"):
                outs_f, _aux_f = stage_vmap(
                    run.chunk_fwd, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None, 0, 0, 0),
                )(run.staged_params, run.staged_xs, x_in, f_sides,
                  run.stage_ids, fmc, run.active_rows)
            # Stash the consumed inputs for backward recompute.
            stash = _stage_ring_set(stash, f_slots, x_in, f_active)
            if hc is not None:
                hbad, habs, hmb = hstats
                brow, arow = health.stage_row_stats(outs_f, S)
                brow = jnp.where(f_active, brow, 0.0)
                arow = jnp.where(f_active, arow, 0.0)
                hmb = jnp.where(
                    (hmb < 0) & (brow > 0), fmc.astype(jnp.float32), hmb
                )
                hstats = (hbad + brow, jnp.maximum(habs, arow), hmb)
            # Ship outputs forward one stage (collective-permute on pp): the
            # value produced by stage s lands in inbuf[s+1] at slot m % W1.
            shifted_vals = jax.tree_util.tree_map(
                lambda o: jnp.roll(o, 1, axis=0), outs_f
            )
            shifted_slots = jnp.roll(f_slots, 1)
            shifted_active = jnp.roll(f_active, 1).at[0].set(False)
            inbuf = _stage_ring_set(
                inbuf, shifted_slots, shifted_vals, shifted_active
            )
            # The last stage's output feeds the head/loss at its backward tick.
            last_row_active = f_active & (run.stage_ids == S - 1)
            outbuf = _stage_ring_set(outbuf, f_slots, outs_f, last_row_active)
            return inbuf, stash, outbuf, hstats

        inbuf, stash, outbuf, hstats = _run_in_span(
            t < t_fe, t_fe == n_ticks, fwd_substep,
            (inbuf, stash, outbuf, hstats),
        )

        # ---------------- backward sub-step ----------------
        def bwd_substep(ops):
            cotbuf, dlay, drep, dembed, dsides, losses, outs = ops
            bm = bwd_sched[t]
            b_active = bm >= 0
            bmc = jnp.maximum(bm, 0)
            b_slots = bmc % W1

            # Head + user loss VJP on the last stage's STASHED output: yields
            # the replicated/head param grads and the stage-output cotangent.
            # The stage forward itself is NOT in this VJP — the uniform vmapped
            # stage backward below recomputes it once, same as every stage.
            m_last = bmc[S - 1]
            key_last = jax.lax.dynamic_index_in_dim(
                run.mb_keys, m_last, 0, keepdims=False
            )
            out_last = jax.tree_util.tree_map(
                lambda ob: jax.lax.dynamic_index_in_dim(
                    ob[S - 1], b_slots[S - 1], 0, keepdims=False
                ),
                outbuf,
            )

            head_loss = functools.partial(_head_loss, run, m_last, key_last)

            with named_region("smp/pipeline/head"):
                loss_m, head_vjp, user_out = jax.vjp(
                    head_loss, run.params_rest, out_last, has_aux=True
                )
                seed = jnp.asarray(run.loss_seed_scale, jnp.float32) * jnp.where(
                    b_active[S - 1], 1.0, 0.0
                )
                d_rep, d_out_last = head_vjp(seed.astype(loss_m.dtype))

            # All stages: plain stage VJP; cotangents come from cotbuf except
            # the last stage's, which is the head/loss cotangent just computed.
            cot_in = _stage_ring_get(cotbuf, b_slots)
            cot_in = jax.tree_util.tree_map(
                lambda c, d: c.at[S - 1].set(d.astype(c.dtype)), cot_in, d_out_last
            )
            b_sides = _gather_sides_rows(sides, bmc)
            stash_in = _stage_ring_get(stash, b_slots)

            with named_region("smp/pipeline/tick_bwd"):
                d_lp_rows, d_x_rows, d_side_rows = stage_vmap(
                    functools.partial(_chunk_bwd, run), S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0, 0),
                )(run.staged_params, run.staged_xs, stash_in,
                  b_sides, cot_in, run.stage_ids, bmc, run.active_rows)

            # Accumulate layer grads (mask idle rows).
            mask_b = b_active

            def acc_rows(acc, rows):
                def add(a, r):
                    m = mask_b.reshape((S,) + (1,) * (r.ndim - 1))
                    return a + jnp.where(m, r.astype(a.dtype), 0)

                return jax.tree_util.tree_map(add, acc, rows)

            dlay = acc_rows(dlay, d_lp_rows)

            # Replicated/head grads: only when the last stage was active.
            drep = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(b_active[S - 1], g.astype(a.dtype), 0),
                drep, d_rep,
            )

            # Route input cotangents: stage s's d_input goes to stage s-1's
            # output cotangent (cotbuf[s-1]); stage 0's goes to the embedding.
            shifted_cots = jax.tree_util.tree_map(
                lambda o: jnp.roll(o, -1, axis=0), d_x_rows
            )
            cot_slots = jnp.roll(b_slots, -1)
            cot_active = jnp.roll(b_active, -1).at[S - 1].set(False)
            cotbuf = _stage_ring_set(cotbuf, cot_slots, shifted_cots, cot_active)
            dembed = _chunk_scatter_add_mb(
                dembed, bmc[0],
                jax.tree_util.tree_map(lambda r: r[0], d_x_rows),
                b_active[0],
            )

            # Side cotangents: every active stage contributes to its microbatch.
            if sides is not None and dsides is not None:
                dsides = _add_side_cotangents(
                    dsides, d_side_rows, run.side_idx, bmc, b_active
                )

            # Loss / user outputs at the last stage's backward tick.
            losses = losses.at[m_last].set(
                jnp.where(b_active[S - 1], loss_m.astype(jnp.float32), losses[m_last])
            )
            outs = _chunk_scatter_set_mb(outs, m_last, user_out, b_active[S - 1])
            return cotbuf, dlay, drep, dembed, dsides, losses, outs

        cotbuf, dlay, drep, dembed, dsides, losses, outs = _run_in_span(
            t >= t_b0, t_b0 == 0, bwd_substep,
            (cotbuf, dlay, drep, dembed, dsides, losses, outs),
        )

        new_carry = (inbuf, stash, cotbuf, outbuf, dlay, drep, dembed,
                     dsides, losses, outs)
        if hc is not None:
            new_carry = new_carry + (hstats,)
        return new_carry, None

    carry0 = (inbuf0, stash0, cotbuf0, outbuf0, dlay0, drep0, dembed0,
              dsides0, losses0, outs0)
    if hc is not None:
        carry0 = carry0 + ((
            jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.float32),
            jnp.full((S,), -1.0, jnp.float32),
        ),)
    with named_region("smp/pipeline/steady"):
        carry_end, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
    if hc is not None:
        (_, _, _, _, dlay, drep, dembed, dsides, losses, outs,
         (hbad, habs, hmb)) = carry_end
        hc.add_stage_stats("1f1b", hbad, habs, hmb)
    else:
        (_, _, _, _, dlay, drep, dembed, dsides, losses, outs) = carry_end

    # [S, maxp, ...] accumulated stage grads -> [L, ...] (scatter-add for
    # padded/uneven layouts; a pure reshape when the layout is dense).
    if run.active_np.all() and run.L == S * run.maxp:
        def to_layers(g):
            return g.reshape((run.L,) + g.shape[2:])
    else:
        to_layers = _rows_to_layers(run.idx_np, run.active_np, run.L)
    return _finish_run(
        run, to_layers, dlay, drep, dembed, dsides, losses, outs
    )


def _pipeline_1f1b_virtual(run, rmode):
    """1F1B with ``run.V`` interleaved model chunks per pipeline stage.

    ``rmode`` is the recompute-planner knob: under ``stash_all``/``auto``
    the forward sub-step captures per-layer vjp residuals into a stash
    ring (``memory.recompute_ring_plan``'s ``f_to_b`` lifetime) and the
    backward sub-step consumes them instead of re-running the chunk
    forward under ``jax.vjp`` — the 1F1B B-recompute disappears where the
    plan stashes. At ``"full"`` the plan machinery never runs.

    Same numerical contract as the v=1 executor (grads/losses/outputs
    interchangeable with the fill-drain path), different schedule shape:

    - the partitioner cut the model into ``C = S*V`` chunks; global
      chunk ``c`` lives on stage ``c % S`` (``parallel/pipeline.py::
      chunk_layout``), so every chunk boundary crossing is a +1 rotation
      on the pp axis — ``jnp.roll`` -> one collective-permute, exactly as
      at v=1, just ``V`` times as often per microbatch;
    - ring buffers are keyed by (local chunk, microbatch): shape
      ``[S, V, W+1, ...]``;
    - stage transfers are DOUBLE-BUFFERED: tick t's fwd outputs / bwd
      cotangents park in transfer registers and the roll
      (collective-permute) + ring write happen at the START of tick t+1 —
      legal because the schedule's cross-chunk dependencies are strictly
      earlier-tick, and it places each permute next to compute that does
      not depend on it so the latency-hiding scheduler can overlap the
      t+1 transfer with tick t+1's first compute instead of serializing
      at the tick boundary;
    - the tick loop is split into three scans — forward-only warmup
      ticks, paired steady-state ticks, backward-only cooldown ticks
      (``interleaved_phase_bounds``). This is what makes the bubble
      shrink with ``V``: a rigidly paired tick would idle one full
      sub-step per warmup/cooldown tick and the sub-slot bubble would
      stay at its v=1 value no matter how many chunks exist.
    """
    S, M, V, sides, hc = run.S, run.M, run.V, run.sides, run.hc
    W1 = run.W + 1
    pin_stage_axis = functools.partial(_pin_stage_axis, num_stages=S)

    tables = build_interleaved_1f1b_schedule(S, M, run.W, V)
    fwd_k_np, fwd_m_np, bwd_k_np, bwd_m_np = tables
    n_ticks = fwd_m_np.shape[0]
    t_b0, t_fe = interleaved_phase_bounds(fwd_m_np, bwd_m_np)
    busy, total = schedule_occupancy(
        fwd_m_np, bwd_m_np, fwd_ticks=t_fe, bwd_ticks=n_ticks - t_b0
    )
    record_pipeline_occupancy(
        "1f1b", S, M, busy_slots=busy, total_slots=total, virtual=V
    )
    # Phase tick counts next to the occupancy gauges: the roofline
    # bubble attribution (utils/profiling.py) and the trace_fuse phase
    # view both read the warmup/steady/cooldown split from here.
    _phase_gauge = telemetry.gauge(
        "smp_pipeline_phase_ticks",
        "ticks per interleaved schedule phase (warmup/steady/cooldown)",
    )
    _phase_gauge.labels(phase="warmup").set(t_b0)
    _phase_gauge.labels(phase="steady").set(t_fe - t_b0)
    _phase_gauge.labels(phase="cooldown").set(n_ticks - t_fe)
    flight_recorder.record_schedule(
        "1f1b",
        _chunk_slots(S, (("fwd", fwd_k_np, fwd_m_np),
                         ("bwd", bwd_k_np, bwd_m_np))),
    )
    fwd_k_sched, fwd_m_sched, bwd_k_sched, bwd_m_sched = (
        jnp.asarray(a) for a in tables
    )

    # ---- buffers ------------------------------------------------------

    inbuf0 = _zeros_chunk_ring(run, W1)    # inbuf[s, k, m % W1]: fwd input of (k, m)
    stash0 = _zeros_chunk_ring(run, W1)    # consumed fwd inputs (bwd recompute)
    cotbuf0 = _zeros_chunk_ring(run, W1)   # output cotangent of (k, m)
    outbuf0 = _zeros_stage_ring(run, W1)   # last chunk's fwd output (row S-1 only)
    xfer_f0 = _zeros_stage_rows(run)       # tick t's raw fwd outputs, rolled at t+1
    xfer_b0 = _zeros_stage_rows(run)       # tick t's raw input cotangents, ditto
    dlay0, drep0, dembed0, dsides0, losses0, outs0 = _zero_accumulators(run)

    # ---- recompute planner (stash_all / auto): capture residuals at F,
    # consume at B — everything below is inert at rmode == "full".
    rstash = False
    all_rstash = True
    fres0 = None
    if rmode != "full":
        stash_rings = recompute_ring_plan(*tables, num_stages=S, virtual=V)
        (capture_fwd, _bwd_in, bwd_full_from_res, _wgt,
         _captured) = _make_residual_split(run)
        res_avals = _probe_stash_avals(run, capture_fwd)
        rplan = remat_plan.plan_pipeline(
            "1f1b", rmode, S, V,
            res_ring_slots=stash_rings["f_to_b"], cot_ring_slots=0,
            res_slot_bytes=_stash_slot_bytes(res_avals),
            cot_slot_bytes=0, cfg=run.cfg,
        )
        if rplan.effective != "full":
            rstash = True
            stash_of_arr, res_col_arr, Vs_r, all_rstash = (
                _stash_chunk_maps(rplan, V)
            )
            Rfb = rplan.res_ring_slots
            fres0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros((S, Vs_r, Rfb) + a.shape[1:], a.dtype),
                res_avals,
            )

    def tick_impl(carry, t, do_fwd, do_bwd):
        """One schedule tick. ``do_fwd``/``do_bwd`` are STATIC phase flags:
        warmup ticks compile only the forward sub-step, cooldown ticks only
        the backward one — the idle sub-steps are never part of the
        program, which is what the occupancy accounting assumes."""
        fres = None
        if rstash:
            fres = carry[-1]
            carry = carry[:-1]
        if hc is not None:
            (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay, drep,
             dembed, dsides, losses, outs, (hbad, habs, hmb)) = carry
        else:
            (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay, drep,
             dembed, dsides, losses, outs) = carry

        # ---------------- deferred stage transfers ----------------
        # Tick t-1's fwd outputs / bwd cotangents cross the pp axis here
        # (jnp.roll -> collective-permute) and land in the rings before
        # this tick's compute reads them. Chunk routing: fwd output of
        # (stage s, chunk k) feeds (s+1 mod S, k + [s == S-1]); bwd input
        # cotangent of (s, k) feeds (s-1 mod S, k - [s == 0]).
        prev = jnp.maximum(t - 1, 0)
        was_prev = t > 0
        # Forward merge only in fwd-capable phases: the last forward tick
        # can only contain LAST-chunk forwards (fwd(c,m) < fwd(c+1,m) and
        # nothing later could consume a non-last chunk's output), and those
        # route to outbuf, never the ring — so cooldown ticks would compile
        # a provably all-masked roll (one dead collective-permute per tick).
        if do_fwd:
            pk = fwd_k_sched[prev]
            pm = fwd_m_sched[prev]
            p_act = (pm >= 0) & was_prev
            dst_k = jnp.roll(pk, 1) + (run.stage_ids == 0)
            dst_m = jnp.roll(jnp.maximum(pm, 0), 1)
            # The last chunk's output (dst_k == V) is the head input, kept
            # in outbuf at its producing tick, not routed forward.
            dst_act = jnp.roll(p_act, 1) & (dst_k < V)
            inbuf = _chunk_ring_set(
                inbuf, jnp.clip(dst_k, 0, V - 1), dst_m % W1,
                jax.tree_util.tree_map(lambda o: jnp.roll(o, 1, axis=0), xfer_f),
                dst_act,
            )
        if do_bwd:
            pbk = bwd_k_sched[prev]
            pbm = bwd_m_sched[prev]
            pb_act = (pbm >= 0) & was_prev
            dst_bk = jnp.roll(pbk, -1) - (run.stage_ids == S - 1)
            dst_bm = jnp.roll(jnp.maximum(pbm, 0), -1)
            # Global chunk 0's input cotangent (dst_bk == -1) went to the
            # embedding accumulator at its producing tick.
            dst_b_act = jnp.roll(pb_act, -1) & (dst_bk >= 0)
            cotbuf = _chunk_ring_set(
                cotbuf, jnp.clip(dst_bk, 0, V - 1), dst_bm % W1,
                jax.tree_util.tree_map(lambda o: jnp.roll(o, -1, axis=0), xfer_b),
                dst_b_act,
            )

        # ---------------- forward sub-step ----------------
        if do_fwd:
            fk = fwd_k_sched[t]
            fm = fwd_m_sched[t]
            f_active = fm >= 0
            fkc = jnp.clip(fk, 0, V - 1)
            fmc = jnp.maximum(fm, 0)
            f_slots = fmc % W1
            ch_params = _select_chunk(run.staged_params, fkc)
            ch_xs = _select_chunk(run.staged_xs, fkc)
            ch_act = _select_chunk(run.active_rows, fkc)
            # Stage 0 chunk 0 reads the embedded queue; everything else
            # reads its ring slot.
            from_q = _gather_mb(run.hidden_q, fmc[0])
            buf_in = _chunk_ring_get(inbuf, fkc, f_slots)
            x_in = jax.tree_util.tree_map(
                lambda q, b: b.at[0].set(jnp.where(fkc[0] == 0, q, b[0])),
                from_q, buf_in,
            )
            f_sides = _gather_sides_rows(sides, fmc)
            c_ids = fkc * S + run.stage_ids
            with named_region("smp/pipeline/tick_fwd"):
                if rstash:
                    # Same forward compute; the per-layer vjp capture
                    # additionally emits the residual leaves the backward
                    # sub-step will consume instead of re-running this.
                    outs_f, _aux_f, res_f = stage_vmap(
                        capture_fwd, S,
                        in_axes=(0, 0, 0, 0 if sides is not None else None,
                                 0, 0, 0),
                    )(ch_params, ch_xs, x_in, f_sides, c_ids, fmc, ch_act)
                    fres = _chunk_ring_set(
                        fres, res_col_arr[fkc], fmc % Rfb, res_f,
                        f_active & stash_of_arr[fkc],
                    )
                else:
                    outs_f, _aux_f = stage_vmap(
                        run.chunk_fwd, S,
                        in_axes=(0, 0, 0, 0 if sides is not None else None,
                                 0, 0, 0),
                    )(ch_params, ch_xs, x_in, f_sides, c_ids, fmc, ch_act)
            outs_f = pin_stage_axis(outs_f)
            stash = _chunk_ring_set(stash, fkc, f_slots, x_in, f_active)
            if hc is not None:
                brow, arow = health.stage_row_stats(outs_f, S)
                brow = jnp.where(f_active, brow, 0.0)
                arow = jnp.where(f_active, arow, 0.0)
                hmb = _chunk_scatter_stat(
                    hmb, fkc, fmc.astype(jnp.float32),
                    f_active & (brow > 0),
                    lambda cur, mb: jnp.where(cur < 0, mb, cur),
                )
                hbad = _chunk_scatter_stat(
                    hbad, fkc, brow, f_active, lambda cur, v: cur + v
                )
                habs = _chunk_scatter_stat(
                    habs, fkc, arow, f_active, jnp.maximum
                )
            last_row_active = f_active & (run.stage_ids == S - 1) & (fkc == V - 1)
            outbuf = _stage_ring_set(outbuf, f_slots, outs_f, last_row_active)
            xfer_f = outs_f

        # ---------------- backward sub-step ----------------
        if do_bwd:
            bk = bwd_k_sched[t]
            bm = bwd_m_sched[t]
            b_active = bm >= 0
            bkc = jnp.clip(bk, 0, V - 1)
            bmc = jnp.maximum(bm, 0)
            b_slots = bmc % W1

            # Head + user loss VJP on the stashed LAST-chunk output: only
            # meaningful when stage S-1 backwards chunk V-1 this tick.
            is_lastk = b_active[S - 1] & (bkc[S - 1] == V - 1)
            m_last = bmc[S - 1]
            key_last = jax.lax.dynamic_index_in_dim(
                run.mb_keys, m_last, 0, keepdims=False
            )
            out_last = jax.tree_util.tree_map(
                lambda ob: jax.lax.dynamic_index_in_dim(
                    ob[S - 1], b_slots[S - 1], 0, keepdims=False
                ),
                outbuf,
            )

            run_head = functools.partial(
                _run_head, run, m_last, key_last, out_last
            )

            # Only 1/V of the backward ticks carry the last chunk, but the
            # head+loss VJP is replicated (not stage-parallel) work: run it
            # under lax.cond so the other ticks skip it entirely instead of
            # computing it masked — at vocab-sized heads the masked version
            # would cost ~V x the v=1 executor's replicated compute.
            head_aval = jax.eval_shape(run_head)
            with named_region("smp/pipeline/head"):
                loss_m, d_rep, d_out_last, user_out = jax.lax.cond(
                    is_lastk,
                    run_head,
                    lambda: jax.tree_util.tree_map(
                        lambda a: jnp.zeros(a.shape, a.dtype), head_aval
                    ),
                )

            cot_in = _chunk_ring_get(cotbuf, bkc, b_slots)
            cot_in = jax.tree_util.tree_map(
                lambda c, d: c.at[S - 1].set(
                    jnp.where(is_lastk, d.astype(c.dtype), c[S - 1])
                ),
                cot_in, d_out_last,
            )
            b_sides = _gather_sides_rows(sides, bmc)
            stash_in = _chunk_ring_get(stash, bkc, b_slots)
            ch_params_b = _select_chunk(run.staged_params, bkc)
            ch_xs_b = _select_chunk(run.staged_xs, bkc)
            ch_act_b = _select_chunk(run.active_rows, bkc)
            c_ids_b = bkc * S + run.stage_ids

            d_side_leaf_rows = None
            with named_region("smp/pipeline/tick_bwd"):
                if rstash:
                    # Backward from the residuals the forward sub-step
                    # stashed: no forward re-run for stashed chunks.
                    res_b = _chunk_ring_get(fres, res_col_arr[bkc], bmc % Rfb)
                    d_lp_res, d_x_res, side_res = stage_vmap(
                        bwd_full_from_res, S
                    )(res_b, cot_in)
                    if all_rstash:
                        d_lp_rows, d_x_rows = d_lp_res, d_x_res
                        d_side_leaf_rows = side_res
                    else:
                        # Budget-degraded chunks keep the recompute path;
                        # a static per-chunk mask selects.
                        d_lp_rec, d_x_rec, d_side_rec = stage_vmap(
                            functools.partial(_chunk_bwd, run), S,
                            in_axes=(0, 0, 0,
                                     0 if sides is not None else None,
                                     0, 0, 0, 0),
                        )(ch_params_b, ch_xs_b, stash_in,
                          b_sides, cot_in, c_ids_b, bmc, ch_act_b)
                        bmask = stash_of_arr[bkc]

                        def sel(a, b):
                            return jnp.where(
                                bmask.reshape((S,) + (1,) * (a.ndim - 1)),
                                a, b.astype(a.dtype),
                            )

                        d_lp_rows = jax.tree_util.tree_map(
                            sel, d_lp_res, d_lp_rec
                        )
                        d_x_rows = jax.tree_util.tree_map(
                            sel, d_x_res, d_x_rec
                        )
                        if sides is not None:
                            rec_all, _, _ = _inexact_leaves(d_side_rec)
                            d_side_leaf_rows = [
                                sel(a, rec_all[i])
                                for a, i in zip(side_res, run.side_idx)
                            ]
                else:
                    d_lp_rows, d_x_rows, d_side_rows = stage_vmap(
                        functools.partial(_chunk_bwd, run), S,
                        in_axes=(0, 0, 0, 0 if sides is not None else None,
                                 0, 0, 0, 0),
                    )(ch_params_b, ch_xs_b, stash_in,
                      b_sides, cot_in, c_ids_b, bmc, ch_act_b)
            d_lp_rows = pin_stage_axis(d_lp_rows)
            d_x_rows = pin_stage_axis(d_x_rows)

            # Accumulate layer grads into the per-(stage, chunk) slot.
            dlay = _chunk_acc_rows(dlay, d_lp_rows, bkc, b_active)

            drep = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(is_lastk, g.astype(a.dtype), 0),
                drep, d_rep,
            )

            dembed = _chunk_scatter_add_mb(
                dembed, bmc[0],
                jax.tree_util.tree_map(lambda r: r[0], d_x_rows),
                b_active[0] & (bkc[0] == 0),
            )

            if sides is not None and dsides is not None:
                if d_side_leaf_rows is not None:
                    for s in range(S):
                        dsides = [
                            _chunk_scatter_add_leaf(d, bmc[s], leaf[s], b_active[s])
                            for d, leaf in zip(dsides, d_side_leaf_rows)
                        ]
                else:
                    dsides = _add_side_cotangents(
                        dsides, d_side_rows, run.side_idx, bmc, b_active
                    )

            losses = losses.at[m_last].set(
                jnp.where(is_lastk, loss_m.astype(jnp.float32), losses[m_last])
            )
            outs = _chunk_scatter_set_mb(outs, m_last, user_out, is_lastk)
            xfer_b = d_x_rows

        new_carry = (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay,
                     drep, dembed, dsides, losses, outs)
        if hc is not None:
            new_carry = new_carry + ((hbad, habs, hmb),)
        if rstash:
            new_carry = new_carry + (fres,)
        return new_carry, None

    carry0 = (
        pin_stage_axis(inbuf0), pin_stage_axis(stash0),
        pin_stage_axis(cotbuf0), pin_stage_axis(outbuf0),
        pin_stage_axis(xfer_f0), pin_stage_axis(xfer_b0),
        pin_stage_axis(dlay0), drep0, dembed0, dsides0, losses0, outs0,
    )
    if hc is not None:
        carry0 = carry0 + (_zeros_health_grids(S, V),)
    if rstash:
        carry0 = carry0 + (pin_stage_axis(fres0),)

    # Named profiler regions per schedule phase: an XLA trace of the
    # compiled step shows the warmup/steady/cooldown loops as separately
    # labeled op groups, so bubble time is attributable to its ramp.
    with named_region("smp/pipeline/warmup"):
        carry_end, _ = jax.lax.scan(
            lambda c, t: tick_impl(c, t, True, False), carry0,
            jnp.arange(0, t_b0),
        )
    with named_region("smp/pipeline/steady"):
        carry_end, _ = jax.lax.scan(
            lambda c, t: tick_impl(c, t, True, True), carry_end,
            jnp.arange(t_b0, t_fe),
        )
    with named_region("smp/pipeline/cooldown"):
        carry_end, _ = jax.lax.scan(
            lambda c, t: tick_impl(c, t, False, True), carry_end,
            jnp.arange(t_fe, n_ticks),
        )
    if rstash:
        carry_end = carry_end[:-1]
    if hc is not None:
        (_, _, _, _, _, _, dlay, drep, dembed, dsides, losses, outs,
         (hbad, habs, hmb)) = carry_end
        # Grid position (s, k) holds GLOBAL chunk k*S + s.
        chunk_ids = np.arange(V)[None, :] * S + np.arange(S)[:, None]
        hc.add_stage_stats("1f1b", hbad, habs, hmb, chunk_ids=chunk_ids)
    else:
        (_, _, _, _, _, _, dlay, drep, dembed, dsides, losses,
         outs) = carry_end

    # The chunked placement interleaves the layer axis across stages, so
    # [S, V, maxp, ...] -> [L, ...] is always a scatter-add (the v=1
    # dense-reshape shortcut cannot apply).
    return _finish_run(
        run, _rows_to_layers(run.idx_np, run.active_np, run.L),
        dlay, drep, dembed, dsides, losses, outs,
    )


def _record_zero_bubble_schedule(run, tables, ring_plan, pass_ticks):
    """Gauges and flight-recorder slots of a zero-bubble build.
    ``pass_ticks``: executed ticks per pass, the occupancy's denominator."""
    S, M, V = run.S, run.M, run.V
    fwd_k_np, fwd_m_np, bwd_k_np, bwd_m_np, wgt_k_np, wgt_m_np = tables
    busy, total = schedule_occupancy(
        fwd_m_np, bwd_m_np, fwd_ticks=pass_ticks["fwd"],
        bwd_ticks=pass_ticks["bwd_input"], wgt=wgt_m_np,
        wgt_ticks=pass_ticks["bwd_weight"],
    )
    record_pipeline_occupancy(
        "zb", S, M, busy_slots=busy, total_slots=total, virtual=V,
        passes=3, pass_ticks=pass_ticks,
    )
    # W-queue accounting next to the occupancy gauges: ring slots actually
    # allocated per (stage, chunk) and the peak number of deferred
    # weight-grad units — the memory side of the bubble trade.
    telemetry.gauge(
        "smp_pipeline_ring_slots",
        "per-(stage, chunk) ring-buffer slots of the pipeline executor",
    ).labels(schedule="zb").set(ring_plan["ring_slots"])
    telemetry.gauge(
        "smp_pipeline_wqueue_peak",
        "peak deferred weight-grad units per (stage, chunk) [zero-bubble]",
    ).labels(schedule="zb").set(ring_plan["w_queue_peak"])
    flight_recorder.record_schedule(
        "zb",
        _chunk_slots(S, (("fwd", fwd_k_np, fwd_m_np, "F"),
                         ("bwd_input", bwd_k_np, bwd_m_np, "B"),
                         ("bwd_weight", wgt_k_np, wgt_m_np, "W"))),
    )


def _add_zero_bubble_stage_stats(run, hstats):
    """The zero-bubble executors' sentinel rows into the collector. Grid
    position (s, k) holds GLOBAL chunk k*S + s; tags carry the pass
    coordinate so a tripped sentinel attributes to the exact (chunk,
    pass) — forward activations vs input cotangents."""
    S, V = run.S, run.V
    ((hbad, habs, hmb), (hbad_b, habs_b, hmb_b)) = hstats
    chunk_ids = np.arange(V)[None, :] * S + np.arange(S)[:, None]
    run.hc.add_stage_stats("zb", hbad, habs, hmb, chunk_ids=chunk_ids,
                           pass_name="fwd")
    run.hc.add_stage_stats("zb", hbad_b, habs_b, hmb_b, chunk_ids=chunk_ids,
                           pass_name="bwd_input")


def _pipeline_zero_bubble(run):
    """ZB-H1 executor: backward split into B (input-grad) and W
    (weight-grad) passes over (chunk, microbatch, pass) schedule units.

    Same numerical contract as the 1F1B executors (grads/losses/outputs
    interchangeable with the fill-drain path at any (pp, v, mb, window));
    the schedule shape differs from ``_pipeline_1f1b_virtual`` in one
    way: each tick has up to THREE sub-steps — F, B, W — and the
    monolithic per-chunk VJP is split:

    - the B sub-step re-runs the chunk forward from the stashed input
      under ``jax.vjp`` w.r.t. (input, sides) ONLY: the input cotangent
      ships upstream immediately (it is the critical path) and the
      weight cotangent is never formed;
    - the W sub-step re-runs the same forward under ``jax.vjp`` w.r.t.
      the chunk params at a LATER tick, re-reading the stashed input and
      the retained output cotangent — the deferred weight-grad work that
      fills the B-drain cooldown, where the monolithic schedule idles;
    - the ring buffers double as the W-queue: stash/cotangent entries
      stay live until the W pass consumes them, so the ring slot count
      comes from ``parallel/memory.py::zero_bubble_ring_plan`` (exact
      alive-depth over the static schedule; == window+1 at the default
      window, i.e. ZB's same-activation-memory claim holds exactly);
    - the head/loss VJP stays monolithic at the last chunk's B tick (it
      produces the cotangent B needs; its param grads are replicated
      work, not a pipeline stage) and its output cotangent is written
      INTO the cotangent ring so the last chunk's W can re-read it.

    The tick loop compiles one scan per contiguous segment of active
    passes (``_zb_segments``): warmup ticks are F-only, the B-drain
    cooldown compiles B+W, and a possible W-only tail drains the queue —
    out-of-span sub-steps never enter the program, which is what the
    occupancy accounting (2*(pp-1)/(3*v*mb + 2*(pp-1)) at the packed
    configs) assumes. GSPMD stage-axis pins and the double-buffered
    transfer registers carry over from the virtual executor unchanged
    (W produces no transfers: weight grads stay stage-local).
    """
    S, M, V, sides, hc = run.S, run.M, run.V, run.sides, run.hc
    pin_stage_axis = functools.partial(_pin_stage_axis, num_stages=S)

    tables = build_zero_bubble_schedule(S, M, run.W, V)
    _, fwd_m_np, _, bwd_m_np, _, wgt_m_np = tables
    n_ticks = fwd_m_np.shape[0]
    f_span, b_span, w_span = zero_bubble_phase_bounds(
        fwd_m_np, bwd_m_np, wgt_m_np
    )
    segments = _zb_segments(f_span, b_span, w_span, n_ticks)
    plan = zero_bubble_ring_plan(
        *tables, num_stages=S, virtual=V, window=run.W
    )
    R1 = plan["ring_slots"]
    _record_zero_bubble_schedule(run, tables, plan, {
        "fwd": f_span[1] - f_span[0], "bwd_input": b_span[1] - b_span[0],
        "bwd_weight": w_span[1] - w_span[0],
    })
    (fwd_k_sched, fwd_m_sched, bwd_k_sched, bwd_m_sched, wgt_k_sched,
     wgt_m_sched) = (jnp.asarray(a) for a in tables)

    # ---- buffers ------------------------------------------------------

    # Ring slot count R1 comes from the memory plan: stash and cotangent
    # entries live until the W pass (not just B), so the alive depth can
    # exceed the 1F1B executors' window+1 — but never does at the default
    # window (the deferral hides inside the slack the in-flight cap
    # already paid for).
    inbuf0 = _zeros_chunk_ring(run, R1)    # inbuf[s, k, m % R1]: fwd input of (k, m)
    stash0 = _zeros_chunk_ring(run, R1)    # consumed fwd inputs (B AND W recompute)
    cotbuf0 = _zeros_chunk_ring(run, R1)   # output cotangent of (k, m); W re-reads
    outbuf0 = _zeros_stage_ring(run, R1)   # last chunk's fwd output (row S-1 only)
    xfer_f0 = _zeros_stage_rows(run)       # tick t's raw fwd outputs, rolled at t+1
    xfer_b0 = _zeros_stage_rows(run)       # tick t's raw input cotangents, ditto
    dlay0, drep0, dembed0, dsides0, losses0, outs0 = _zero_accumulators(run)

    def tick_impl(carry, t, do_fwd, do_bwd, do_wgt):
        """One schedule tick. The pass flags are STATIC per segment:
        out-of-span sub-steps are never compiled. Sub-step order within a
        tick is F -> B -> W, which is what legalizes same-tick B(c,m)
        after F(c,m) (last chunk) and W(c,m) after B(c,m)."""
        if hc is not None:
            (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay, drep,
             dembed, dsides, losses, outs, hstats) = carry
            ((hbad, habs, hmb), (hbad_b, habs_b, hmb_b)) = hstats
        else:
            (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay, drep,
             dembed, dsides, losses, outs) = carry

        # ---------------- deferred stage transfers ----------------
        # Tick t-1's fwd outputs / input cotangents cross the pp axis
        # here (jnp.roll -> collective-permute), exactly as in the
        # virtual executor. Gating on the CURRENT segment's flags is
        # legal for the same reason as there: the last F tick can only
        # contain last-chunk forwards (routed to outbuf) and the last B
        # tick only chunk-0 backwards (routed to the embedding), so the
        # first tick outside a span has nothing to merge. W produces no
        # transfers at all — weight grads stay stage-local.
        prev = jnp.maximum(t - 1, 0)
        was_prev = t > 0
        if do_fwd:
            pk = fwd_k_sched[prev]
            pm = fwd_m_sched[prev]
            p_act = (pm >= 0) & was_prev
            dst_k = jnp.roll(pk, 1) + (run.stage_ids == 0)
            dst_m = jnp.roll(jnp.maximum(pm, 0), 1)
            dst_act = jnp.roll(p_act, 1) & (dst_k < V)
            inbuf = _chunk_ring_set(
                inbuf, jnp.clip(dst_k, 0, V - 1), dst_m % R1,
                jax.tree_util.tree_map(lambda o: jnp.roll(o, 1, axis=0), xfer_f),
                dst_act,
            )
        if do_bwd:
            pbk = bwd_k_sched[prev]
            pbm = bwd_m_sched[prev]
            pb_act = (pbm >= 0) & was_prev
            dst_bk = jnp.roll(pbk, -1) - (run.stage_ids == S - 1)
            dst_bm = jnp.roll(jnp.maximum(pbm, 0), -1)
            dst_b_act = jnp.roll(pb_act, -1) & (dst_bk >= 0)
            cotbuf = _chunk_ring_set(
                cotbuf, jnp.clip(dst_bk, 0, V - 1), dst_bm % R1,
                jax.tree_util.tree_map(lambda o: jnp.roll(o, -1, axis=0), xfer_b),
                dst_b_act,
            )

        # ---------------- forward sub-step ----------------
        if do_fwd:
            fk = fwd_k_sched[t]
            fm = fwd_m_sched[t]
            f_active = fm >= 0
            fkc = jnp.clip(fk, 0, V - 1)
            fmc = jnp.maximum(fm, 0)
            f_slots = fmc % R1
            ch_params = _select_chunk(run.staged_params, fkc)
            ch_xs = _select_chunk(run.staged_xs, fkc)
            ch_act = _select_chunk(run.active_rows, fkc)
            from_q = _gather_mb(run.hidden_q, fmc[0])
            buf_in = _chunk_ring_get(inbuf, fkc, f_slots)
            x_in = jax.tree_util.tree_map(
                lambda q, b: b.at[0].set(jnp.where(fkc[0] == 0, q, b[0])),
                from_q, buf_in,
            )
            f_sides = _gather_sides_rows(sides, fmc)
            c_ids = fkc * S + run.stage_ids
            with named_region("smp/pipeline/tick_fwd"):
                outs_f, _aux_f = stage_vmap(
                    run.chunk_fwd, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0),
                )(ch_params, ch_xs, x_in, f_sides, c_ids, fmc, ch_act)
            outs_f = pin_stage_axis(outs_f)
            stash = _chunk_ring_set(stash, fkc, f_slots, x_in, f_active)
            if hc is not None:
                brow, arow = health.stage_row_stats(outs_f, S)
                brow = jnp.where(f_active, brow, 0.0)
                arow = jnp.where(f_active, arow, 0.0)
                hmb = _chunk_scatter_stat(
                    hmb, fkc, fmc.astype(jnp.float32),
                    f_active & (brow > 0),
                    lambda cur, mb: jnp.where(cur < 0, mb, cur),
                )
                hbad = _chunk_scatter_stat(
                    hbad, fkc, brow, f_active, lambda cur, v: cur + v
                )
                habs = _chunk_scatter_stat(
                    habs, fkc, arow, f_active, jnp.maximum
                )
            last_row_active = f_active & (run.stage_ids == S - 1) & (fkc == V - 1)
            outbuf = _stage_ring_set(outbuf, f_slots, outs_f, last_row_active)
            xfer_f = outs_f

        # ---------------- backward-input sub-step ----------------
        if do_bwd:
            bk = bwd_k_sched[t]
            bm = bwd_m_sched[t]
            b_active = bm >= 0
            bkc = jnp.clip(bk, 0, V - 1)
            bmc = jnp.maximum(bm, 0)
            b_slots = bmc % R1

            is_lastk = b_active[S - 1] & (bkc[S - 1] == V - 1)
            m_last = bmc[S - 1]
            key_last = jax.lax.dynamic_index_in_dim(
                run.mb_keys, m_last, 0, keepdims=False
            )
            out_last = jax.tree_util.tree_map(
                lambda ob: jax.lax.dynamic_index_in_dim(
                    ob[S - 1], b_slots[S - 1], 0, keepdims=False
                ),
                outbuf,
            )

            run_head = functools.partial(
                _run_head, run, m_last, key_last, out_last
            )

            head_aval = jax.eval_shape(run_head)
            with named_region("smp/pipeline/head"):
                loss_m, d_rep, d_out_last, user_out = jax.lax.cond(
                    is_lastk,
                    run_head,
                    lambda: jax.tree_util.tree_map(
                        lambda a: jnp.zeros(a.shape, a.dtype), head_aval
                    ),
                )

            cot_in = _chunk_ring_get(cotbuf, bkc, b_slots)
            cot_in = jax.tree_util.tree_map(
                lambda c, d: c.at[S - 1].set(
                    jnp.where(is_lastk, d.astype(c.dtype), c[S - 1])
                ),
                cot_in, d_out_last,
            )
            # Retain the head cotangent in the ring: unlike the fused
            # executors, the last chunk's backward touches its cotangent
            # TWICE (B now, W later) and only B gets it from the head
            # VJP. Masked to the producing row so other stages' ring
            # entries are untouched.
            cotbuf = _chunk_ring_set(
                cotbuf, bkc, b_slots, cot_in,
                b_active & (run.stage_ids == S - 1) & (bkc == V - 1),
            )
            b_sides = _gather_sides_rows(sides, bmc)
            stash_in = _chunk_ring_get(stash, bkc, b_slots)
            ch_params_b = _select_chunk(run.staged_params, bkc)
            ch_xs_b = _select_chunk(run.staged_xs, bkc)
            ch_act_b = _select_chunk(run.active_rows, bkc)
            c_ids_b = bkc * S + run.stage_ids

            def chunk_bwd_input(lp, lxs, x, side, cot, c_idx, m_idx, act_row):
                """Input-grad pass: VJP w.r.t. (input, sides) only — the
                weight cotangent is never formed here."""

                def f(x_, side_):
                    return run.chunk_fwd(lp, lxs, x_, side_, c_idx, m_idx, act_row)

                _, vjp = jax.vjp(f, x, side)
                return vjp((cot, run.aux_seed))

            with named_region("smp/pipeline/tick_bwd_input"):
                d_x_rows, d_side_rows = stage_vmap(
                    chunk_bwd_input, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0, 0),
                )(ch_params_b, ch_xs_b, stash_in,
                  b_sides, cot_in, c_ids_b, bmc, ch_act_b)
            d_x_rows = pin_stage_axis(d_x_rows)

            if hc is not None:
                brow_b, arow_b = health.stage_row_stats(d_x_rows, S)
                brow_b = jnp.where(b_active, brow_b, 0.0)
                arow_b = jnp.where(b_active, arow_b, 0.0)
                hmb_b = _chunk_scatter_stat(
                    hmb_b, bkc, bmc.astype(jnp.float32),
                    b_active & (brow_b > 0),
                    lambda cur, mb: jnp.where(cur < 0, mb, cur),
                )
                hbad_b = _chunk_scatter_stat(
                    hbad_b, bkc, brow_b, b_active, lambda cur, v: cur + v
                )
                habs_b = _chunk_scatter_stat(
                    habs_b, bkc, arow_b, b_active, jnp.maximum
                )

            drep = jax.tree_util.tree_map(
                lambda a, g: a + jnp.where(is_lastk, g.astype(a.dtype), 0),
                drep, d_rep,
            )

            dembed = _chunk_scatter_add_mb(
                dembed, bmc[0],
                jax.tree_util.tree_map(lambda r: r[0], d_x_rows),
                b_active[0] & (bkc[0] == 0),
            )

            if sides is not None and dsides is not None:
                dsides = _add_side_cotangents(
                    dsides, d_side_rows, run.side_idx, bmc, b_active
                )

            losses = losses.at[m_last].set(
                jnp.where(is_lastk, loss_m.astype(jnp.float32), losses[m_last])
            )
            outs = _chunk_scatter_set_mb(outs, m_last, user_out, is_lastk)
            xfer_b = d_x_rows

        # ---------------- weight-grad sub-step ----------------
        if do_wgt:
            wk = wgt_k_sched[t]
            wm = wgt_m_sched[t]
            w_active = wm >= 0
            wkc = jnp.clip(wk, 0, V - 1)
            wmc = jnp.maximum(wm, 0)
            w_slots = wmc % R1

            w_sides = _gather_sides_rows(sides, wmc)
            stash_w = _chunk_ring_get(stash, wkc, w_slots)
            cot_w = _chunk_ring_get(cotbuf, wkc, w_slots)
            ch_params_w = _select_chunk(run.staged_params, wkc)
            ch_xs_w = _select_chunk(run.staged_xs, wkc)
            ch_act_w = _select_chunk(run.active_rows, wkc)
            c_ids_w = wkc * S + run.stage_ids

            with named_region("smp/pipeline/tick_bwd_weight"):
                d_lp_rows = stage_vmap(
                    functools.partial(_chunk_bwd_weight, run), S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0, 0),
                )(ch_params_w, ch_xs_w, stash_w,
                  w_sides, cot_w, c_ids_w, wmc, ch_act_w)
            d_lp_rows = pin_stage_axis(d_lp_rows)
            dlay = _chunk_acc_rows(dlay, d_lp_rows, wkc, w_active)

        new_carry = (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, dlay,
                     drep, dembed, dsides, losses, outs)
        if hc is not None:
            new_carry = new_carry + (
                ((hbad, habs, hmb), (hbad_b, habs_b, hmb_b)),
            )
        return new_carry, None

    carry0 = (
        pin_stage_axis(inbuf0), pin_stage_axis(stash0),
        pin_stage_axis(cotbuf0), pin_stage_axis(outbuf0),
        pin_stage_axis(xfer_f0), pin_stage_axis(xfer_b0),
        pin_stage_axis(dlay0), drep0, dembed0, dsides0, losses0, outs0,
    )
    if hc is not None:
        carry0 = carry0 + (
            (_zeros_health_grids(S, V), _zeros_health_grids(S, V)),
        )

    carry_end = carry0
    for a, b, (do_f, do_b, do_w) in segments:
        with named_region(_zb_segment_region(do_f, do_b, do_w)):
            carry_end, _ = jax.lax.scan(
                lambda c, t, f=do_f, bb=do_b, w=do_w: tick_impl(
                    c, t, f, bb, w
                ),
                carry_end, jnp.arange(a, b),
            )
    if hc is not None:
        (_, _, _, _, _, _, dlay, drep, dembed, dsides, losses, outs,
         hstats) = carry_end
        _add_zero_bubble_stage_stats(run, hstats)
    else:
        (_, _, _, _, _, _, dlay, drep, dembed, dsides, losses,
         outs) = carry_end

    return _finish_run(
        run, _rows_to_layers(run.idx_np, run.active_np, run.L),
        dlay, drep, dembed, dsides, losses, outs,
    )


def _pipeline_zero_bubble_stash(run, rmode):
    """ZB-H1 executor under a non-default recompute plan
    (``recompute: stash_weight | stash_all | auto``). Returns ``None``,
    before anything is recorded or emitted, when the plan degrades every
    chunk: the dispatch then runs ``_pipeline_zero_bubble`` on the same
    ``run``.

    Same numerical contract and schedule as ``_pipeline_zero_bubble``;
    two structural differences, both existing only on this knob-gated
    path (the default executor stays byte-identical):

    - **Residual stash instead of W-pass recompute**: the B sub-step
      runs the chunk forward as per-layer ``jax.vjp`` captures
      (``_make_residual_split``), writing the flattened residual leaves
      and the per-layer output cotangents into stash rings sized by
      ``memory.recompute_ring_plan``; the deferred W sub-step rebuilds
      each layer's vjp from the rings and computes weight-grad matmuls
      ONLY — no forward re-run, no cotangent chain. Under ``stash_all``
      the residuals are captured at the F sub-step itself, so B skips
      its forward too. ``auto`` plans per-(stage, chunk): degraded
      chunks keep the recompute path (both paths compile, selected by a
      static per-chunk mask).

    - **One scan, conditional sub-steps**: instead of one compiled scan
      per contiguous segment of active passes, the whole tick range is
      ONE scan whose F/B/W sub-steps run under ``lax.cond`` keyed by
      static per-tick activity arrays. Out-of-phase ticks skip their
      sub-steps at runtime (same executed work as the segmented loops,
      modulo rare mid-span gap ticks, which execute masked), and each
      pass's ops are compiled exactly ONCE — the segmented executor
      compiles every pass into each of its segments, which is most of
      what the structural remat census counts against the ZB schedule.
    """
    S, M, V, sides, hc = run.S, run.M, run.V, run.sides, run.hc
    pin_stage_axis = functools.partial(_pin_stage_axis, num_stages=S)

    tables = build_zero_bubble_schedule(S, M, run.W, V)
    fwd_k_np, fwd_m_np, bwd_k_np, bwd_m_np, _, wgt_m_np = tables
    n_ticks = fwd_m_np.shape[0]
    plan_rings = zero_bubble_ring_plan(
        *tables, num_stages=S, virtual=V, window=run.W
    )
    R1 = plan_rings["ring_slots"]
    stash_rings = recompute_ring_plan(*tables, num_stages=S, virtual=V)

    # ---- residual split and the plan ----------------------------------

    capture_fwd, bwd_from_res, _bwd_full, wgt_from_res, _captured = (
        _make_residual_split(run)
    )
    # Probe the residual/cotangent stash shapes (and capture the vjp
    # treedef) with an abstract trace of one B-style capture row sweep.
    res_avals, cot_avals = _probe_stash_avals(
        run, capture_fwd, bwd_from_res=bwd_from_res
    )
    plan = remat_plan.plan_pipeline(
        "zb", rmode, S, V,
        res_ring_slots=(stash_rings["f_to_w"] if rmode == "stash_all"
                        else stash_rings["b_to_w"]),
        cot_ring_slots=stash_rings["b_to_w"],
        res_slot_bytes=_stash_slot_bytes(res_avals),
        cot_slot_bytes=_stash_slot_bytes(cot_avals), cfg=run.cfg,
    )
    if plan.effective == "full":
        return None
    capture_at_f = plan.effective == "stash_all"
    stash_of_arr, res_col_arr, Vs, all_stash = _stash_chunk_maps(plan, V)
    Rres = plan.res_ring_slots
    Rcot = plan.cot_ring_slots

    # Static per-tick activity: which sub-steps this tick executes. A
    # sub-step also runs (masked) on a tick whose PREVIOUS tick produced
    # stage transfers that still need merging — the transfer registers
    # hold exactly one tick, so the merge cannot be deferred past it.
    stage_col = np.arange(S)[None, :]
    f_any = (fwd_m_np >= 0).any(axis=1)
    b_any = (bwd_m_np >= 0).any(axis=1)
    w_any = (wgt_m_np >= 0).any(axis=1)
    f_xfer = ((fwd_m_np >= 0)
              & ~((stage_col == S - 1) & (fwd_k_np == V - 1))).any(axis=1)
    b_xfer = ((bwd_m_np >= 0)
              & ~((stage_col == 0) & (bwd_k_np == 0))).any(axis=1)
    f_run = f_any.copy()
    f_run[1:] |= f_xfer[:-1]
    b_run = b_any.copy()
    b_run[1:] |= b_xfer[:-1]
    w_run = w_any

    _record_zero_bubble_schedule(run, tables, plan_rings, {
        "fwd": int(f_run.sum()), "bwd_input": int(b_run.sum()),
        "bwd_weight": int(w_run.sum()),
    })
    (fwd_k_sched, fwd_m_sched, bwd_k_sched, bwd_m_sched, wgt_k_sched,
     wgt_m_sched) = (jnp.asarray(a) for a in tables)
    f_run_sched = jnp.asarray(f_run)
    b_run_sched = jnp.asarray(b_run)
    w_run_sched = jnp.asarray(w_run)

    # ---- buffers ------------------------------------------------------

    def zeros_stash_ring(avals, n):
        # [S, Vs, n, ...]: stage axis leads (pp-sharded like its
        # siblings); leaf shapes come from the probe avals (leading
        # stage axis dropped).
        return jax.tree_util.tree_map(
            lambda a: jnp.zeros((S, Vs, n) + a.shape[1:], a.dtype), avals
        )

    inbuf0 = _zeros_chunk_ring(run, R1)
    stash0 = _zeros_chunk_ring(run, R1)
    cotbuf0 = _zeros_chunk_ring(run, R1)
    outbuf0 = _zeros_stage_ring(run, R1)
    xfer_f0 = _zeros_stage_rows(run)
    xfer_b0 = _zeros_stage_rows(run)
    wres0 = zeros_stash_ring(res_avals, Rres)
    wcot0 = zeros_stash_ring(cot_avals, Rcot)
    dlay0, drep0, dembed0, dsides0, losses0, outs0 = _zero_accumulators(run)

    # ---- sub-steps (each a lax.cond branch over the whole carry) ------

    def f_substep(carry, t):
        (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot, dlay,
         drep, dembed, dsides, losses, outs, hstats) = carry
        (hbad, habs, hmb), hstats_b = hstats

        prev = jnp.maximum(t - 1, 0)
        was_prev = t > 0
        pk = fwd_k_sched[prev]
        pm = fwd_m_sched[prev]
        p_act = (pm >= 0) & was_prev
        dst_k = jnp.roll(pk, 1) + (run.stage_ids == 0)
        dst_m = jnp.roll(jnp.maximum(pm, 0), 1)
        dst_act = jnp.roll(p_act, 1) & (dst_k < V)
        inbuf = _chunk_ring_set(
            inbuf, jnp.clip(dst_k, 0, V - 1), dst_m % R1,
            jax.tree_util.tree_map(lambda o: jnp.roll(o, 1, axis=0), xfer_f),
            dst_act,
        )

        fk = fwd_k_sched[t]
        fm = fwd_m_sched[t]
        f_active = fm >= 0
        fkc = jnp.clip(fk, 0, V - 1)
        fmc = jnp.maximum(fm, 0)
        f_slots = fmc % R1
        ch_params = _select_chunk(run.staged_params, fkc)
        ch_xs = _select_chunk(run.staged_xs, fkc)
        ch_act = _select_chunk(run.active_rows, fkc)
        from_q = _gather_mb(run.hidden_q, fmc[0])
        buf_in = _chunk_ring_get(inbuf, fkc, f_slots)
        x_in = jax.tree_util.tree_map(
            lambda q, b: b.at[0].set(jnp.where(fkc[0] == 0, q, b[0])),
            from_q, buf_in,
        )
        f_sides = _gather_sides_rows(sides, fmc)
        c_ids = fkc * S + run.stage_ids
        with named_region("smp/pipeline/tick_fwd"):
            if capture_at_f:
                outs_f, _aux_f, res_f = stage_vmap(
                    capture_fwd, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0),
                )(ch_params, ch_xs, x_in, f_sides, c_ids, fmc, ch_act)
                wres = _chunk_ring_set(
                    wres, res_col_arr[fkc], fmc % Rres, res_f,
                    f_active & stash_of_arr[fkc],
                )
            else:
                outs_f, _aux_f = stage_vmap(
                    run.chunk_fwd, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0),
                )(ch_params, ch_xs, x_in, f_sides, c_ids, fmc, ch_act)
        outs_f = pin_stage_axis(outs_f)
        stash = _chunk_ring_set(stash, fkc, f_slots, x_in, f_active)
        if hc is not None:
            brow, arow = health.stage_row_stats(outs_f, S)
            brow = jnp.where(f_active, brow, 0.0)
            arow = jnp.where(f_active, arow, 0.0)
            hmb = _chunk_scatter_stat(
                hmb, fkc, fmc.astype(jnp.float32),
                f_active & (brow > 0),
                lambda cur, mb: jnp.where(cur < 0, mb, cur),
            )
            hbad = _chunk_scatter_stat(
                hbad, fkc, brow, f_active, lambda cur, v: cur + v
            )
            habs = _chunk_scatter_stat(
                habs, fkc, arow, f_active, jnp.maximum
            )
        last_row_active = f_active & (run.stage_ids == S - 1) & (fkc == V - 1)
        outbuf = _stage_ring_set(outbuf, f_slots, outs_f, last_row_active)
        xfer_f = outs_f
        return (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot,
                dlay, drep, dembed, dsides, losses, outs,
                ((hbad, habs, hmb), hstats_b))

    def b_substep(carry, t):
        (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot, dlay,
         drep, dembed, dsides, losses, outs, hstats) = carry
        hstats_f, (hbad_b, habs_b, hmb_b) = hstats

        prev = jnp.maximum(t - 1, 0)
        was_prev = t > 0
        pbk = bwd_k_sched[prev]
        pbm = bwd_m_sched[prev]
        pb_act = (pbm >= 0) & was_prev
        dst_bk = jnp.roll(pbk, -1) - (run.stage_ids == S - 1)
        dst_bm = jnp.roll(jnp.maximum(pbm, 0), -1)
        dst_b_act = jnp.roll(pb_act, -1) & (dst_bk >= 0)
        cotbuf = _chunk_ring_set(
            cotbuf, jnp.clip(dst_bk, 0, V - 1), dst_bm % R1,
            jax.tree_util.tree_map(lambda o: jnp.roll(o, -1, axis=0), xfer_b),
            dst_b_act,
        )

        bk = bwd_k_sched[t]
        bm = bwd_m_sched[t]
        b_active = bm >= 0
        bkc = jnp.clip(bk, 0, V - 1)
        bmc = jnp.maximum(bm, 0)
        b_slots = bmc % R1

        is_lastk = b_active[S - 1] & (bkc[S - 1] == V - 1)
        m_last = bmc[S - 1]
        key_last = jax.lax.dynamic_index_in_dim(
            run.mb_keys, m_last, 0, keepdims=False
        )
        out_last = jax.tree_util.tree_map(
            lambda ob: jax.lax.dynamic_index_in_dim(
                ob[S - 1], b_slots[S - 1], 0, keepdims=False
            ),
            outbuf,
        )

        run_head = functools.partial(
            _run_head, run, m_last, key_last, out_last
        )

        head_aval = jax.eval_shape(run_head)
        with named_region("smp/pipeline/head"):
            loss_m, d_rep, d_out_last, user_out = jax.lax.cond(
                is_lastk,
                run_head,
                lambda: jax.tree_util.tree_map(
                    lambda a: jnp.zeros(a.shape, a.dtype), head_aval
                ),
            )

        cot_in = _chunk_ring_get(cotbuf, bkc, b_slots)
        cot_in = jax.tree_util.tree_map(
            lambda c, d: c.at[S - 1].set(
                jnp.where(is_lastk, d.astype(c.dtype), c[S - 1])
            ),
            cot_in, d_out_last,
        )
        # Retain the head cotangent for a possible RECOMPUTE W pass on a
        # degraded last chunk (mixed auto plans); harmless otherwise.
        cotbuf = _chunk_ring_set(
            cotbuf, bkc, b_slots, cot_in,
            b_active & (run.stage_ids == S - 1) & (bkc == V - 1),
        )
        b_sides = _gather_sides_rows(sides, bmc)
        stash_in = _chunk_ring_get(stash, bkc, b_slots)
        ch_params_b = _select_chunk(run.staged_params, bkc)
        ch_xs_b = _select_chunk(run.staged_xs, bkc)
        ch_act_b = _select_chunk(run.active_rows, bkc)
        c_ids_b = bkc * S + run.stage_ids
        b_cols = res_col_arr[bkc]
        b_stash_act = b_active & stash_of_arr[bkc]

        with named_region("smp/pipeline/tick_bwd_input"):
            if capture_at_f:
                # Residuals were captured at F: no backward-time forward.
                # stash_all plans are never partial (only auto degrades
                # chunks, and auto targets stash_weight on this
                # schedule), so every chunk's residuals are in the ring.
                assert all_stash
                res_b = _chunk_ring_get(wres, b_cols, bmc % Rres)
            else:
                _out_b, _aux_b, res_b = stage_vmap(
                    capture_fwd, S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0),
                )(ch_params_b, ch_xs_b, stash_in, b_sides, c_ids_b, bmc,
                  ch_act_b)
            d_x_rows, d_side_rows, cot_stack = stage_vmap(bwd_from_res, S)(
                res_b, cot_in
            )
        d_x_rows = pin_stage_axis(d_x_rows)
        # Stash for the deferred W pass (stashed chunks only).
        if not capture_at_f:
            wres = _chunk_ring_set(
                wres, b_cols, bmc % Rres, res_b, b_stash_act
            )
        wcot = _chunk_ring_set(
            wcot, b_cols, bmc % Rcot, cot_stack, b_stash_act
        )

        if hc is not None:
            brow_b, arow_b = health.stage_row_stats(d_x_rows, S)
            brow_b = jnp.where(b_active, brow_b, 0.0)
            arow_b = jnp.where(b_active, arow_b, 0.0)
            hmb_b = _chunk_scatter_stat(
                hmb_b, bkc, bmc.astype(jnp.float32),
                b_active & (brow_b > 0),
                lambda cur, mb: jnp.where(cur < 0, mb, cur),
            )
            hbad_b = _chunk_scatter_stat(
                hbad_b, bkc, brow_b, b_active, lambda cur, v: cur + v
            )
            habs_b = _chunk_scatter_stat(
                habs_b, bkc, arow_b, b_active, jnp.maximum
            )

        drep = jax.tree_util.tree_map(
            lambda a, g: a + jnp.where(is_lastk, g.astype(a.dtype), 0),
            drep, d_rep,
        )

        dembed = _chunk_scatter_add_mb(
            dembed, bmc[0],
            jax.tree_util.tree_map(lambda r: r[0], d_x_rows),
            b_active[0] & (bkc[0] == 0),
        )

        if sides is not None and dsides is not None:
            # d_side_rows: per-stage accumulated inexact side-cotangent
            # leaves (already filtered to run.side_idx order).
            for s in range(S):
                dsides = [
                    _chunk_scatter_add_leaf(d, bmc[s], leaf[s], b_active[s])
                    for d, leaf in zip(dsides, d_side_rows)
                ]

        losses = losses.at[m_last].set(
            jnp.where(is_lastk, loss_m.astype(jnp.float32), losses[m_last])
        )
        outs = _chunk_scatter_set_mb(outs, m_last, user_out, is_lastk)
        xfer_b = d_x_rows
        return (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot,
                dlay, drep, dembed, dsides, losses, outs,
                (hstats_f, (hbad_b, habs_b, hmb_b)))

    def w_substep(carry, t):
        (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot, dlay,
         drep, dembed, dsides, losses, outs, hstats) = carry

        wk = wgt_k_sched[t]
        wm = wgt_m_sched[t]
        w_active = wm >= 0
        wkc = jnp.clip(wk, 0, V - 1)
        wmc = jnp.maximum(wm, 0)
        w_cols = res_col_arr[wkc]
        w_stash = stash_of_arr[wkc]
        ch_act_w = _select_chunk(run.active_rows, wkc)

        with named_region("smp/pipeline/tick_bwd_weight"):
            res_w = _chunk_ring_get(wres, w_cols, wmc % Rres)
            cot_w = _chunk_ring_get(wcot, w_cols, wmc % Rcot)
            d_lp_rows = stage_vmap(wgt_from_res, S)(res_w, cot_w)
            if not all_stash:
                # Degraded chunks keep the recompute path: vjp w.r.t. the
                # chunk params re-running the forward from the input
                # stash and the retained chunk-output cotangent.
                w_slots = wmc % R1
                w_sides = _gather_sides_rows(sides, wmc)
                stash_w = _chunk_ring_get(stash, wkc, w_slots)
                cotc_w = _chunk_ring_get(cotbuf, wkc, w_slots)
                ch_params_w = _select_chunk(run.staged_params, wkc)
                ch_xs_w = _select_chunk(run.staged_xs, wkc)
                c_ids_w = wkc * S + run.stage_ids

                d_lp_rec = stage_vmap(
                    functools.partial(_chunk_bwd_weight, run), S,
                    in_axes=(0, 0, 0, 0 if sides is not None else None,
                             0, 0, 0, 0),
                )(ch_params_w, ch_xs_w, stash_w, w_sides, cotc_w,
                  c_ids_w, wmc, ch_act_w)
                d_lp_rows = jax.tree_util.tree_map(
                    lambda a, b: jnp.where(
                        w_stash.reshape((S,) + (1,) * (a.ndim - 1)), a, b
                    ),
                    d_lp_rows, d_lp_rec,
                )
        d_lp_rows = pin_stage_axis(d_lp_rows)
        dlay = _chunk_acc_rows(dlay, d_lp_rows, wkc, w_active)
        return (inbuf, stash, cotbuf, outbuf, xfer_f, xfer_b, wres, wcot,
                dlay, drep, dembed, dsides, losses, outs, hstats)

    def tick(carry, t):
        carry = jax.lax.cond(
            f_run_sched[t], lambda c: f_substep(c, t), lambda c: c, carry
        )
        carry = jax.lax.cond(
            b_run_sched[t], lambda c: b_substep(c, t), lambda c: c, carry
        )
        carry = jax.lax.cond(
            w_run_sched[t], lambda c: w_substep(c, t), lambda c: c, carry
        )
        return carry, None

    carry0 = (
        pin_stage_axis(inbuf0), pin_stage_axis(stash0),
        pin_stage_axis(cotbuf0), pin_stage_axis(outbuf0),
        pin_stage_axis(xfer_f0), pin_stage_axis(xfer_b0),
        pin_stage_axis(wres0), pin_stage_axis(wcot0),
        pin_stage_axis(dlay0), drep0, dembed0, dsides0, losses0, outs0,
        (_zeros_health_grids(S, V), _zeros_health_grids(S, V)),
    )
    with named_region("smp/pipeline/steady"):
        carry_end, _ = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
    (_, _, _, _, _, _, _, _, dlay, drep, dembed, dsides, losses, outs,
     hstats) = carry_end
    if hc is not None:
        _add_zero_bubble_stage_stats(run, hstats)

    return _finish_run(
        run, _rows_to_layers(run.idx_np, run.active_np, run.L),
        dlay, drep, dembed, dsides, losses, outs,
    )


def _set_subtree(tree, path, sub):
    """Return a copy of `tree` with the node at '/'-path replaced by `sub`."""
    parts = [p for p in path.strip("/").split("/") if p]

    def rec(node, i):
        if i == len(parts):
            return sub
        out = dict(node)
        out[parts[i]] = rec(node[parts[i]], i + 1)
        return out

    return rec(tree, 0)
