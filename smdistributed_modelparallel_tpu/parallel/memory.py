"""Activation checkpointing and host offloading.

Parity target: reference ``torch/patches/checkpoint.py`` (``smp.checkpoint``
/ ``smp.checkpoint_sequential`` / ``set_activation_checkpointing``) and
``torch/offload.py`` (``TensorOffloader``: pinned-CPU buffers, d2h/h2d
streams, ``activation_loading_horizon``).

TPU-native re-design: checkpointing is ``jax.checkpoint`` (remat) around
layer applications — the reference's enable_grad re-forward becomes XLA
rematerialization inside the backward. Offloading is a remat *policy*:
layer-boundary activations tagged ``checkpoint_name`` are offloaded to
``pinned_host`` memory by XLA, which also schedules the d2h/h2d copies to
overlap compute — subsuming the reference's hand-rolled stream pipeline and
its ``activation_loading_horizon`` knob.
"""

import jax
from jax.ad_checkpoint import checkpoint_name

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils.logger import get_logger

logger = get_logger()

LAYER_ACT_NAME = "smp_layer_act"
# The flash forward kernel's two outputs, named by ``ops/pallas_attention.
# _fa_fwd``: every policy below keeps them.
FLASH_OUT_NAME = "smp_flash_out"
FLASH_LSE_NAME = "smp_flash_lse"
_KEPT_NAMES = (FLASH_OUT_NAME, FLASH_LSE_NAME)
_warned_offload = False


def offload_supported():
    """Host offload needs a backend with pinned_host memory (TPU; recent
    CPU backends also support it)."""
    try:
        dev = jax.devices()[0]
        kinds = [m.kind for m in dev.addressable_memories()]
        return "pinned_host" in kinds
    except Exception:
        return False


def remat_policy():
    """Checkpoint policy for layer remat, honoring offload_activations
    and the ``recompute`` knob.

    Every mode keeps the flash forward kernel's output and logsumexp
    (``smp_flash_out``, ``smp_flash_lse``): they are the residuals of
    ``flash_attention``'s backward, and recomputing them is a whole kernel
    (no other value of a layer costs as much time a byte kept) where q, k
    and v are three cheap projections. The price is memory that grows with
    the stack: ``B*T*H*hd*2 + B*H*T*4`` bytes a layer a microbatch in flight,
    ``H*hd / d_model`` of the layer boundary that is kept anyway (all of
    it again where one chip holds every head). A function with no flash
    call carries neither name and is rematerialized as before.

    ``recompute: "full"`` (the default) keeps nothing else, and under
    ``offload_activations`` the layer boundary goes to the host besides.
    The stash modes map onto the ``dots_with_no_batch_dims_saveable``
    policy family: non-pipeline runs (pp=1 microbatch scan, fill-drain)
    have no schedule for the recompute planner to stash against, so the
    same memory-for-FLOPs trade is taken one level down, inside
    ``jax.checkpoint``: ``stash_weight``/``auto`` save the weight-matmul
    outputs (the dominant recompute) with the two names, ``stash_all``
    saves everything (checkpoint becomes a no-op boundary). Offloading
    takes precedence — an offload policy already saves the layer boundary
    to host, and combining the two would double-store.
    """
    global _warned_offload
    policies = jax.checkpoint_policies
    keep_flash = policies.save_only_these_names(*_KEPT_NAMES)
    cfg = state.cfg
    if cfg is None:
        return keep_flash
    if not cfg.offload_activations:
        from smdistributed_modelparallel_tpu.parallel import remat_plan

        mode = remat_plan.resolve(cfg)
        if mode in ("stash_weight", "auto"):
            return policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable, keep_flash
            )
        if mode == "stash_all":
            return policies.everything_saveable
        return keep_flash
    if not offload_supported():
        if not _warned_offload:
            logger.warning(
                "offload_activations requested but the backend exposes no "
                "pinned_host memory; falling back to plain rematerialization."
            )
            _warned_offload = True
        return keep_flash
    return policies.save_and_offload_only_these_names(
        names_which_can_be_saved=list(_KEPT_NAMES),
        names_which_can_be_offloaded=[LAYER_ACT_NAME],
        offload_src="device",
        offload_dst="pinned_host",
    )


def name_layer_activation(x):
    """Tag a layer-boundary activation for the offload policy."""
    cfg = state.cfg
    if cfg is not None and cfg.offload_activations and offload_supported():
        return checkpoint_name(x, LAYER_ACT_NAME)
    return x


def checkpoint(fn, *args, **kwargs):
    """``smp.checkpoint``: run `fn` under rematerialization.

    Parity: reference ``smp.checkpoint(module, *args)``
    (``torch/patches/checkpoint.py:248-300``). Two call forms:
    ``smp.checkpoint(fn)(args...)`` (decorator) or
    ``smp.checkpoint(fn, args...)`` (immediate, reference-style).
    """
    wrapped = jax.checkpoint(fn, policy=remat_policy())
    if args or kwargs:
        return wrapped(*args, **kwargs)
    return wrapped


def checkpoint_sequential(fns, input, strategy="each"):
    """``smp.checkpoint_sequential``: remat a chain of callables.

    Parity: reference ``torch/patches/checkpoint.py:302-359`` (nn.Sequential
    with per-module or grouped strategies: "each" | "group_N").
    """
    if strategy == "each":
        group = 1
    elif strategy.startswith("group_"):
        group = int(strategy.split("_", 1)[1])
    else:
        raise ValueError(f"Unknown checkpoint_sequential strategy {strategy!r}")
    policy = remat_policy()
    x = input
    i = 0
    fns = list(fns)
    while i < len(fns):
        chunk = fns[i:i + group]

        def run_chunk(x, chunk=chunk):
            for f in chunk:
                x = f(x)
            return x

        x = jax.checkpoint(run_chunk, policy=policy)(x)
        i += group
    return x


def zero_bubble_ring_plan(fwd_k, fwd_m, bwd_k, bwd_m, wgt_k, wgt_m,
                          num_stages, virtual, window):
    """Ring-buffer budget of the zero-bubble (ZB-H1) executor.

    The split backward extends ring-entry lifetimes: a stashed chunk
    input and its retained output cotangent stay live from the forward
    until the DEFERRED weight-grad pass consumes them (the fused
    executors free them at the monolithic backward). This walks the
    static schedule and returns the exact peak:

    - ``stash_alive_peak``: max per-(stage, chunk) count of microbatches
      forwarded but not yet weight-graded at any tick (counting a
      same-tick F-write/W-read as overlapping — the executor's sub-step
      order writes the forward stash before the W pass reads);
    - ``w_queue_peak``: max per-(stage, chunk) count of deferred
      weight-grad units (input-graded, not yet weight-graded) — the
      "W-queue" depth the cooldown packing costs;
    - ``ring_slots``: slots the executor allocates per (stage, chunk)
      ring — ``max(stash_alive_peak, window + 1)``. The ``window + 1``
      floor is the fused executors' ring size (the in-flight input
      buffer needs it regardless of W deferral), so
      ``extra_ring_slots == 0`` means ZB's same-activation-memory claim
      holds exactly: the deferral fits in slack the in-flight cap
      already paid for. At the default window it always does; tighter
      windows may grow the ring and the executor's
      ``smp_pipeline_ring_slots`` gauge reports what was allocated.
    """
    S, V = int(num_stages), int(virtual)
    n_ticks = int(fwd_m.shape[0])
    C = S * V
    f_ticks = [[] for _ in range(C)]   # per global chunk, m-ordered
    b_ticks = [[] for _ in range(C)]
    w_ticks = [[] for _ in range(C)]
    for t in range(n_ticks):
        for s in range(S):
            if fwd_m[t, s] >= 0:
                f_ticks[int(fwd_k[t, s]) * S + s].append(t)
            if bwd_m[t, s] >= 0:
                b_ticks[int(bwd_k[t, s]) * S + s].append(t)
            if wgt_m[t, s] >= 0:
                w_ticks[int(wgt_k[t, s]) * S + s].append(t)
    import bisect

    stash_alive_peak = 0
    w_queue_peak = 0
    for c in range(C):
        fts, bts, wts = f_ticks[c], b_ticks[c], w_ticks[c]
        for m, ft in enumerate(fts):
            # Alive at F(c, m)'s tick: m+1 forwarded minus Ws strictly
            # before it (a same-tick W runs after the F write).
            freed = bisect.bisect_left(wts, ft)
            stash_alive_peak = max(stash_alive_peak, m + 1 - freed)
        for m, bt in enumerate(bts):
            # Deferred at B(c, m)'s tick: m+1 input-graded minus Ws
            # strictly before it (a same-tick W drains after B).
            drained = bisect.bisect_left(wts, bt)
            w_queue_peak = max(w_queue_peak, m + 1 - drained)
    ring_slots = max(stash_alive_peak, int(window) + 1, 2)
    return {
        "ring_slots": ring_slots,
        "stash_alive_peak": stash_alive_peak,
        "w_queue_peak": w_queue_peak,
        "extra_ring_slots": ring_slots - (int(window) + 1),
    }


def _ring_slots_for(write_ticks, read_ticks):
    """Minimum ``m % R`` ring size for one chunk's stash entries: entry m
    is written at ``write_ticks[m]`` and last read at ``read_ticks[m]``
    (both m-ordered — the schedules are FIFO per (stage, chunk)). The
    executors order sub-steps F -> B -> W within a tick and every stash
    write-pass precedes its read-pass, so a same-tick write of entry
    ``m + R`` lands BEFORE the read of entry ``m`` — strict inequality is
    required, i.e. entry ``m`` counts as alive through its read tick."""
    import bisect

    peak = 0
    for m, wt in enumerate(write_ticks):
        # Entries m' < m still alive at this write: read tick >= wt.
        first_alive = bisect.bisect_left(read_ticks, wt)
        peak = max(peak, m - first_alive + 1)
    return max(peak, 1)


def recompute_ring_plan(fwd_k, fwd_m, bwd_k, bwd_m, wgt_k=None, wgt_m=None,
                        num_stages=1, virtual=1):
    """Stash-ring budget of the recompute planner (``parallel/
    remat_plan.py``): exact per-(stage, chunk) ring sizes for the three
    residual-stash lifetimes the stash executors use, walked from the
    static schedule like ``zero_bubble_ring_plan``:

    - ``b_to_w``: entries written by the B pass, consumed by the W pass —
      the ``stash_weight`` residual + cotangent rings (== the W-queue
      depth under the strict write-before-read slot convention);
    - ``f_to_w``: written at F, consumed at W — the ``stash_all``
      residual ring on the zero-bubble schedule;
    - ``f_to_b``: written at F, consumed at B — the ``stash_all``
      residual ring on the interleaved/1F1B schedules (pass ``wgt_*`` as
      None for those).

    Returns ``{"b_to_w", "f_to_w", "f_to_b", "per_chunk": {name: [C]}}``
    (global-chunk-indexed per-chunk peaks; the scalar is their max).
    """
    import numpy as np

    S, V = int(num_stages), int(virtual)
    C = S * V
    n_ticks = int(np.asarray(fwd_m).shape[0])

    def ticks_of(k_arr, m_arr):
        out = [[] for _ in range(C)]
        if k_arr is None or m_arr is None:
            return None
        k_arr = np.asarray(k_arr)
        m_arr = np.asarray(m_arr)
        for t in range(n_ticks):
            for s in range(S):
                if m_arr[t, s] >= 0:
                    out[int(k_arr[t, s]) * S + s].append(t)
        return out

    f_ticks = ticks_of(fwd_k, fwd_m)
    b_ticks = ticks_of(bwd_k, bwd_m)
    w_ticks = ticks_of(wgt_k, wgt_m)

    per_chunk = {"b_to_w": [], "f_to_w": [], "f_to_b": []}
    for c in range(C):
        if w_ticks is not None:
            per_chunk["b_to_w"].append(
                _ring_slots_for(b_ticks[c], w_ticks[c])
            )
            per_chunk["f_to_w"].append(
                _ring_slots_for(f_ticks[c], w_ticks[c])
            )
        per_chunk["f_to_b"].append(_ring_slots_for(f_ticks[c], b_ticks[c]))
    return {
        "b_to_w": max(per_chunk["b_to_w"], default=0),
        "f_to_w": max(per_chunk["f_to_w"], default=0),
        "f_to_b": max(per_chunk["f_to_b"], default=0),
        "per_chunk": per_chunk,
    }


def module_checkpoint_enabled(mm, *paths):
    """Whether any of the given module paths has an activation-checkpoint
    config registered (smp.set_activation_checkpointing)."""
    if mm is None:
        return False
    for p in paths:
        if mm.checkpoint_config(p) is not None:
            return True
    return False
