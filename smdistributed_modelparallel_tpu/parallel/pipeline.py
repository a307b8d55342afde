"""Pipeline parallelism: compiled SPMD microbatch pipelining.

Parity target: reference pipeline subsystem — ``torch/pipeline.py:24-145``
(microbatch state machine), ``torch/server.py`` (the MPMD event loop that
*creates* pipelining by task ordering), ``active_microbatches`` windowing.

TPU-native re-design (SURVEY §7-M2): the pipeline is not a server loop but a
``lax.scan`` over ticks inside the one compiled step:

- layer parameters live stacked with a leading ``[num_layers]`` axis (the
  model builds them with ``flax.linen.scan``), resharded per-stage as
  ``[S, layers_per_stage, ...]`` with the stage axis on the mesh's ``pp``
  axis;
- each tick ``vmap``s the stage body over the stage axis — GSPMD partitions
  the vmapped computation so each device executes only its own stage — and
  shifts the carry buffer one stage forward with ``jnp.roll`` on the
  pp-sharded axis, which XLA lowers to a collective-permute over ICI (the
  reference's NCCL P2P "links", SURVEY §2.1 N3);
- stage 0 consumes microbatch ``t`` at tick ``t``; the last stage emits
  microbatch ``t - (S-1)``; total ticks = num_microbatches + S - 1;
- backward is JAX AD through the tick scan (reverse-time pipeline). Both
  ``pipeline: simple`` and ``interleaved`` lower to this schedule; the
  interleaved memory advantage is recovered with per-layer rematerialization
  (``jax.checkpoint``) rather than schedule reordering.

Models opt in by exposing ``pipeline_spec()`` (see ``PipelineSpec``); the
``smp.nn`` transformer family and the model zoo implement it. Non-layered
modules cannot be pipelined under SPMD and raise a clear error.
"""

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.backend.topology import PP_AXIS
from smdistributed_modelparallel_tpu.utils.exceptions import PartitionError
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.profiling import named_region

logger = get_logger()


@dataclass
class PipelineSpec:
    """How a module decomposes into embed -> repeated layer -> head.

    Attributes:
      layer_path: '/'-joined path of the parameter subtree whose leaves carry
        a leading [num_layers] axis (built with ``flax.linen.scan``).
      num_layers: total layer count L (must be divisible by pp_degree).
      layer_module: unbound flax module for ONE layer; applied per-slice
        during pipelining.
      embed_method / head_method: method names on the root module computing
        the pre-layer carry and the post-layer output. Both may use any
        non-layer parameters (they run replicated across stages; their
        parameters stay replicated on the pp axis). ``None`` = identity
        (the module IS the layer stack, e.g. DistributedTransformer).
      carry_remat: rematerialize each layer application (activation
        checkpointing inside the pipeline).
      layer_xs: optional pytree of stacked [num_layers, ...] per-layer
        inputs threaded into each layer application (e.g. layer_idx,
        is_local for GPT-Neo alternating attention).
      carry_is_tuple: carry is (hidden, cross_states, attention_mask) and
        the layer takes them as separate arguments (the smp.nn transformer
        family's calling convention).
    """

    layer_path: str
    num_layers: int
    layer_module: Any
    embed_method: Optional[str] = "embed"
    head_method: Optional[str] = "head"
    carry_remat: bool = False
    layer_xs: Any = None
    carry_is_tuple: bool = False
    layer_costs: Optional[list] = None   # per-layer relative time costs
    boundaries: Optional[list] = None    # [(start, end)] per chunk (filled
                                         # by partition_for_pipeline; one
                                         # entry per stage at v=1, pp*v
                                         # entries under virtual stages)
    virtual_degree: int = 1              # chunks per stage (Megatron-style
                                         # interleaved virtual pipeline)


def get_pipeline_spec(module):
    fn = getattr(module, "pipeline_spec", None)
    if fn is None:
        return None
    return fn() if callable(fn) else fn


def partition_for_pipeline(model):
    """Produce the stage assignment for a pipelineable model.

    Stage boundaries come from the cost-model partitioner
    (``parallel/module_partition.py`` — the reference's d'Hondt/min-max
    engine, ``torch/module_partition.py:182-905``) over per-layer costs
    (parameter bytes blended with time costs by ``memory_weight``).
    Manual ``smp.set_partition("<layer_path>#<i>", stage)`` pins constrain
    the boundaries. Non-uniform per-stage layer counts are supported — the
    executors pad stages to the max count with masked slots.
    """
    cfg = state.cfg
    pp = cfg.pipeline_parallel_degree
    virtual = int(getattr(cfg, "virtual_pipeline_degree", 1) or 1)
    from smdistributed_modelparallel_tpu.nn.auto_distribute import unwrap_hooks

    root = unwrap_hooks(model.module)
    spec = get_pipeline_spec(root)
    if spec is None:
        raise PartitionError(
            "pipeline_parallel_degree > 1 requires a pipelineable model: one "
            "exposing pipeline_spec() (smp.nn.DistributedTransformer* and the "
            "smp model zoo do). Arbitrary module graphs cannot be pipelined "
            "under SPMD."
        )
    L = spec.num_layers
    nchunks = pp * virtual
    if L < nchunks:
        raise PartitionError(
            f"num_layers={L} < pipeline_parallel_degree * "
            f"virtual_pipeline_degree = {pp} * {virtual} = {nchunks}: at "
            "least one layer per chunk is required."
        )
    if virtual > 1:
        # Chunked stage assignments are non-contiguous along the layer
        # sequence (chunk c -> stage c % pp), which the manual-partition
        # surfaces cannot express: each would silently produce a layout
        # the executor rejects, so fail with intent up front.
        mm = model.module_manager
        pinned = [
            p for p in mm.get_manual_partitions()
            if p.startswith(spec.layer_path + "#")
        ]
        if pinned or not cfg.auto_partition or cfg.load_partition:
            raise PartitionError(
                "virtual_pipeline_degree > 1 is incompatible with manual "
                "layer pins, auto_partition: False, and load_partition: the "
                "interleaved chunk placement (chunk c on stage c % pp) is "
                "not a contiguous stage assignment."
            )
    # Honor activation-checkpoint configs inside the pipeline: the stacked
    # executor applies layers directly (not via the module's own scan), so
    # the remat lives on the executor's layer application.
    if not spec.carry_remat:
        mm = model.module_manager
        if getattr(root, "activation_checkpointing", False):
            spec.carry_remat = True
        else:
            for prefix in mm.checkpoint_configs:
                if prefix == "" or spec.layer_path.startswith(prefix):
                    spec.carry_remat = True
                    break

    # One contiguous cost-balanced range per CHUNK; chunk c executes on
    # stage c % pp (at v=1 a chunk IS a stage, so this is the old layout).
    spec.virtual_degree = virtual
    spec.boundaries = _choose_boundaries(model, spec, nchunks)
    assignment = {}
    for c, (a, b) in enumerate(spec.boundaries):
        for layer in range(a, b):
            assignment[f"{spec.layer_path}#{layer}"] = c % pp
    model._pipeline_spec = spec
    model.module_manager.register_spec_provider(
        layer_param_sharding_provider(spec), name="pipeline_layers"
    )
    if virtual > 1:
        logger.info(
            "Pipeline partition: %d layers -> %d stages x %d virtual "
            "chunks %s.",
            L, pp, virtual, [b - a for a, b in spec.boundaries],
        )
    else:
        logger.info(
            "Pipeline partition: %d layers -> %d stages %s.",
            L, pp, [b - a for a, b in spec.boundaries],
        )
    return assignment


def _layer_cost_inputs(model, spec):
    """(param_bytes_per_layer, time_cost_per_layer) for the cost model.

    Parameter bytes come from the materialized stacked layer subtree
    (shapes are concrete by partition time). Time costs: declared
    ``spec.layer_costs`` first; otherwise, for heterogeneous stacks
    (distinct per-layer xs, e.g. GPT-Neo local/global alternation), each
    distinct layer variant is MEASURED with a one-time timed run on the
    current device — the reference's 5-trial timed trace
    (``torch/patches/tracing.py:41-86``, ``torch/module_manager.py:
    435-499``); homogeneous stacks stay uniform. ``skip_tracing`` disables
    the measurement.
    """
    L = spec.num_layers
    params = model._params
    pbytes = 0.0
    if params is not None:
        try:
            sub = _get_subtree(params, spec.layer_path)
            pbytes = sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(sub)
            ) / max(L, 1)
        except (KeyError, TypeError):
            pbytes = 0.0
    times = list(spec.layer_costs) if spec.layer_costs else None
    if times is None:
        times = _measured_layer_times(model, spec)
    if times is None:
        times = [1.0] * L
    if len(times) != L:
        raise PartitionError(
            f"pipeline_spec.layer_costs has {len(times)} entries for "
            f"{L} layers."
        )
    return [pbytes] * L, times


# Test hook: callable(sig, fn, args) -> seconds, replacing the wall-clock
# timer (CPU test tiers can't observe kernel-level cost differences).
_LAYER_TIMER = None


def _time_call(sig, fn, *args):
    import time

    if _LAYER_TIMER is not None:
        return _LAYER_TIMER(sig, fn, args)

    def run():
        jax.block_until_ready(fn(*args))

    run()  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)
    return best


def _measured_layer_times(model, spec):
    """Per-layer time costs measured per distinct xs variant, or None when
    measurement is off / impossible / pointless (homogeneous stack)."""
    import numpy as np

    cfg = state.cfg
    if cfg is None or cfg.skip_tracing or spec.layer_xs is None:
        return None
    if model._params is None:
        return None
    xs_np = {k: np.asarray(v) for k, v in spec.layer_xs.items()}
    keys = sorted(k for k in xs_np if k != "layer_idx")
    if not keys:
        return None
    L = spec.num_layers
    sigs = [tuple(xs_np[k][i].item() for k in keys) for i in range(L)]
    if len(set(sigs)) < 2:
        return None
    D = getattr(spec.layer_module, "hidden_size", None) or getattr(
        spec.layer_module, "d_model", None
    )
    if not D:
        return None
    try:
        sub = _get_subtree(model._params, spec.layer_path)
    except (KeyError, TypeError):
        return None
    lp = jax.tree_util.tree_map(lambda a: a[0], sub)
    T = int(getattr(spec.layer_module, "causal_mask_size", None) or 128)
    T = max(8, min(T, 512))
    x = jnp.zeros((2, T, D), jnp.float32)
    rngs = {"dropout": jax.random.key(0)}

    times_by_sig = {}
    # Only process 0 measures: its timings win the broadcast below anyway,
    # so peer processes skip the per-variant compiles + timed device runs
    # (at pod scale that is real init-critical-path work thrown away).
    if jax.process_index() == 0:
        for sig in sorted(set(sigs)):
            xs_one = {k: jnp.asarray(v) for k, v in zip(keys, sig)}
            if "layer_idx" in xs_np:
                xs_one["layer_idx"] = jnp.asarray(0, jnp.int32)

            def fn(lp, x, _xs=xs_one):
                if spec.carry_is_tuple:
                    return spec.layer_module.apply(
                        {"params": lp}, x, cross_states=None,
                        attention_mask=None, xs=_xs, rngs=rngs,
                    )
                return spec.layer_module.apply(
                    {"params": lp}, x, xs=_xs, rngs=rngs
                )

            times_by_sig[sig] = _time_call(sig, jax.jit(fn), lp, x)
    else:
        times_by_sig = {sig: 0.0 for sig in set(sigs)}
    if jax.process_count() > 1:
        # Multi-controller agreement: every process must derive the SAME
        # boundaries (different stage splits would compile divergent SPMD
        # programs and hang the first collective). Process 0's timings win
        # — the reference broadcasts its trace results the same way
        # (torch/server.py:264).
        from jax.experimental import multihost_utils

        vals = np.asarray([times_by_sig[s] for s in sorted(times_by_sig)])
        vals = multihost_utils.broadcast_one_to_all(vals)
        times_by_sig = dict(zip(sorted(times_by_sig), vals.tolist()))
    logger.info(
        "Measured layer-variant costs: %s",
        {str(k): round(v, 6) for k, v in times_by_sig.items()},
    )
    return [times_by_sig[s] for s in sigs]


def _choose_boundaries(model, spec, pp):
    """Contiguous per-stage layer ranges from costs + manual pins."""
    from smdistributed_modelparallel_tpu.parallel.module_partition import (
        ModuleNode,
        ModulePartitioner,
        min_max_segments_pinned,
    )

    cfg = state.cfg
    L = spec.num_layers
    pbytes, times = _layer_cost_inputs(model, spec)

    pins = {}
    for prefix, stage in model.module_manager.get_manual_partitions().items():
        if prefix.startswith(spec.layer_path + "#"):
            try:
                pins[int(prefix.rsplit("#", 1)[1])] = stage
            except ValueError:
                raise PartitionError(
                    f"Malformed layer pin '{prefix}': expected "
                    f"'{spec.layer_path}#<layer_index>'."
                )
    for idx, stage in pins.items():
        if not (0 <= idx < L):
            raise PartitionError(f"Pinned layer {idx} out of range [0, {L}).")

    if not cfg.auto_partition:
        # Manual partitioning (reference ``auto_partition: False`` +
        # ``default_partition`` semantics, ``backend/config.yaml:150-170``,
        # ``torch/module_manager.py:1061``): every layer goes to
        # ``default_partition`` unless explicitly pinned with
        # smp.set_partition.
        default = cfg.default_partition
        if default is None or not (0 <= default < pp):
            raise PartitionError(
                f"auto_partition: False requires default_partition in "
                f"[0, {pp}) (got {default})."
            )
        stages = [pins.get(i, default) for i in range(L)]
        if any(b < a for a, b in zip(stages, stages[1:])):
            raise PartitionError(
                f"Manual partition produced a non-contiguous stage order "
                f"{stages}; the SPMD executor requires non-decreasing "
                "stage assignments along the layer sequence."
            )
        bounds = []
        start = 0
        for s in range(pp):
            end = start
            while end < L and stages[end] == s:
                end += 1
            if end == start:
                raise PartitionError(
                    f"Manual partition leaves stage {s} empty "
                    f"(stages={stages}); every pipeline stage needs at "
                    "least one layer."
                )
            bounds.append((start, end))
            start = end
        return bounds

    mw = cfg.memory_weight
    total_m = sum(pbytes) or 1.0
    total_t = sum(times) or 1.0
    blended = [
        mw * (m / total_m) + (1.0 - mw) * (t / total_t)
        for m, t in zip(pbytes, times)
    ]
    if pins:
        return min_max_segments_pinned(blended, pp, pins)
    # No pins: run the reference-parity tree partitioner (min-max DP
    # segmentation + d'Hondt stage allocation) over the layer sequence.
    root = ModuleNode(name=spec.layer_path)
    root.children = [
        ModuleNode(name=f"{spec.layer_path}#{i}", param_bytes=pbytes[i],
                   time=times[i])
        for i in range(L)
    ]
    assignment = ModulePartitioner(
        root, pp, memory_weight=mw
    ).partition()
    stages = [assignment[f"{spec.layer_path}#{i}"] for i in range(L)]
    if any(b < a for a, b in zip(stages, stages[1:])):
        raise PartitionError(
            f"Partitioner produced a non-contiguous stage order {stages}; "
            "the SPMD executor requires contiguous stages."
        )
    bounds = []
    start = 0
    for s in range(pp):
        end = start
        while end < L and stages[end] == s:
            end += 1
        bounds.append((start, end))
        start = end
    if start != L:
        raise PartitionError(
            f"Partitioner left layers unassigned (stages={stages})."
        )
    return bounds


def stage_layout(spec, num_stages):
    """(layer_index_grid [S, maxp], active_mask [S, maxp], maxp) for the
    executors. Uniform boundaries collapse to the dense reshape layout."""
    import numpy as np

    bounds = spec.boundaries
    L = spec.num_layers
    if bounds is None:
        per = L // num_stages
        bounds = [(s * per, (s + 1) * per) for s in range(num_stages)]
    maxp = max(b - a for a, b in bounds)
    idx = np.zeros((num_stages, maxp), np.int32)
    active = np.zeros((num_stages, maxp), bool)
    for s, (a, b) in enumerate(bounds):
        n = b - a
        idx[s, :n] = np.arange(a, b)
        active[s, :n] = True
    return idx, active, maxp


def chunk_layout(spec, num_stages, virtual):
    """(layer_index_grid [S, V, maxp], active_mask [S, V, maxp], maxp) for
    the interleaved 1F1B executor: chunk ``c`` of ``spec.boundaries`` sits
    at ``[c % S, c // S]`` (stage, local chunk). The per-chunk grids come
    from ``stage_layout`` over the C = S*V chunk boundaries (one source of
    truth for bounds defaults and padding), re-laid to the interleaved
    placement."""
    C = num_stages * virtual
    if spec.boundaries is not None and len(spec.boundaries) != C:
        raise PartitionError(
            f"pipeline spec has {len(spec.boundaries)} chunk boundaries "
            f"for {num_stages} stages x {virtual} virtual chunks."
        )
    idx, active, maxp = stage_layout(spec, C)   # [C, maxp], chunk order
    shape = (virtual, num_stages, maxp)
    # Row c -> grid[c % S, c // S]: reshape to [V, S, .] and swap.
    return (idx.reshape(shape).transpose(1, 0, 2),
            active.reshape(shape).transpose(1, 0, 2), maxp)


# Stage views are index-gathers over the stacked layer axis only — inner
# dims (tp axes, zero3's rdp shards) ride along with their shardings
# unconstrained, so under ``sharded_params: zero3`` the per-layer rdp
# all-gather stays at each stage's point of use inside the schedule loop
# instead of being hoisted into an upfront whole-model gather. The 1F1B
# executors additionally pin the staged axis (``pin_stage_axis``) with
# UNCONSTRAINED inner dims for the same reason.
def staged_chunk_views(spec, layer_params, num_stages, virtual):
    """Stage the [L, ...] layer stack as ([S, V, maxp, ...] params,
    [S, V, maxp, ...] xs, [S, V, maxp] active mask) for the interleaved
    executor.

    The chunked placement (chunk c -> stage c % S) interleaves the layer
    axis across stages, so unlike the v=1 reshape this is always a gather
    across the even [L] storage sharding — one layer-param reshard per
    step, amortized over all V chunks' compute.
    """
    idx, active, maxp = chunk_layout(spec, num_stages, virtual)
    gidx = jnp.asarray(idx)
    staged_params = jax.tree_util.tree_map(lambda x: x[gidx], layer_params)
    staged_xs = jax.tree_util.tree_map(
        lambda x: jnp.asarray(x)[gidx], spec.layer_xs
    )
    return staged_params, staged_xs, jnp.asarray(active)


def layer_param_sharding_provider(spec):
    """Spec provider: stacked layer params get their leading (layer) axis
    sharded over pp; everything else replicated across pp. When the layer
    count does not divide pp (uneven/padded boundaries) the stack stays
    replicated — the executor's per-stage gather distributes the compute."""
    from jax.sharding import PartitionSpec as P

    prefix = spec.layer_path.strip("/")
    pp = state.cfg.pipeline_parallel_degree if state.cfg else 1

    def provider(path, leaf):
        if path == prefix or path.startswith(prefix + "/"):
            ndim = getattr(leaf, "ndim", 0)
            if ndim >= 1 and leaf.shape[0] % pp == 0:
                return P(PP_AXIS, *([None] * (ndim - 1)))
        return None

    return provider


# ----------------------------------------------------------------------
# Execution
# ----------------------------------------------------------------------


def pipeline_forward(model, params, stacked_inputs, rngs_key, mb_kwargs=None):
    """Run the full pipelined forward for all microbatches.

    Args:
      model: DistributedModel with ``_pipeline_spec`` installed.
      params: full parameter tree; layer subtree leaves have leading [L].
      stacked_inputs: pytree of arrays with leading [num_microbatches] —
        the captured inputs of the user's single ``model(...)`` call.
      rngs_key: PRNG key for dropout etc. (folded per microbatch and layer).

    Returns:
      (stacked outputs with leading [num_microbatches], summed MoE aux loss
      over all microbatches and layers — a 0.0 scalar for MoE-free models).
    """
    spec = model._pipeline_spec
    cfg = state.cfg
    phys_stages = cfg.pipeline_parallel_degree
    virtual = int(getattr(spec, "virtual_degree", 1) or 1)
    # virtual_pipeline_degree > 1 cut the model into pp*v chunks; this
    # executor (forward-only path under the interleaved config) runs them
    # as pp*v sequential logical stages — same math, contiguous [C]
    # staging (chunk i on physical stage i // v). The interleaved chunk
    # placement lives in the 1F1B executor only; telemetry and health
    # below attribute back to PHYSICAL stage + chunk coordinates so
    # operators never see stages that don't exist.
    S = phys_stages * virtual
    num_mb = cfg.microbatches
    L = spec.num_layers
    from smdistributed_modelparallel_tpu.nn.auto_distribute import unwrap_hooks

    module = unwrap_hooks(model.module)
    layer_module = spec.layer_module

    layer_params = _get_subtree(params, spec.layer_path)

    # embed/head also run with aux collection so an MoE living outside the
    # layer stack keeps its balancing loss under pp (parity with pp=1,
    # where DistributedModel.__call__ collects from the whole module).
    def embed_mb(mb_input, key):
        args, kwargs = mb_input
        if spec.embed_method is None:
            # The module IS the layer stack; the model(...) input is the carry.
            return args[0], jnp.zeros((), jnp.float32)
        return apply_collecting_aux(
            module, {"params": params}, *args,
            rngs=_mk_rngs(model, key, "embed"),
            method=spec.embed_method, **kwargs,
        )

    def head_mb(carry, key):
        # `carry` here is the collected hidden only (side values never
        # leave the layer stack).
        if spec.head_method is None:
            return carry, jnp.zeros((), jnp.float32)
        return apply_collecting_aux(
            module, {"params": params}, carry,
            rngs=_mk_rngs(model, key, "head"),
            method=spec.head_method,
        )

    apply_one_layer = make_layer_apply(model, spec, layer_module)

    if spec.carry_remat:
        from smdistributed_modelparallel_tpu.parallel.memory import remat_policy

        apply_one_layer = jax.checkpoint(apply_one_layer, policy=remat_policy())

    def stage_body(stage_layer_params, stage_layer_xs, carry, key, active_row):
        """Apply this stage's layer slots sequentially (scan over the local
        layer axis); padded slots pass the carry through unchanged. Returns
        (carry, summed MoE aux loss of the active slots)."""

        def body(c, xs):
            lp, lxs, i, act = xs
            new_c, aux = apply_one_layer(lp, c, lxs, jax.random.fold_in(key, i))
            out_c = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act, n, o), new_c, c
            )
            return out_c, jnp.where(act, aux, 0.0)

        idx = jnp.arange(active_row.shape[0])
        out, auxs = jax.lax.scan(
            body, carry, (stage_layer_params, stage_layer_xs, idx, active_row)
        )
        return out, jnp.sum(auxs)

    mb_keys = jax.random.split(rngs_key, num_mb)

    # Embed all microbatches upfront (the pipeline's input queue).
    with named_region("smp/pipeline/embed"):
        embedded, embed_auxs = _scan_map(embed_mb, stacked_inputs, mb_keys)

    # [L, ...] -> [S, maxp, ...]; dim 0 stays sharded on pp. Uniform
    # boundaries collapse to a reshape; uneven ones gather padded slots.
    staged_params, staged_xs, active_rows = staged_layer_views(
        spec, layer_params, S
    )

    n_ticks = num_mb + S - 1
    # Schedule occupancy -> measured bubble fraction. Fill-drain busy slots
    # are exactly num_mb per stage over num_mb + S - 1 ticks, so the
    # measured fraction coincides with the theoretical (pp-1)/(mb+pp-1);
    # recording both keeps the report honest when the executor changes.
    from smdistributed_modelparallel_tpu.utils import health
    from smdistributed_modelparallel_tpu.utils.flight_recorder import (
        flight_recorder,
    )
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_pipeline_occupancy,
    )

    # Gauges carry the PHYSICAL stage count; under chunked specs the
    # measured fraction (C-1)/(mb+C-1) sitting above the interleaved
    # theoretical bound is the honest report — this executor runs the
    # chunks sequentially, it does not interleave them.
    record_pipeline_occupancy(
        "fill_drain", phys_stages, num_mb, busy_slots=num_mb * S,
        total_slots=n_ticks * S, virtual=virtual,
    )
    # The busy (tick, stage) -> microbatch assignments land in the flight
    # recorder once per trace: a stall dump can then say which schedule
    # slot each rank's program was built to be in, not just "in step N".
    # Chunked specs record (physical stage, chunk) coordinates.
    # Chunked specs record (physical stage, GLOBAL chunk) coordinates —
    # the logical stage IS the boundary/chunk index here, matching the
    # chunk ids the 1F1B executor records for the same layers.
    flight_recorder.record_schedule(
        "fill_drain",
        ((t, s, "fwd", t - s) if virtual == 1
         else (t, s // virtual, "fwd", t - s, s)
         for t in range(n_ticks) for s in range(S)
         if 0 <= t - s < num_mb),
    )
    # Only the hidden flows stage-to-stage over the pp permute; tuple-carry
    # side values (cross_states, attention_mask) are static per-microbatch
    # inputs, gathered per stage per tick instead of rolled through ICI.
    if spec.carry_is_tuple:
        rolled = embedded[0]
        sides = embedded[1:]
    else:
        rolled = embedded
        sides = None
    carry_shape = jax.tree_util.tree_map(lambda x: x[0], rolled)
    # Stage input buffer: [S, ...carry]; buf[s] is the input consumed by
    # stage s at the next tick.
    buf0 = jax.tree_util.tree_map(
        lambda x: jnp.zeros((S,) + x.shape, x.dtype), carry_shape
    )

    vmapped_stages = stage_vmap(stage_body, S)
    stage_keys = jax.random.split(rngs_key, S)
    stage_ids = jnp.arange(S)

    # Health sentinel (SMP_HEALTH_CHECK != off while this trace runs):
    # per-stage non-finite counts / finite abs-max of the stage-boundary
    # activations, plus the first bad microbatch per stage, accumulate in
    # the tick carry — one masked reduce per tick, no extra outputs until
    # the collector fuses them into the step's health word.
    hc = health.active()

    def tick(tick_carry, t):
        # Feed stage 0 with microbatch t (clamped; invalid ticks produce
        # garbage that is never collected — and whose aux loss is masked
        # out below).
        if hc is not None:
            buf, aux_acc, (hbad, habs, hmb) = tick_carry
        else:
            buf, aux_acc = tick_carry
        mb_idx = jnp.minimum(t, num_mb - 1)
        feed = jax.tree_util.tree_map(
            lambda e, b: b.at[0].set(
                jax.lax.dynamic_index_in_dim(e, mb_idx, 0, keepdims=False)
            ),
            rolled, buf,
        )
        if sides is not None:
            # Stage s processes microbatch t - s at tick t.
            stage_mbs = jnp.clip(t - stage_ids, 0, num_mb - 1)
            stage_sides = tuple(
                jax.tree_util.tree_map(
                    lambda a: jax.vmap(
                        lambda i: jax.lax.dynamic_index_in_dim(
                            a, i, 0, keepdims=False
                        )
                    )(stage_mbs),
                    side,
                )
                for side in sides
            )
            carry_in = (feed,) + stage_sides
        else:
            carry_in = feed
        # Distinct dropout keys per (stage, tick).
        tick_keys = jax.vmap(lambda k: jax.random.fold_in(k, t))(stage_keys)
        with named_region("smp/pipeline/tick_fwd"):
            outs, aux_row = vmapped_stages(
                staged_params, staged_xs, carry_in, tick_keys, active_rows
            )
        x_outs = outs[0] if sides is not None else outs
        # MoE aux: stage s holds microbatch t - s; fill/drain ticks where
        # that index is invalid computed on garbage/duplicate inputs and
        # must not contribute.
        valid = (t - stage_ids >= 0) & (t - stage_ids < num_mb)
        aux_acc = aux_acc + jnp.sum(jnp.where(valid, aux_row, 0.0))
        # Collect last stage's output (microbatch t - (S-1) when valid).
        tail = jax.tree_util.tree_map(lambda o: o[S - 1], x_outs)
        # Shift stage outputs forward one stage: collective-permute on pp.
        nxt = jax.tree_util.tree_map(
            lambda o: jnp.roll(o, shift=1, axis=0), x_outs
        )
        if hc is not None:
            brow, arow = health.stage_row_stats(x_outs, S)
            brow = jnp.where(valid, brow, 0.0)
            arow = jnp.where(valid, arow, 0.0)
            hmb_new = jnp.where(
                (hmb < 0) & (brow > 0),
                (t - stage_ids).astype(jnp.float32), hmb,
            )
            return (nxt, aux_acc,
                    (hbad + brow, jnp.maximum(habs, arow), hmb_new)), tail
        return (nxt, aux_acc), tail

    carry0 = (buf0, jnp.zeros((), jnp.float32))
    if hc is not None:
        carry0 = carry0 + ((
            jnp.zeros((S,), jnp.float32), jnp.zeros((S,), jnp.float32),
            jnp.full((S,), -1.0, jnp.float32),
        ),)
    with named_region("smp/pipeline/fill_drain"):
        carry_end, tails = jax.lax.scan(tick, carry0, jnp.arange(n_ticks))
    if hc is not None:
        (_, aux_total, (hbad, habs, hmb)) = carry_end
        if virtual > 1:
            # Sequential chunk layout: logical stage i is global chunk i,
            # running on physical stage i // v — reshape so sentinel trips
            # attribute to stages that exist on the machine, tagged with
            # the global chunk (boundary) index.
            import numpy as np

            hbad = hbad.reshape(phys_stages, virtual)
            habs = habs.reshape(phys_stages, virtual)
            hmb = hmb.reshape(phys_stages, virtual)
            chunk_ids = np.arange(S).reshape(phys_stages, virtual)
            hc.add_stage_stats(
                "fill_drain", hbad, habs, hmb, chunk_ids=chunk_ids
            )
        else:
            hc.add_stage_stats("fill_drain", hbad, habs, hmb)
    else:
        (_, aux_total) = carry_end
    # tails[t] is microbatch t-(S-1); keep the last num_mb ticks.
    collected = jax.tree_util.tree_map(lambda x: x[S - 1:], tails)

    with named_region("smp/pipeline/head"):
        outputs, head_auxs = _scan_map(head_mb, collected, mb_keys)
    return outputs, aux_total + jnp.sum(embed_auxs) + jnp.sum(head_auxs)


def apply_collecting_aux(module, variables, *args, **kwargs):
    """Flax apply with ``mutable=["intermediates"]``: returns (out, aux)
    where ``aux`` is the summed sown MoE load-balancing loss as an f32
    scalar (0.0 when nothing was sown). Running with the collection mutable
    is what lets ``sow`` escape the apply — the executors fold the summed
    aux into the differentiated loss (see ``step.py`` /
    ``pipeline_1f1b.py``)."""
    from smdistributed_modelparallel_tpu.nn.moe import collect_moe_aux

    out, mut = module.apply(
        variables, *args, mutable=["intermediates"], **kwargs
    )
    aux = collect_moe_aux(mut.get("intermediates"))
    aux = (
        jnp.zeros((), jnp.float32) if aux is None else aux.astype(jnp.float32)
    )
    return out, aux


def make_layer_apply(model, spec, layer_module, side_in_carry=True):
    """Single-layer application shared by both pipeline executors.

    Returns ``apply_one_layer(lp, carry, layer_xs, key, side=None) ->
    (new_carry, aux)`` with ``aux`` the layer's MoE aux loss (0.0 for dense
    layers). For tuple-carry specs the two executors thread the side values
    differently: the fill-drain executor keeps them inside the carry
    (``side_in_carry=True``: carry is (x, cross, amask) in and out), while
    1F1B rolls only the hidden and passes (cross, amask) via ``side``
    (``side_in_carry=False``)."""
    from smdistributed_modelparallel_tpu.parallel.memory import (
        name_layer_activation,
    )

    def apply_one_layer(lp, carry, layer_xs, key, side=None):
        rngs = _mk_rngs(model, key, "layer")
        if spec.carry_is_tuple:
            if side_in_carry:
                x, cross, amask = carry
            else:
                x = carry
                cross, amask = side
            out, aux = apply_collecting_aux(
                layer_module, {"params": lp}, x, cross_states=cross,
                attention_mask=amask, xs=layer_xs, rngs=rngs,
            )
            new_c = (
                (name_layer_activation(out), cross, amask)
                if side_in_carry else name_layer_activation(out)
            )
            return new_c, aux
        if spec.layer_xs is not None:
            out, aux = apply_collecting_aux(
                layer_module, {"params": lp}, carry, xs=layer_xs, rngs=rngs
            )
        else:
            out, aux = apply_collecting_aux(
                layer_module, {"params": lp}, carry, rngs=rngs
            )
        return name_layer_activation(out), aux

    return apply_one_layer


def _scan_map(fn, stacked, keys):
    """Map fn over the leading microbatch axis via lax.scan (sequential, so
    per-microbatch activations do not coexist)."""

    def body(_, xs):
        tree, key = xs
        return 0, fn(tree, key)

    _, out = jax.lax.scan(body, 0, (stacked, keys))
    return out


def _mk_rngs(model, key, tag):
    import zlib

    return {
        s: jax.random.fold_in(key, zlib.crc32(f"{tag}/{s}".encode()))
        for s in model.rng_streams
    }


def staged_layer_views(spec, layer_params, num_stages):
    """Stage the [L, ...] layer stack as ([S, maxp, ...] params,
    [S, maxp, ...] xs, [S, maxp] active mask).

    Uniform boundaries are a plain reshape (dim 0 stays pp-sharded, no data
    movement); uneven boundaries gather into padded slots — the gather
    crosses the even [L] storage sharding, so uneven splits trade one
    layer-param reshard per step for balanced stage compute.
    """
    L = spec.num_layers
    idx, active, maxp = stage_layout(spec, num_stages)
    uniform = active.all() and L == num_stages * maxp
    if uniform:
        staged_params = jax.tree_util.tree_map(
            lambda x: x.reshape((num_stages, maxp) + x.shape[1:]), layer_params
        )
        staged_xs = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x).reshape(
                (num_stages, maxp) + jnp.asarray(x).shape[1:]
            ),
            spec.layer_xs,
        )
    else:
        gidx = jnp.asarray(idx)
        staged_params = jax.tree_util.tree_map(
            lambda x: x[gidx], layer_params
        )
        staged_xs = jax.tree_util.tree_map(
            lambda x: jnp.asarray(x)[gidx], spec.layer_xs
        )
    return staged_params, staged_xs, jnp.asarray(active)


def _pp_size():
    mesh = state.mesh
    return dict(mesh.shape).get(PP_AXIS, 1) if mesh is not None else 1


def pin_stage_axis(tree, num_stages):
    """Pin ONLY the leading stage axis of every stage-parallel value
    (leading dim ``num_stages``) to the pp mesh axis and leave the rest
    unconstrained, so batch/tp shardings still propagate. The chunked
    gather ([L] -> [S, V, maxp]) breaks the sharding propagation that gives
    the plain v=1 executor its stage placement for free (a reshape keeps
    dim 0 on pp; a gather's output is unconstrained, and GSPMD then happily
    replicates the whole tick loop).

    UNCONSTRAINED (not None) on the non-stage dims is load-bearing for
    pp x zero3 composition: None would force the staged views replicated,
    upfront-gathering every rdp-sharded parameter before the tick loop.
    UNCONSTRAINED lets propagation keep the rdp dims sharded, so the
    all-gather lands INSIDE the loop at each stage's point of use
    (per-stage gather scoping — asserted by the zero3 composition gate's
    loop_gather_ops census)."""
    if _pp_size() <= 1:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec as P

    def pin(x):
        if getattr(x, "ndim", 0) < 1 or x.shape[0] != num_stages:
            return x
        rest = [P.UNCONSTRAINED] * (x.ndim - 1)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(state.mesh, P(PP_AXIS, *rest))
        )

    return jax.tree_util.tree_map(pin, tree)


def stage_vmap(fn, num_stages, in_axes=0):
    """``jax.vmap`` of model code over the leading stage axis that also
    NAMES the axis: ``spmd_axis_name=pp`` when the mesh has pp > 1 and the
    ``num_stages`` rows divide over it (S = pp, or pp * v contiguous chunks
    in the fill-drain executor), plain ``vmap`` otherwise.

    GSPMD partitions plain ops over the pp-sharded stage dim by propagation,
    but what a stage holds that is NOT left to propagation only learns of
    the stage dim through this name: a ``shard_map`` region (the flash
    kernel's, the cp and collective-matmul rings) gives the batched dim the
    vmap's ``spmd_axis_name`` in its specs, or nothing — and with nothing
    every pp rank gathers and computes all stages' rows; a
    ``with_sharding_constraint`` likewise pins the stage dim to pp instead
    of leaving it open. Every stage-mapped call of model code in the
    executors goes through here; the ring helpers map the same axis over
    pure indexing and stay plain. No region inside a stage may itself name
    pp (``shard_map`` refuses a spec that repeats the vmap's axis)."""
    pp = _pp_size()
    named = pp > 1 and num_stages % pp == 0
    return jax.vmap(
        fn, in_axes=in_axes, spmd_axis_name=PP_AXIS if named else None
    )


def _get_subtree(params, path):
    node = params
    for part in path.strip("/").split("/"):
        if part:
            node = node[part]
    return node
