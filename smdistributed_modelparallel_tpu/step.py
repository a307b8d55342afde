"""@smp.step — the compiled training-step engine.

Parity target: reference ``torch/step.py:118-357`` (``StepFunction``): split
args into microbatches, execute forward/backward per microbatch under the
pipeline, reassemble ``StepOutput``. The reference dispatches each microbatch
through the module-server event loop (``torch/server.py``); here the whole
step — microbatch loop, forward, backward, gradient accumulation, data-
parallel reduction — is ONE jit-compiled SPMD program:

- the user step function runs under JAX tracing; ``model(...)`` applies the
  flax module with the trace's parameters and ``model.backward(loss)``
  records the loss to differentiate;
- microbatches are a ``lax.scan`` over a stacked leading axis (gradient
  accumulation with mean semantics, parity with
  ``torch/allreduce/ddp.py:92-98``);
- data parallelism comes from batch sharding over the mesh's data axes —
  XLA inserts the gradient psum (the reference's bucketed NCCL allreduce,
  SURVEY §2.1 N7, disappears);
- pipeline parallelism (pp > 1) lowers the scan to a 1F1B schedule (M2,
  ``parallel/pipeline.py``).

First call = the reference's trace-and-partition moment
(``torch/server.py:345-352``): parameters are materialized eagerly from the
first microbatch, the partitioner runs, then the step compiles.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from smdistributed_modelparallel_tpu.backend.split import (
    DeferredSplit,
    NonSplit,
    StepOutput,
    TensorSplitter,
    microbatch_slice,
    stack_leaf,
)
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.model import DistributedModel
from smdistributed_modelparallel_tpu.parallel import zero as zero_mod
from smdistributed_modelparallel_tpu.parallel.sharding import batch_spec
from smdistributed_modelparallel_tpu.resilience.chaos import chaos
from smdistributed_modelparallel_tpu.resilience.preemption import preemption
from smdistributed_modelparallel_tpu.resilience.supervisor import supervisor
from smdistributed_modelparallel_tpu.utils import exec_cache
from smdistributed_modelparallel_tpu.utils import health
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.utils import profiling
from smdistributed_modelparallel_tpu.utils.exceptions import StepUsageError
from smdistributed_modelparallel_tpu.utils.flight_recorder import flight_recorder
from smdistributed_modelparallel_tpu.utils.goodput import goodput
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import (
    record_step_time,
    telemetry,
)
from smdistributed_modelparallel_tpu.nn.utils import half_cast as half_cast_util

logger = get_logger()


class _ModelRef:
    """Static placeholder for a DistributedModel inside traced args.

    Value-hashable: instances are created fresh on every step call and feed
    the compiled-function cache key, so identity hashing would defeat the
    cache and silently retrace every step.
    """

    def __init__(self, index):
        self.index = index

    def __hash__(self):
        return hash((_ModelRef, self.index))

    def __eq__(self, other):
        return isinstance(other, _ModelRef) and other.index == self.index

    def __repr__(self):
        # Stable across processes: the repr feeds the persistent
        # executable cache's disk key (the default object repr embeds a
        # heap address).
        return f"_ModelRef({self.index})"


class StepFunction:
    def __init__(self, fn, non_split_inputs=None, input_split_axes=None):
        self.fn = fn
        self.non_split_inputs = non_split_inputs
        self.input_split_axes = input_split_axes
        self._cache = {}
        self._last_runner = None
        functools.update_wrapper(self, fn)

    # ------------------------------------------------------------------

    def __call__(self, *args, **kwargs):
        if state.cfg is None:
            raise StepUsageError("Call smp.init(config) before invoking an @smp.step function.")
        # One parent span round the whole call; its children (prepare,
        # lookup, place, dispatch, install, bookkeeping) cover it, so the
        # host time a step spends outside its executable has a name.
        with profiling.region("step", step=state.step_count):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        cfg = state.cfg
        with profiling.region("step/prepare"):
            model, stacked_args, stacked_kwargs, bucket_state = (
                self._prepare(cfg, args, kwargs)
            )
            tl = state.timeline
            telemetry.set_phase(f"step_{state.step_count}")
            flight_recorder.record_step("begin", state.step_count)
            # On-demand profiler capture (SMP_PROFILE=steps=N:M / SIGUSR2):
            # starts exactly at this step's begin edge when armed; a single
            # attribute test otherwise.
            profiling.capture.on_step_begin(state.step_count)
        t_step = time.perf_counter()
        if tl is not None and tl.enabled:
            tl.start_step(state.step_count)
            with tl.span(f"step_{state.step_count}"):
                grads, outputs = self._run_compiled(
                    model, stacked_args, stacked_kwargs, bucket_state
                )
                with profiling.region("step/fetch"):
                    jax.block_until_ready(outputs)
            tl.end_step(state.step_count)
            tl.flush()
        else:
            grads, outputs = self._run_compiled(
                model, stacked_args, stacked_kwargs, bucket_state
            )
        with profiling.region("step/bookkeeping"):
            # Dispatch wall time: exact when the timeline blocked above,
            # otherwise a lower bound (async dispatch returns before the
            # device finishes) — still enough for compile-vs-steady-state
            # attribution. Log-bucketed, so a tail step stays visible.
            record_step_time(time.perf_counter() - t_step)
            # Goodput ledger tick (publish + sentinel window at most once
            # per tick interval): one attribute test while disarmed.
            goodput.on_step_edge(state.step_count)
            profiling.capture.on_step_end(state.step_count, outputs=outputs)
            flight_recorder.record_step("end", state.step_count)
            telemetry.counter("smp_step_total", "step invocations").inc()
            if state.memory_metrics is not None:
                state.memory_metrics.record_step(state.step_count)
            from smdistributed_modelparallel_tpu.utils.metrics import (
                record_device_memory_telemetry,
            )

            record_device_memory_telemetry()
            state.step_count += 1
            # Step edge: the only point where every rank is at a known,
            # identical position in the program — chaos faults land here
            # deterministically, and a pending preemption (SIGTERM,
            # sentinel file, peer notice) turns into the coordinated
            # emergency checkpoint before the next step's work begins.
            # Both are single-flag no-ops when disarmed, and the
            # failure-recovery supervisor's edge hook (close a pending
            # recovery's MTTR, raise typed on a detected peer failure
            # before the next dispatch can hang on it) is ONE attribute
            # test when SMP_SUPERVISOR=off.
            chaos.on_step_edge(state.step_count)
            preemption.maybe_emergency_save()
            if supervisor.active:
                supervisor.on_step_edge()
        return StepOutput(outputs)

    def _prepare(self, cfg, args, kwargs):
        """Model extraction, shape bucketing, microbatch stacking and (on
        a model's first call) the init / backward-discovery pass."""
        model, clean_args, clean_kwargs = self._extract_model(args, kwargs)
        splitter = TensorSplitter(
            cfg.microbatches, self.non_split_inputs, self.input_split_axes
        )
        arg_names = _positional_names(self.fn, len(clean_args))
        # Shape bucketing (SMP_SHAPE_BUCKETS): pad the batch/sequence dims
        # up to the next configured bucket so variable-shaped batches map
        # onto a small set of compiled (and disk-cached) executables.
        # Batch padding is masked at microbatch granularity inside the
        # compiled program (exact, not approximate); unset policy is one
        # env lookup and leaves everything byte-identical.
        bucket_state = None
        policy = exec_cache.bucket_policy()
        if policy is not None:
            clean_args, clean_kwargs, bucket_state = _apply_shape_buckets(
                clean_args, clean_kwargs, arg_names, splitter, policy, cfg
            )
        stacked_args, stacked_kwargs = splitter.stack_microbatches(
            clean_args, clean_kwargs, arg_names
        )

        if model is not None and not model.initialized:
            self._init_run(model, stacked_args, stacked_kwargs)
        elif model is not None:
            # Model may have been initialized by another step fn or an eager
            # call: this StepFunction still needs to learn whether it calls
            # backward, and the partitioner must have run.
            self._discover_backward(model, stacked_args, stacked_kwargs)
            if model._partition_result is None:
                from smdistributed_modelparallel_tpu.parallel.partition import (
                    maybe_auto_partition,
                )

                maybe_auto_partition(model)
        return model, stacked_args, stacked_kwargs, bucket_state

    # ------------------------------------------------------------------

    def _extract_model(self, args, kwargs):
        model = None

        def swap(v):
            nonlocal model
            if isinstance(v, DistributedModel):
                model = v
                return _ModelRef(0)
            return v

        args = tuple(swap(a) for a in args)
        kwargs = {k: swap(v) for k, v in kwargs.items()}
        if model is None:
            model = state.model
        return model, args, kwargs

    def _init_run(self, model, stacked_args, stacked_kwargs):
        """Eager run of microbatch 0: materializes params (lazy flax init),
        discovers whether backward is used, and gives the partitioner
        concrete shapes. Parity: the reference's first-step trace
        (``torch/worker.py:248-278``)."""
        logger.info("First @smp.step call: running init/trace pass on microbatch 0.")
        mb_args = microbatch_slice(stacked_args, 0)
        mb_kwargs = microbatch_slice(stacked_kwargs, 0)
        mb_args, mb_kwargs = _resolve_model_refs(mb_args, mb_kwargs, model)
        model._tls.in_step = True
        model._tls.rngs = {s: state.rng_manager.next_key("init_" + s) for s in model.rng_streams}
        state._tracing = True
        try:
            self.fn(*mb_args, **mb_kwargs)
        finally:
            state._tracing = False
            self._has_backward = model._end_step_trace() is not None
        from smdistributed_modelparallel_tpu.parallel.partition import maybe_auto_partition

        maybe_auto_partition(model)

    def _discover_backward(self, model, stacked_args, stacked_kwargs):
        """Abstractly trace microbatch 0 to learn whether this step function
        calls model.backward (cheap: jax.eval_shape, no compute)."""
        if hasattr(self, "_has_backward"):
            return
        mb_args = microbatch_slice(stacked_args, 0)
        mb_kwargs = microbatch_slice(stacked_kwargs, 0)
        step_fn = self

        def probe(params):
            rngs = {s: jax.random.key(0) for s in model.rng_streams}
            model._begin_step_trace(params, rngs)
            try:
                args, kwargs = _resolve_model_refs(mb_args, mb_kwargs, model)
                step_fn.fn(*args, **kwargs)
            finally:
                loss = model._end_step_trace()
            step_fn._has_backward = loss is not None
            return jnp.zeros(())

        with jax.set_mesh(state.mesh):
            jax.eval_shape(probe, model.params)

    # ------------------------------------------------------------------

    def _run_compiled(self, model, stacked_args, stacked_kwargs,
                      bucket_state=None):
        with profiling.region("step/lookup"):
            # Chaos seam: `wedge@step=N:ms=M` hangs HERE — inside dispatch,
            # after the step-begin edge, before the compiled program runs —
            # so the rank keeps heartbeating (detector thread) while its
            # reported step edge stalls: the peers' supervisors must classify
            # it wedged, not dead. One env lookup when disarmed.
            chaos.on_step_dispatch(state.step_count)
            cfg = state.cfg
            mesh = state.mesh
            num_mb = cfg.microbatches

            # Partition the arg tree into scan leaves (DeferredSplit: restacked
            # to [num_mb, ...] inside the compiled program), broadcast array
            # leaves, and static leaves.
            tree = (stacked_args, stacked_kwargs)
            leaves, treedef = jax.tree_util.tree_flatten(
                tree, is_leaf=lambda x: isinstance(x, (NonSplit, _ModelRef, DeferredSplit))
            )
            scan_idx, bcast_idx, static = [], [], {}
            scan_vals, bcast_vals, scan_meta = [], [], []
            for i, leaf in enumerate(leaves):
                if isinstance(leaf, _ModelRef):
                    static[i] = leaf
                elif isinstance(leaf, DeferredSplit):
                    scan_idx.append(i)
                    scan_vals.append(leaf.value)
                    scan_meta.append((leaf.axis, leaf.num_mb, leaf.stacked))
                elif isinstance(leaf, NonSplit):
                    if _is_jax_type(leaf.value):
                        bcast_idx.append(i)
                        bcast_vals.append(leaf.value)
                    else:
                        static[i] = leaf.value
                else:  # untracked array leaf: broadcast
                    bcast_idx.append(i)
                    bcast_vals.append(leaf)

            # Fused optimizer update (TPU extension, cfg.fused_optimizer_step):
            # compile the optax update into the step program so a full training
            # iteration is ONE device launch. Disabled under fp16 loss scaling
            # (the overflow-skip decision lives in the scaler on the host).
            opt = state.optimizer
            fused = (
                getattr(cfg, "fused_optimizer_step", False)
                and opt is not None
                and opt.model is model
                and state.loss_scaler is None
                and getattr(self, "_has_backward", True)
            )
            if fused:
                opt._ensure_state()

            # state.generation pins the entry to the topology it was compiled
            # under: smp.reset()/re-init with a different cfg or mesh must not
            # serve a stale program whose shapes/flags happen to collide. The
            # health mode is part of the key: the sentinel reduces live inside
            # the program, so flipping SMP_HEALTH_CHECK recompiles. The
            # pipeline shape tuple (pp, schedule, virtual degree, microbatch
            # math) is keyed explicitly as well: the baked 1F1B schedule and
            # chunk layout depend on all four, and the key must not rely on
            # every config change also bumping the generation.
            hmode = health.mode()
            # Shape bucketing: a masked (microbatch-weighted) program differs
            # from the exact-shape program even at identical input shapes, so
            # the mask flag is part of the key. The weight VECTOR is a device
            # input — every occupancy of one bucket shares one executable.
            masked = bucket_state is not None
            pipe_key = (cfg.pipeline_parallel_degree, cfg.pipeline,
                        getattr(cfg, "virtual_pipeline_degree", 1),
                        num_mb, cfg.active_microbatches)
            # ZeRO knobs change the built program (param sharding layout,
            # slice-grad restructuring, bucket boundaries) without moving any
            # shape component — key them explicitly so a knob flip can never
            # warm-hit a stale executable. Mirrored in the exec-cache's
            # verified knob facts (utils/exec_cache.py) for the disk entries.
            # Sub-knobs that cannot affect the program under the current mode
            # (bucket/prefetch without zero3, the persistence threshold
            # without any ZeRO param sharding) are canonicalized out so an
            # idle env var never spuriously invalidates caches.
            zero3 = cfg.zero3_enabled
            zero_key = (getattr(cfg, "sharded_params", "none"),
                        getattr(cfg, "zero3_bucket_mb", 0) if zero3 else 0,
                        cfg.sdp_param_persistence_threshold
                        if (zero3 or cfg.zero2d_enabled) else 0,
                        cfg.sharded_data_parallel_degree,
                        # Prefetch flips between the transfer-register scan
                        # and the lifted scan at identical shapes.
                        zero_mod.prefetch_knob() if zero3 else "-")
            # Recompute-planner knob: a stash mode rebuilds the pipeline
            # executors (and the checkpoint policy) at identical shapes, so
            # the knob must be keyed. Canonicalized so idle values never
            # move the key: the default ("full") contributes NOTHING — the
            # key (and the disk key every stored entry and golden hashes)
            # stays byte-identical to pre-knob builds regardless of stray
            # budget env vars — and the budget is keyed only under "auto"
            # (the only mode that reads it).
            from smdistributed_modelparallel_tpu.parallel import remat_plan
            rmode = remat_plan.resolve(cfg)
            # Under "auto", an UNSET budget (-1: planner falls back to the
            # last audit's temp bytes or its own ring bound) is a different
            # program than an explicit 0 (degrade everything) — keep them
            # distinct. The audit-derived default itself is deliberately not
            # keyed (it is a volatile registry value); a plan drift under the
            # same key is caught by the disk cache's lowered-module content
            # hash, costing a verified miss, never a wrong program.
            _rbudget = getattr(cfg, "recompute_budget_mb", None)
            recompute_key = (
                () if rmode == "full"
                else ((rmode,
                       (-1 if _rbudget is None else int(_rbudget))
                       if rmode == "auto" else 0),)
            )
            # Overlapped-tp knobs: the ring decomposition and the fused QKV
            # kernel rebuild the program at identical shapes. Canonicalized
            # the recompute way: the defaults (mode "off" via
            # collective_matmul.tp_overlap_mode — which also folds in the
            # tp<=1 / cp>1 inertness — and fused_qkv False) contribute
            # NOTHING, so default keys stay byte-identical to pre-knob
            # builds. Mirrored in the exec-cache knob facts.
            from smdistributed_modelparallel_tpu.ops.collective_matmul import (
                fused_qkv_effective,
                tp_overlap_mode,
            )
            tmode = tp_overlap_mode(cfg)
            _fused_qkv = fused_qkv_effective(cfg)
            tp_overlap_key = (
                () if tmode == "off" and not _fused_qkv
                else ((tmode, _fused_qkv),)
            )
            # Low-precision knob, canonicalized the same way: the default
            # ("bf16", also the pp>1/zero3 fallback via
            # quant.matmul_precision_mode) contributes NOTHING — default
            # keys and the committed goldens stay byte-identical — while
            # fp8 rebuilds the program (quantized seams, the QuantState
            # input/output) at identical shapes. Mirrored in the exec-cache
            # knob facts.
            from smdistributed_modelparallel_tpu import quant as quant_mod
            qmode = quant_mod.matmul_precision_mode(cfg)
            quant_key = () if qmode == "bf16" else ((qmode,),)
            key_pre = (pipe_key, zero_key) + recompute_key + tp_overlap_key + quant_key + (
                       treedef, tuple(scan_idx), tuple(bcast_idx),
                       tuple((i, _static_key(v)) for i, v in sorted(static.items())),
                       tuple((v.shape, str(v.dtype)) for v in scan_vals),
                       tuple(scan_meta),
                       tuple((v.shape, str(v.dtype)) for v in bcast_vals),
                       getattr(self, "_has_backward", True), fused)
            key_post = (model.training if model is not None else None,
                        hmode, masked)
            key = ((state.generation,) + key_pre
                   + (opt._serial if fused else None,) + key_post)
            # Disk-cache key: generation and optimizer serial are per-process
            # instance counters that can never match across a restart — the
            # disk entry drops both and relies on the lowered-module hash
            # (verified at load) to catch any content difference they guarded.
            disk_key_src = key_pre + (None,) + key_post
            compiled = self._cache.get(key)
            cache_events = telemetry.counter(
                "smp_step_compile_cache_total",
                "compiled-step cache lookups by outcome",
            )
            if compiled is None:
                cache_events.labels(event="miss").inc()
                # Prior-generation entries are unreachable (their key[0] can
                # never match again) — evict them so re-init cycles don't
                # accumulate dead compiled executables.
                stale = [k for k in self._cache if k[0] != state.generation]
                for k in stale:
                    del self._cache[k]
                telemetry.set_phase(f"step_{state.step_count}/trace")
                t_build = time.perf_counter()
                with profiling.region("step/trace"):
                    compiled = self._build(
                        model, treedef, scan_idx, bcast_idx, static, num_mb,
                        scan_meta, opt.build_update_fn() if fused else None,
                        masked=masked,
                    )
                t_build = time.perf_counter() - t_build
                telemetry.histogram(
                    "smp_step_trace_seconds", "step program build/trace wall time"
                ).observe(t_build)
                flight_recorder.record_compile("trace", "step", t_build)
                # The X-ray fingerprint is keyed by this cache key: one audit
                # per distinct compiled program, re-identifiable across runs.
                compiled.audit_key = hlo_audit.cache_key_hash(key)
                compiled.disk_key = exec_cache.stable_key_hash(disk_key_src)
                self._cache[key] = compiled
            else:
                cache_events.labels(event="hit").inc()
            self._last_runner = compiled
            tokens = _count_tokens(scan_vals, scan_meta)
            if tokens:
                telemetry.counter(
                    "smp_step_tokens_total",
                    "input tokens consumed by step invocations",
                ).inc(tokens)
        with profiling.region("step/place"):
            # Device placement: params already sharded; shard batch over data axes
            # (replicate arrays whose dims don't divide the mesh axes, e.g. tiny
            # test batches). Skip the dispatch when the leaf already sits on the
            # target sharding (the steady-state case).
            scan_vals = [
                _place(v, _input_sharding(mesh, cfg, v, meta))
                for v, meta in zip(scan_vals, scan_meta)
            ]
            rng = state.step_rng
            if rng is None:
                rng = state.rng_manager.next_key("step")
            loss_scale = _cached_scalar(
                state.loss_scaler.loss_scale if state.loss_scaler else 1.0
            )
            opt_state = opt._opt_state if fused else ()
            has_backward = getattr(self, "_has_backward", True)
            if model is not None:
                # Forgot-optimizer.step() detector (both paths): a pending
                # fused update OR unconsumed grads with params untouched since
                # the previous step means the last step's work is being
                # discarded. Once is normal (an eval step in between);
                # repeatedly means the model silently never learns. Counter is
                # per-model (multi-model loops warn for the forgotten one) and
                # reset by that model's optimizer.step(). Eval-only steps (no
                # backward) neither produce nor consume updates — a train step
                # followed by N eval steps before optimizer.step() is a normal
                # loop shape, so they don't count.
                stale = model._pending_update is not None or (
                    model._grads_store is not None
                    and model._params is getattr(model, "_params_at_step", None)
                )
                if (stale and has_backward
                        and not getattr(cfg, "fused_step_donation", False)):
                    n = getattr(model, "_dropped_updates", 0) + 1
                    model._dropped_updates = n
                    if n == 3:
                        logger.warning(
                            "3 training steps ran without optimizer.step(): "
                            "parameter updates are computed and then "
                            "discarded, so the model is NOT learning. Call "
                            "optimizer.step() after each step (or enable "
                            "fused_step_donation to auto-install updates)."
                        )
                # An eval-only step must not clobber the pending train-step
                # state either: the fused update tuple and the fp16
                # grads-finite flag belong to the preceding train step and
                # are consumed by the upcoming optimizer.step().
                if has_backward:
                    model._params_at_step = model._params
                    model._pending_update = None
            in_params = model.params
            extra = ()
            if masked:
                extra = (_cached_mb_weights(
                    num_mb, bucket_state["active_mb"], mesh
                ),)
            if qmode == "fp8":
                # The delayed-scaling state rides the step like the fp16
                # loss scale: last step's scales enter as a program input,
                # the rolled history + refreshed scales come back as the
                # program's quant output, absorbed below.
                extra = extra + (quant_mod.ensure_state().arrays(),)
        (grads, outputs, grads_finite, next_rng, fused_out, health_word,
         quant_out) = (
            compiled(in_params, opt_state, scan_vals, bcast_vals, rng,
                     loss_scale, *extra)
        )
        with profiling.region("step/install"):
            if qmode == "fp8" and quant_out:
                quant_mod.ensure_state().absorb(quant_out)
            state.step_rng = next_rng
            schema = list(getattr(compiled, "health_schema", ()) or ())
            if schema:
                # Submit the still-on-device health word: the PREVIOUS step's
                # word is decoded now (its step has finished — no sync on the
                # step just dispatched). The bisector retains references to the
                # exact dispatched inputs so a trip can re-run this step
                # eagerly with per-module checkpoints.
                bisect_fn = None
                if model is not None and model._output_aval is not None:
                    reconstruct = self._make_reconstruct(
                        model, treedef, scan_idx, bcast_idx, static
                    )

                    def mb_args(mb, _sv=tuple(scan_vals), _sm=tuple(scan_meta),
                                _bv=tuple(bcast_vals), _rc=reconstruct):
                        # Restack on the host: the dispatched inputs are
                        # batch-sharded over the data axes, and an eager
                        # reshape of such an array to [num_mb, mb, ...] has
                        # no sharding to give its result.
                        leaves = [
                            stack_leaf(
                                np.asarray(v) if v.is_fully_addressable else v,
                                *m,
                            )[mb]
                            for v, m in zip(_sv, _sm)
                        ]
                        return _rc(leaves, list(_bv))

                    # in_params: the exact tree this step consumed. Retaining
                    # it for one step keeps bisection honest when an optimizer
                    # update lands before the word is decoded (it is dropped
                    # with the pending entry; donated trees are detected and
                    # fall back to the live params).
                    bisect_fn = health.make_bisector(
                        model, self.fn, mb_args, num_mb, rng, has_backward,
                        step_params=in_params,
                    )
                health.monitor.submit(
                    state.step_count, health_word, schema, hmode, bisect_fn
                )
            if model is not None and has_backward:
                model._grads_finite = grads_finite
                if grads is not None:
                    raw_div = getattr(compiled, "raw_divisor", None)
                    if raw_div:
                        if masked:
                            # The raw accumulator holds only the active
                            # microbatches (padding carries zero weight); the
                            # lazy mean divides by the live active count.
                            raw_div = bucket_state["active_mb"]
                        model._set_raw_grads(grads, raw_div)
                    else:
                        model._grads = grads
                if fused:
                    if getattr(cfg, "fused_step_donation", False):
                        # Donated inputs are gone: install the update NOW and
                        # leave a self-consistent pending tuple so a following
                        # optimizer.step() no-ops instead of re-applying.
                        model.params = fused_out[0]
                        opt._opt_state = fused_out[1]
                        model._pending_update = (
                            grads, fused_out[0], fused_out[1],
                            fused_out[0], fused_out[1],
                        )
                    else:
                        # Tokens of the exact inputs the fused update consumed:
                        # optimizer.step() installs the precomputed result only
                        # if neither grads, params, nor opt_state were replaced
                        # since.
                        model._pending_update = (
                            grads, fused_out[0], fused_out[1], in_params,
                            opt_state,
                        )
            if masked and bucket_state["active_mb"] < num_mb:
                # Padded microbatches computed garbage under a zero weight;
                # the user-visible StepOutput carries only the real ones
                # (padding is whole trailing microbatches by construction).
                act = bucket_state["active_mb"]
                outputs = jax.tree_util.tree_map(lambda x: x[:act], outputs)
        return grads, outputs

    @staticmethod
    def _make_reconstruct(model, treedef, scan_idx, bcast_idx, static):
        def reconstruct(mb_scan_leaves, bcast_leaves):
            leaves = [None] * treedef.num_leaves
            for i, v in zip(scan_idx, mb_scan_leaves):
                leaves[i] = v
            for i, v in zip(bcast_idx, bcast_leaves):
                leaves[i] = v
            for i, v in static.items():
                leaves[i] = v
            args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
            return _resolve_model_refs(args, kwargs, model)

        return reconstruct

    def _build(self, model, treedef, scan_idx, bcast_idx, static, num_mb,
               scan_meta, fused_update, masked=False):
        cfg = state.cfg
        if (
            cfg.pipeline_parallel_degree > 1
            and model is not None
            and model._pipeline_spec is not None
            and model._output_aval is not None
        ):
            return self._build_pipeline(
                model, treedef, scan_idx, bcast_idx, static, num_mb,
                scan_meta, fused_update,
            )
        has_backward = getattr(self, "_has_backward", True)
        half = cfg.half_dtype
        fn = self.fn

        reconstruct = self._make_reconstruct(model, treedef, scan_idx, bcast_idx, static)

        def mb_forward(run_params, mb_scan_leaves, bcast_leaves, key):
            rngs = {
                s: jax.random.fold_in(key, h)
                for h, s in enumerate(model.rng_streams)
            }
            model._begin_step_trace(run_params, rngs)
            try:
                args, kwargs = reconstruct(mb_scan_leaves, bcast_leaves)
                out = _user(fn, args, kwargs)
            finally:
                loss = model._end_step_trace()
            if has_backward and loss is None:
                raise StepUsageError(
                    "model.backward(loss) was not called in the step function."
                )
            return (loss if has_backward else jnp.zeros(())), out

        use_scaler = cfg.fp16
        # ZeRO-3 explicit gradient path: the microbatch forward runs
        # vmapped over an rdp-reshaped batch axis, so the per-slice weight
        # grads are genuine per-device partial sums and the cross-replica
        # reduction is OUR bucketed reduce-scatter (zero3_grad_reduce),
        # not a GSPMD-chosen all-reduce. Requires rdp to be the only
        # nontrivial mesh axis; other compositions keep sharded params +
        # just-in-time gathers with GSPMD-reduced grads.
        z3_manual = (
            zero_mod.zero3_manual_grads_supported(cfg) and has_backward
        )
        z3_rdp = zero_mod.rdp_size() if z3_manual else 1
        # Per-microbatch batch axis of each scan leaf (stacked inputs
        # carry their batch at 0 by the splitter's contract).
        mb_axes = [0 if stacked else axis for axis, _n, stacked in scan_meta]

        def step_impl(params, scan_leaves, bcast_leaves, rng, loss_scale,
                      mb_weights=None):
            hc = health.active()
            keys = jax.random.split(rng, num_mb)
            # Half-cast hoisted out of the microbatch scan: the cast is
            # loop-invariant, and differentiating w.r.t. the half params is
            # numerically identical (the astype VJP is an exact bf16->fp32
            # upcast of the cotangent, applied below at accumulation).
            with profiling.named_region("smp/step/cast_params"):
                run_params = half_cast_util(params, half)
            if has_backward:
                def scaled_fwd(run_params, mb_leaves, bcast_leaves, key):
                    loss, out = mb_forward(run_params, mb_leaves, bcast_leaves, key)
                    # fp8 delayed scaling: amax recorded during this
                    # forward are JVP-trace values — they must exit
                    # value_and_grad as aux OUTPUTS (a Python-side stash
                    # would hold dead tracers once the grad closes).
                    qd = _quant().scan_drain()
                    if qd:
                        out = (out, qd)
                    # fp16: differentiate scale*loss so half grads stay
                    # representable (reference LossScaler.backward).
                    return loss * loss_scale, out

                grad_fn = jax.value_and_grad(scaled_fwd, has_aux=True)

                use_z3 = z3_manual and zero_mod.zero3_sliceable(
                    scan_leaves, mb_axes, z3_rdp
                )
                if z3_manual and not use_z3:
                    logger.warning(
                        "zero3: a microbatch batch dim is not divisible by "
                        "rdp=%d; falling back to the GSPMD gradient "
                        "reduction for this program.", z3_rdp,
                    )
                if use_z3:
                    # Output-shape probe (abstract, no compute): the user
                    # fn's outputs must survive the slice-vmap round trip
                    # exactly — leading batch dims scale by rdp, scalars
                    # stay scalar. Outputs that don't (batch on a later
                    # axis, shapes that happen not to scale) cannot be
                    # reassembled without guessing; keep them untouched on
                    # the GSPMD gradient path instead.
                    def _out_avals(leaves):
                        def probe(rp, ls, key):
                            _, out = mb_forward(rp, ls, bcast_leaves, key)
                            return out

                        return jax.eval_shape(
                            probe, run_params, leaves, keys[0]
                        )

                    try:
                        plain_avals = _out_avals([
                            jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
                            for l in scan_leaves
                        ])
                        sliced_avals = _out_avals([
                            jax.ShapeDtypeStruct(
                                l.shape[1:1 + a]
                                + (l.shape[1 + a] // z3_rdp,)
                                + l.shape[2 + a:],
                                l.dtype,
                            )
                            for l, a in zip(scan_leaves, mb_axes)
                        ])
                        use_z3 = zero_mod.zero3_outputs_mergeable(
                            plain_avals, sliced_avals, z3_rdp
                        )
                    except Exception as e:
                        use_z3 = False
                        logger.warning(
                            "zero3: output-shape probe failed (%s); "
                            "falling back to the GSPMD gradient "
                            "reduction for this program.", e,
                        )
                    if not use_z3:
                        logger.warning(
                            "zero3: step outputs are not slice-mergeable "
                            "(need leading-batch arrays or scalars); "
                            "using the GSPMD gradient reduction so "
                            "outputs stay exact."
                        )

                def z3_body(acc, xs):
                    if mb_weights is None:
                        mb_leaves, key = xs
                        wmb = None
                    else:
                        mb_leaves, key, wmb = xs
                    sliced = [
                        zero_mod.zero3_slice_batch(l, a, z3_rdp)
                        for l, a in zip(mb_leaves, mb_axes)
                    ]
                    slice_keys = jax.random.split(key, z3_rdp)

                    def slice_fwd(run_params, sl_leaves, k):
                        loss, out = mb_forward(
                            run_params, sl_leaves, bcast_leaves, k
                        )
                        return loss * loss_scale, out

                    (loss_v, out), pgrads = jax.vmap(
                        jax.value_and_grad(slice_fwd, has_aux=True),
                        in_axes=(None, 0, 0),
                    )(run_params, sliced, slice_keys)
                    grads = zero_mod.zero3_grad_reduce(
                        pgrads, params, model, name="step"
                    )
                    out = zero_mod.zero3_merge_outputs(out)
                    loss_v = jnp.mean(loss_v)
                    if wmb is not None:
                        grads = jax.tree_util.tree_map(
                            lambda g: wmb.astype(g.dtype) * g, grads
                        )
                        loss_v = loss_v * wmb
                    acc = _accumulate(acc, grads)
                    ys = (out, loss_v) if hc is not None else out
                    return acc, ys

                def body(acc, xs):
                    # Shape bucketing (mb_weights): padded microbatches
                    # carry a zero weight — their grads and losses are
                    # masked out exactly, and the mean below divides by
                    # the ACTIVE count, so a bucketed run's numbers equal
                    # the exact-shape run's.
                    if mb_weights is None:
                        mb_leaves, key = xs
                        wmb = None
                    else:
                        mb_leaves, key, wmb = xs
                    (loss_v, out), grads = grad_fn(
                        run_params, mb_leaves, bcast_leaves, key
                    )
                    if _quant().scan_was_drained():
                        # Unwrap the aux-threaded amax and re-record them
                        # at THIS trace level so the body-end drain ships
                        # them out of the microbatch scan.
                        out, qaux = out
                        _quant().absorb_stacked(qaux)
                    if wmb is not None:
                        grads = jax.tree_util.tree_map(
                            lambda g: wmb.astype(g.dtype) * g, grads
                        )
                        loss_v = loss_v * wmb
                    acc = _accumulate(acc, grads)
                    # Health sentinel: the per-microbatch loss rides out of
                    # the scan so the word records the FIRST bad microbatch.
                    ys = (out, loss_v) if hc is not None else out
                    # fp8 delayed scaling: the amax observations absorbed
                    # from the grad aux above exit the scan as stacked
                    # outputs; () outside a quant trace — the ys pytree
                    # (and the program) is unchanged at the default.
                    qd = _quant().scan_drain()
                    if qd:
                        ys = (ys, qd)
                    return acc, ys

                acc0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, _acc_dtype(p.dtype, cfg)), params
                )
                if zero_mod.zero3_enabled(cfg):
                    # Sharded gradient accumulator: the carry keeps the
                    # params' rdp-sharded placements across microbatches,
                    # so per-mb grads reduce INTO shards rather than
                    # materializing replicated between iterations.
                    acc0 = zero_mod.zero3_pin_grads(acc0, model)
                xs = (
                    (scan_leaves, keys) if mb_weights is None
                    else (scan_leaves, keys, mb_weights)
                )
                grads, ys = jax.lax.scan(
                    z3_body if use_z3 else body, acc0, xs
                )
                if _quant().scan_was_drained():
                    ys, qstk = ys
                    # Max over the microbatch axis: one amax per slot for
                    # the whole step, folded into the rolled history at
                    # the runner's finalize.
                    _quant().absorb_stacked(qstk)
                if hc is not None:
                    outs, losses = ys
                    hc.add_stacked("loss", losses / loss_scale)
                    hc.add_stacked("outputs", outs)
                else:
                    outs = ys
                if fused_update is not None:
                    # Fused mode: return the RAW accumulator (aliases the
                    # scan carry, no extra materialization); the averaging
                    # folds into the optimizer-update kernels in the runner,
                    # and into a lazy divide if the user reads model.grads.
                    # (Loss scaling is off in fused mode.)
                    if zero_mod.zero3_enabled(cfg):
                        grads = zero_mod.zero3_pin_grads(grads, model)
                    return grads, outs, None
                # Microbatch averaging: parity with reference
                # torch/allreduce/ddp.py:92-98 (grads divided by num_mb);
                # loss-scale undone in the same pass. Bucketed programs
                # average over the active-microbatch count instead.
                divisor = (
                    num_mb if mb_weights is None
                    else jnp.maximum(jnp.sum(mb_weights), 1.0)
                )
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / (divisor * loss_scale)).astype(p.dtype),
                    grads, params,
                )
                if zero_mod.zero3_enabled(cfg):
                    grads = zero_mod.zero3_pin_grads(grads, model)
                finite = _grads_finite(grads) if use_scaler else None
                return grads, outs, finite

            def body(carry, xs):
                mb_leaves, key = xs
                _, out = mb_forward(run_params, mb_leaves, bcast_leaves, key)
                qd = _quant().scan_drain()
                return carry, ((out, qd) if qd else out)

            _, outs = jax.lax.scan(body, 0, (scan_leaves, keys))
            if _quant().scan_was_drained():
                outs, qstk = outs
                _quant().absorb_stacked(qstk)
            if hc is not None:
                hc.add_stacked("outputs", outs)
            return None, outs, None

        return _make_runner(step_impl, "step", scan_meta, fused_update, model,
                            raw_divisor=num_mb if fused_update is not None else None)

    def _build_pipeline(self, model, treedef, scan_idx, bcast_idx, static,
                        num_mb, scan_meta, fused_update):
        """pp > 1: one pipelined forward over all microbatches.

        The user fn is traced twice per microbatch: once with the model call
        intercepted to *capture* its inputs (loss math on the dummy output is
        dead code XLA eliminates), and once with the call *forced* to the
        pipeline's output for that microbatch to compute loss/outputs.
        Requires exactly one model(...) call per step function.

        Schedule dispatch: ``pipeline: interleaved`` (the default) lowers to
        the 1F1B executor with bounded in-flight microbatches
        (``parallel/pipeline_1f1b.py``; ``virtual_pipeline_degree > 1``
        selects its interleaved virtual-stage generalization inside the
        same entry point); ``zero_bubble`` takes the same entry point and
        selects the ZB-H1 split-backward executor (input-grad/weight-grad
        passes scheduled separately); ``simple`` / forward-only steps use
        the fill-drain executor (``parallel/pipeline.py``, which runs
        chunked layouts as sequential logical stages).
        """
        from smdistributed_modelparallel_tpu.parallel.pipeline import pipeline_forward

        has_backward = getattr(self, "_has_backward", True)
        cfg = state.cfg
        half = cfg.half_dtype
        fn = self.fn
        out_aval = model._output_aval
        reconstruct = self._make_reconstruct(model, treedef, scan_idx, bcast_idx, static)

        use_scaler = cfg.fp16
        use_1f1b = has_backward and cfg.pipeline in ("interleaved",
                                                     "zero_bubble")

        def capture_inputs(scan_leaves, bcast_leaves, keys):
            def cap_body(_, xs):
                mb_leaves, key = xs
                model._begin_capture(out_aval)
                try:
                    args, kwargs = reconstruct(mb_leaves, bcast_leaves)
                    _user(fn, args, kwargs)
                finally:
                    model._end_step_trace()
                captured = model._last_captured
                if len(captured) != 1:
                    raise StepUsageError(
                        "pipeline_parallel_degree > 1 requires exactly one "
                        f"model(...) call per step function (got {len(captured)})."
                    )
                return 0, captured[0]

            _, stacked_inputs = jax.lax.scan(cap_body, 0, (scan_leaves, keys))
            return stacked_inputs

        if use_1f1b:
            from smdistributed_modelparallel_tpu.parallel.pipeline_1f1b import (
                pipeline_1f1b,
            )

            def step_impl(params, scan_leaves, bcast_leaves, rng, loss_scale):
                keys = jax.random.split(rng, num_mb)
                stacked_inputs = capture_inputs(scan_leaves, bcast_leaves, keys)
                run_p = half_cast_util(params, half)

                def mb_loss_fn(out, mb_index, key):
                    mb_leaves = [
                        jax.lax.dynamic_index_in_dim(l, mb_index, 0, keepdims=False)
                        for l in scan_leaves
                    ]
                    rngs = {
                        s: jax.random.fold_in(key, h)
                        for h, s in enumerate(model.rng_streams)
                    }
                    model._begin_force(run_p, rngs, out)
                    try:
                        args, kwargs = reconstruct(mb_leaves, bcast_leaves)
                        user_out = _user(fn, args, kwargs)
                    finally:
                        loss = model._end_step_trace()
                    if loss is None:
                        raise StepUsageError(
                            "model.backward(loss) was not called in the step function."
                        )
                    return loss, user_out

                grads, losses, outs = pipeline_1f1b(
                    model, params, stacked_inputs, rng, mb_loss_fn,
                    loss_scale / num_mb,
                )
                hc = health.active()
                if hc is not None:
                    # Stage-boundary entries were contributed inside
                    # pipeline_1f1b (its tick scan is in THIS trace); the
                    # per-microbatch losses/outputs are unscaled here.
                    hc.add_stacked("loss", losses)
                    hc.add_stacked("outputs", outs)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / loss_scale).astype(p.dtype), grads, params
                )
                if zero_mod.zero3_enabled(cfg):
                    # pp x zero3: grads leave rdp-sharded; the reduction
                    # itself is GSPMD's (per-stage, inside the tick loop).
                    grads = zero_mod.zero3_pin_grads(grads, model)
                finite = _grads_finite(grads) if use_scaler else None
                return grads, outs, finite

            return _make_runner(
                step_impl, "step_pipeline_1f1b", scan_meta, fused_update, model
            )

        def step_impl(params, scan_leaves, bcast_leaves, rng, loss_scale):
            keys = jax.random.split(rng, num_mb)
            stacked_inputs = capture_inputs(scan_leaves, bcast_leaves, keys)
            # Health entries added INSIDE forward_all belong to the
            # value_and_grad inner trace; they leave through the aux output
            # (names are static Python and escape via this box) and are
            # restored into the step-trace collector afterwards.
            health_names = []

            def forward_all(p):
                hc = health.active()
                hmark = hc.mark() if hc is not None else 0
                run_p = half_cast_util(p, half)
                outs, pipe_aux = pipeline_forward(model, run_p, stacked_inputs, rng)

                def post_body(_, xs):
                    mb_leaves, out, key = xs
                    rngs = {
                        s: jax.random.fold_in(key, h)
                        for h, s in enumerate(model.rng_streams)
                    }
                    model._begin_force(run_p, rngs, out)
                    try:
                        args, kwargs = reconstruct(mb_leaves, bcast_leaves)
                        user_out = _user(fn, args, kwargs)
                    finally:
                        loss = model._end_step_trace()
                    if has_backward and loss is None:
                        raise StepUsageError(
                            "model.backward(loss) was not called in the step function."
                        )
                    return 0, (
                        loss if has_backward else jnp.zeros(()),
                        user_out,
                    )

                _, (losses, user_outs) = jax.lax.scan(
                    post_body, 0, (scan_leaves, outs, keys)
                )
                if hc is not None:
                    hc.add_stacked("loss", losses)
                    hc.add_stacked("outputs", user_outs)
                # MoE aux loss from the layer stack (0.0 for dense models);
                # mean-over-microbatch semantics matching the task loss.
                aux_w = float(getattr(cfg, "moe_aux_loss_weight", 1.0))
                total = jnp.mean(losses) + aux_w * pipe_aux / num_mb
                hvals = ()
                if hc is not None:
                    drained = hc.drain(hmark)
                    health_names[:] = [n for n, _, _, _ in drained]
                    hvals = tuple((b, a, m) for _, b, a, m in drained)
                return total * loss_scale, (user_outs, hvals)

            def restore_health(hvals):
                hc = health.active()
                if hc is not None:
                    hc.restore([
                        (n,) + tuple(v)
                        for n, v in zip(health_names, hvals)
                    ])

            if has_backward:
                (_, (outs, hvals)), grads = jax.value_and_grad(
                    forward_all, has_aux=True
                )(params)
                restore_health(hvals)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / loss_scale).astype(p.dtype), grads, params
                )
                if zero_mod.zero3_enabled(cfg):
                    grads = zero_mod.zero3_pin_grads(grads, model)
                finite = _grads_finite(grads) if use_scaler else None
                return grads, outs, finite
            _, (outs, hvals) = forward_all(params)
            restore_health(hvals)
            return None, outs, None

        return _make_runner(step_impl, "step_pipeline", scan_meta, fused_update, model)


def _quant():
    """Lazy quant-module accessor for the trace-time seams (keeps the
    import out of step.py's module load order)."""
    from smdistributed_modelparallel_tpu import quant

    return quant


def _make_runner(step_impl, name, scan_meta, fused_update, model,
                 raw_divisor=None):
    """Jit + AOT-compile the full per-step program once.

    The wrapper around ``step_impl`` performs, inside the SAME compiled
    program: the microbatch restack of raw batch leaves, the RNG-key advance
    (the next step's key is a program output, so no eager dispatch per
    step), and — under ``fused_optimizer_step`` — the optimizer update
    pinned to the partitioner's param shardings. Logs the one-time compile
    report (FLOPs / bytes / peak memory — the reference's one-time Studio
    metrics upload, ``torch/step.py:295-312``). Falls back to plain jit
    dispatch if the AOT path is unavailable."""
    from smdistributed_modelparallel_tpu.utils.metrics import (
        one_time_compile_report,
    )

    param_pin = model._param_shardings if model is not None else None
    opt_pin = None
    if fused_update is not None and state.optimizer is not None:
        # Captured eagerly (shardings are not queryable on tracers).
        opt_pin = jax.tree_util.tree_map(
            lambda l: l.sharding if isinstance(l, jax.Array) else None,
            state.optimizer._opt_state,
        )

    donate = (
        fused_update is not None
        and bool(getattr(state.cfg, "fused_step_donation", False))
    )

    # Health sentinel: the collector is live for exactly the span of each
    # step-program trace; the tags it gathers fuse into one [K, 3] "health
    # word" output. With SMP_HEALTH_CHECK=off the context yields None and
    # the program is byte-identical to a build without the sentinel.
    hmode = health.mode()
    schema_box = []

    # fp8 delayed scaling (matmul_precision: fp8): the runner decides
    # ONCE, at build time, whether this program threads QuantState —
    # mirroring the health sentinel: at the "bf16" default no context
    # installs, the quant output is () (flattens to nothing), and the
    # traced program is byte-identical to a build without smp.quant.
    quanted = _quant().matmul_precision_mode(state.cfg) == "fp8"

    def full_impl(params, opt_state, raw_scan, bcast_vals, rng, loss_scale,
                  *extra):
        # `extra` is the shape-bucketing microbatch-weight vector when the
        # step engine built a masked program, then the QuantState arrays
        # under fp8; empty otherwise (and the traced program is
        # byte-identical to the pre-bucketing build).
        qarrs = None
        if quanted:
            qarrs = extra[-1]
            extra = extra[:-1]
        with _quant().step_trace(qarrs), health.collecting(hmode) as hc:
            if hc is not None and hc.mode == "full":
                hc.add_tree("params", params)
            use_rng, next_rng = jax.random.split(rng)
            scan_leaves = [
                stack_leaf(v, *m) for v, m in zip(raw_scan, scan_meta)
            ]
            grads, outs, finite = step_impl(
                params, scan_leaves, bcast_vals, use_rng, loss_scale, *extra
            )
            if fused_update is not None:
                upd_grads = grads
                if raw_divisor is not None:
                    # Average the raw accumulator on the way into the update —
                    # this divide fuses into the optimizer's elementwise kernels
                    # instead of materializing an averaged-grads output. Under
                    # shape bucketing the accumulator holds only the ACTIVE
                    # microbatches' (weighted) grads, so the mean divides by
                    # the live active count instead of the static num_mb.
                    divisor = (
                        jnp.maximum(jnp.sum(extra[0]), 1.0) if extra
                        else raw_divisor
                    )
                    upd_grads = jax.tree_util.tree_map(
                        lambda g, p: (g / divisor).astype(p.dtype),
                        grads, params,
                    )
                new_params, new_opt = fused_update(params, opt_state, upd_grads)
                if param_pin is not None:
                    new_params = jax.lax.with_sharding_constraint(new_params, param_pin)
                if opt_pin is not None:
                    new_opt = jax.tree_util.tree_map(
                        lambda l, s: jax.lax.with_sharding_constraint(l, s)
                        if s is not None else l,
                        new_opt, opt_pin,
                        is_leaf=lambda x: x is None,
                    )
                fused_out = (new_params, new_opt)
            else:
                upd_grads = grads
                fused_out = ()
            if hc is not None and upd_grads is not None:
                # Global (averaged) grads: one entry for the whole tree.
                hc.add_tree("grads", upd_grads)
            word = ()
            if hc is not None:
                packed, names = hc.pack()
                if packed is not None:
                    word = packed
                    schema_box[:] = names
            # Rolled amax history + refreshed scales — the program's
            # quant output, absorbed into state.quant_state by the step
            # engine. () when not quanted: the flat outputs (and the
            # compiled program) are unchanged.
            qout = _quant().finalize(qarrs) if quanted else ()
        return grads, outs, finite, next_rng, fused_out, word, qout

    # fused_step_donation: params/opt_state buffers alias into
    # new_params/new_opt (same shapes + pinned shardings), dropping the
    # extra copy from peak HBM; the runner installs the update eagerly.
    jitted = jax.jit(full_impl, donate_argnums=(0, 1) if donate else ())
    mesh = state.mesh
    holder = {}

    def run(params, opt_state, scan_vals, bcast_vals, rng, loss_scale,
            *extra):
        with jax.set_mesh(mesh):
            if "compiled" not in holder:
                compiled = None
                source = "fresh"
                module_sha = None
                telemetry.set_phase(f"compile/{name}")
                t_lower = t_compile = 0.0
                disk_key = getattr(run, "disk_key", None)
                use_cache = bool(disk_key) and exec_cache.enabled()
                try:
                    # Trace+lower ALWAYS runs — shared by the fresh and
                    # warm paths (and, under the executable cache, the
                    # content check that catches changed user code or
                    # optimizer constants the shape key cannot see).
                    # Timed separately from the compile so the warm-start
                    # win (compile -> deserialize) is attributable.
                    t0 = time.perf_counter()
                    with profiling.region("step/lower"):
                        lowered = jitted.lower(
                            params, opt_state, scan_vals, bcast_vals,
                            rng, loss_scale, *extra,
                        )
                        if use_cache:
                            module_sha = exec_cache.module_hash(lowered)
                    t_lower = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    with profiling.region("step/compile"):
                        if use_cache:
                            # Persistent executable cache (smp.exec_cache):
                            # a verified disk hit replaces the XLA compile.
                            # The X-ray gauges/flight event are re-published
                            # from the post-load audit inside load(), so
                            # warm starts never bypass the drift gates.
                            with profiling.region("step/exec_cache_load"):
                                compiled, cached_audit = exec_cache.load(
                                    name, disk_key, module_sha=module_sha,
                                    params=params,
                                    expected_param_shardings=param_pin,
                                )
                            if compiled is not None:
                                source = "disk_cache"
                                run.hlo_audit = cached_audit
                        if compiled is None:
                            compiled = _compile_keyed_on_names(lowered)
                    t_compile = time.perf_counter() - t0
                    state.last_compile_report = one_time_compile_report(
                        name, compiled
                    )
                except Exception as e:  # pragma: no cover - backend-specific
                    # A compile-time RESOURCE_EXHAUSTED gets its post-mortem
                    # here; the jit fallback below will hit it again and
                    # raise through the guarded call path.
                    health.maybe_oom_postmortem(name, None, e)
                    logger.debug("AOT compile report unavailable: %s", e)
                telemetry.histogram(
                    "smp_step_lower_seconds",
                    "trace+lower wall time (paid by fresh and warm paths)",
                ).observe(t_lower)
                telemetry.histogram(
                    "smp_step_compile_seconds",
                    "XLA compile wall time (disk_cache source: "
                    "deserialize+verify instead of compile)",
                ).labels(source=source).observe(t_compile)
                flight_recorder.record_compile("lower", name, t_lower)
                flight_recorder.record_compile("xla_compile", name, t_compile)
                exec_cache.record_compile_event(name, source, t_compile)
                if compiled is not None and source == "fresh":
                    # Compiled-program X-ray (smp.xray): collective census
                    # + replication detector + remat/memory fingerprint of
                    # the program just built. SMP_HLO_AUDIT=off makes this
                    # a no-op before the executable is touched.
                    run.hlo_audit = hlo_audit.maybe_audit(
                        name, compiled,
                        key=getattr(run, "audit_key", None),
                        params=params,
                        expected_param_shardings=param_pin,
                    )
                    if use_cache:
                        with profiling.region("step/exec_cache_store"):
                            exec_cache.store(
                                name, disk_key, compiled,
                                module_sha=module_sha,
                                audit=run.hlo_audit,
                                compile_seconds=t_compile,
                            )
                telemetry.set_phase(f"run/{name}")
                holder["compiled"] = compiled
            c = holder["compiled"]
            if c is not None:
                try:
                    with profiling.region("step/dispatch"):
                        return c(params, opt_state, scan_vals, bcast_vals,
                                 rng, loss_scale, *extra)
                except (TypeError, ValueError) as e:
                    # Input aval/sharding mismatch only (the step cache keys
                    # on shapes, so this is a layout drift, e.g. resharded
                    # params after checkpoint load). Real runtime failures
                    # (XlaRuntimeError etc.) propagate.
                    logger.warning(
                        "AOT step executable rejected inputs (%s); "
                        "falling back to jit dispatch.", e,
                    )
                    holder["compiled"] = None
                except Exception as e:
                    # RESOURCE_EXHAUSTED: dump the executable's XLA memory
                    # breakdown + live buffers + remat/offload config before
                    # the error reaches the user (utils/health.py).
                    health.maybe_oom_postmortem(name, c, e)
                    raise
            try:
                with profiling.region("step/dispatch"):
                    return jitted(params, opt_state, scan_vals, bcast_vals,
                                  rng, loss_scale, *extra)
            except Exception as e:
                health.maybe_oom_postmortem(name, holder.get("compiled"), e)
                raise

    run.jitted = jitted
    run.mesh = mesh
    run.holder = holder
    run.step_name = name
    run.raw_divisor = raw_divisor if fused_update is not None else None
    run.health_schema = schema_box
    return run


_CACHE_KEY_METADATA = "jax_compilation_cache_include_metadata_in_key"


def _compile_keyed_on_names(lowered):
    """``lowered.compile()``; with the op index on, under a persistent
    cache key that holds the module's metadata too. JAX keys its compile
    cache on the module stripped of debug info, so a cache directory an
    older build filled hands this build that build's executable, the same
    program under the older ``op_name``s, and every scope added since
    reads nothing (``hlo_audit.op_index`` is built from those names). A
    second run of one tree lowers the same metadata and still hits."""
    if not (hlo_audit.enabled() and hasattr(jax.config, _CACHE_KEY_METADATA)):
        return lowered.compile()
    before = getattr(jax.config, _CACHE_KEY_METADATA)
    jax.config.update(_CACHE_KEY_METADATA, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(_CACHE_KEY_METADATA, before)


def _user(fn, args, kwargs):
    """The user's step function as the step program traces it, under the
    outermost scope of the tree (``utils/profiling.SCOPES``): an operation
    whose innermost scope is this one ran code the user wrote (a loss
    written out in the step function), not the library's."""
    with profiling.named_region("smp/step/user"):
        return fn(*args, **kwargs)


def _accumulate(acc, grads):
    """One microbatch's gradients added into the accumulator, in the
    accumulator's dtype (under a scope of its own: the device trace then
    says what accumulation costs)."""
    with profiling.named_region("smp/step/accumulate"):
        return jax.tree_util.tree_map(
            lambda a, g: a + g.astype(a.dtype), acc, grads
        )


def _count_tokens(scan_vals, scan_meta):
    """Token count of one step's batch for the telemetry throughput
    counter: leading batch dims x sequence dim of the FIRST batch-like scan
    input ([B, T, ...] raw; [num_mb, mb, T, ...] pre-stacked). A proxy, not
    an exact semantic count — inputs without a sequence dim count their
    batch elements."""
    for v, (axis, num_mb, stacked) in zip(scan_vals, scan_meta):
        shape = getattr(v, "shape", None)
        if not shape or len(shape) < 2:
            continue
        lead = min(3 if stacked else 2, len(shape))
        tokens = 1
        for d in shape[:lead]:
            tokens *= int(d)
        return tokens
    return 0


def _place(v, sharding):
    if isinstance(v, jax.Array) and v.sharding == sharding:
        return v
    return jax.device_put(v, sharding)


_SCALAR_CACHE = {}


def _cached_scalar(value):
    """Device scalar for a host float, cached: avoids a host->device
    transfer per step for values that change rarely (the loss scale)."""
    key = float(value)
    out = _SCALAR_CACHE.get(key)
    if out is None:
        if len(_SCALAR_CACHE) > 64:
            _SCALAR_CACHE.clear()
        out = jnp.asarray(key, jnp.float32)
        _SCALAR_CACHE[key] = out
    return out


_MB_WEIGHTS_CACHE = {}


def _cached_mb_weights(num_mb, active, mesh):
    """Replicated [num_mb] 0/1 weight vector for a bucketed step: ones for
    the active (real) microbatches, zeros for the padding. Cached per
    occupancy so steady-state bucketed steps pay no host->device
    transfer."""
    import numpy as np

    key = (num_mb, active, mesh)
    out = _MB_WEIGHTS_CACHE.get(key)
    if out is None:
        if len(_MB_WEIGHTS_CACHE) > 64:
            _MB_WEIGHTS_CACHE.clear()
        w = np.zeros((num_mb,), np.float32)
        w[:active] = 1.0
        out = jax.device_put(w, NamedSharding(mesh, P()))
        _MB_WEIGHTS_CACHE[key] = out
    return out


def _apply_shape_buckets(args, kwargs, arg_names, splitter, policy, cfg):
    """Pad batch/sequence dims of the splittable step inputs up to the
    configured ``SMP_SHAPE_BUCKETS`` boundaries.

    Returns ``(args, kwargs, bucket_state)``; ``bucket_state`` is None
    when no masked program is needed (policy doesn't apply, batch already
    above every bucket, padding would create a partial microbatch, or
    the path doesn't support masking) and ``{"active_mb": k, ...}`` when
    the engine should build/reuse the microbatch-masked program.

    Exactness contract: batch padding fills whole trailing microbatches
    (rejected as ``unbucketable`` otherwise), masked to zero weight inside
    the compiled program — losses/grads equal the exact-shape run's.
    Sequence padding appends ``seq_pad``-valued positions on the right;
    masking those is the model's contract (causal attention + ignore-index
    losses are unaffected).
    """
    from smdistributed_modelparallel_tpu.backend.split import _is_array

    num_mb = cfg.microbatches
    # Masked batch bucketing composes with the plain scan path (fused
    # optimizer included — the update's microbatch divisor becomes the
    # active count); the pipeline schedules bake the microbatch layout
    # into the program and stay exact-shape.
    maskable = cfg.pipeline_parallel_degree <= 1

    def leaf_axis_pairs(value, name):
        if name is not None and name in splitter.non_split_inputs:
            return []
        axis = splitter.input_split_axes.get(name, 0)
        return [
            (leaf, axis)
            for leaf in jax.tree_util.tree_leaves(
                value, is_leaf=lambda x: hasattr(x, "smp_slice")
            )
            if _is_array(leaf) and not hasattr(leaf, "smp_slice")
            and leaf.ndim > axis
        ]

    named = [
        (v, arg_names[i] if i < len(arg_names) else None)
        for i, v in enumerate(args)
    ] + [(v, k) for k, v in kwargs.items()]
    pairs = [p for v, n in named for p in leaf_axis_pairs(v, n)]
    if not pairs:
        return args, kwargs, None
    batch = int(pairs[0][0].shape[pairs[0][1]])
    ref_seq = None
    for leaf, axis in pairs:
        if leaf.ndim > axis + 1:
            ref_seq = int(leaf.shape[axis + 1])
            break

    batch_tgt = None
    active_mb = None
    if maskable and policy.get("batch"):
        tgt = exec_cache.bucket_for(batch, policy["batch"])
        if tgt is None:
            exec_cache.record_bucket("unbucketable")
            logger.debug(
                "shape buckets: batch %d exceeds every bucket %s; exact "
                "compile.", batch, policy["batch"],
            )
        elif tgt % num_mb != 0 or batch % max(tgt // num_mb, 1) != 0:
            # A partial microbatch cannot be masked exactly (its loss
            # would mix real and padded rows); fall back to the exact
            # shape rather than silently change the numbers.
            exec_cache.record_bucket("unbucketable")
            logger.debug(
                "shape buckets: batch %d -> bucket %d not maskable at "
                "microbatches=%d; exact compile.", batch, tgt, num_mb,
            )
        else:
            batch_tgt = tgt
            active_mb = batch // (tgt // num_mb)
            exec_cache.record_bucket(
                "padded" if tgt != batch else "exact"
            )
    seq_tgt = None
    if policy.get("seq") and ref_seq is not None:
        st = exec_cache.bucket_for(ref_seq, policy["seq"])
        if st is not None and st != ref_seq:
            seq_tgt = st

    if batch_tgt is None and seq_tgt is None:
        return args, kwargs, None

    def pad_leaf(leaf, axis):
        pads = [(0, 0)] * leaf.ndim
        changed = False
        if (batch_tgt is not None and batch_tgt != batch
                and leaf.shape[axis] == batch):
            pads[axis] = (0, batch_tgt - batch)
            changed = True
        if changed:
            leaf = jnp.pad(leaf, pads)
            pads = [(0, 0)] * leaf.ndim
            changed = False
        if (seq_tgt is not None and leaf.ndim > axis + 1
                and leaf.shape[axis + 1] == ref_seq):
            pads[axis + 1] = (0, seq_tgt - ref_seq)
            leaf = jnp.pad(
                leaf, pads, constant_values=policy.get("seq_pad", 0)
            )
        return leaf

    def pad_value(value, name):
        if name is not None and name in splitter.non_split_inputs:
            return value
        axis = splitter.input_split_axes.get(name, 0)
        return jax.tree_util.tree_map(
            lambda leaf: pad_leaf(leaf, axis)
            if _is_array(leaf) and not hasattr(leaf, "smp_slice")
            and leaf.ndim > axis else leaf,
            value,
            is_leaf=lambda x: hasattr(x, "smp_slice"),
        )

    new_args = tuple(
        pad_value(v, arg_names[i] if i < len(arg_names) else None)
        for i, v in enumerate(args)
    )
    new_kwargs = {k: pad_value(v, k) for k, v in kwargs.items()}
    if batch_tgt is None:
        # Sequence-only padding needs no mask: the program is the
        # standard one at the bucketed shape.
        return new_args, new_kwargs, None
    return new_args, new_kwargs, {
        "active_mb": int(active_mb),
        "num_mb": int(num_mb),
        "batch": int(batch),
        "batch_target": int(batch_tgt),
        "seq_target": seq_tgt,
    }


def _input_sharding(mesh, cfg, arr, meta):
    """Batch sharding for a raw (or pre-stacked) scan input, dropping mesh
    axes that don't divide the corresponding dim (falls back to
    replication). For raw leaves the divisibility check applies to the
    post-stack per-microbatch dim."""
    axis, num_mb, stacked = meta
    ndim = len(arr.shape)
    spec = list(batch_spec(
        cfg, ndim, batch_axis=0 if stacked else axis, stacked=stacked
    ))
    batch_dim = 1 if stacked else axis
    for dim, axes in enumerate(spec):
        if axes is None:
            continue
        axes_tuple = axes if isinstance(axes, tuple) else (axes,)
        size = 1
        for a in axes_tuple:
            size *= mesh.shape[a]
        dim_size = arr.shape[dim]
        if dim == batch_dim and not stacked:
            dim_size = dim_size // num_mb
        if dim_size % size != 0:
            spec[dim] = None
    return NamedSharding(mesh, P(*spec))


def _grads_finite(grads):
    """Single bool: every grad element finite (the reference's overflow
    allgather across pp+tp collapses to this reduction under SPMD)."""
    leaves = [jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]
    out = leaves[0]
    for l in leaves[1:]:
        out = jnp.logical_and(out, l)
    return out


def _acc_dtype(dtype, cfg):
    if jnp.issubdtype(dtype, jnp.floating) and cfg._fp32_grad_accumulation:
        return jnp.float32
    return dtype


def _resolve_model_refs(args, kwargs, model):
    def res(v):
        return model if isinstance(v, _ModelRef) else v

    args = jax.tree_util.tree_map(
        res, args, is_leaf=lambda x: isinstance(x, _ModelRef)
    )
    kwargs = jax.tree_util.tree_map(
        res, kwargs, is_leaf=lambda x: isinstance(x, _ModelRef)
    )
    return args, kwargs


def _is_jax_type(v):
    # Python scalars stay static (hashable cache keys): users branch on them
    # (`if training:`) and flax takes them as static flags; tracing them
    # would raise TracerBoolConversionError.
    import numpy as np

    return isinstance(v, (jax.Array, np.ndarray, jnp.ndarray))


def _static_key(v):
    try:
        hash(v)
        return v
    except TypeError:
        return repr(v)


def _positional_names(fn, n):
    import inspect

    try:
        params = list(inspect.signature(fn).parameters.values())
    except (TypeError, ValueError):
        return [None] * n
    names = []
    for p in params:
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            names.append(p.name)
    while len(names) < n:
        names.append(None)
    return names[:n]


def step(fn=None, *, non_split_inputs=None, input_split_axes=None):
    """Decorator: ``@smp.step`` or ``@smp.step(non_split_inputs=[...])``.

    Parity: reference ``torch/step.py:118`` / ``backend/split.py`` options.
    """
    if fn is not None:
        return StepFunction(fn)

    def wrap(f):
        return StepFunction(f, non_split_inputs, input_split_axes)

    return wrap
