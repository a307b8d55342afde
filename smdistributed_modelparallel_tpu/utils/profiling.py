"""Performance observability: named profiler regions, on-demand XLA
profiler capture, and roofline/MFU attribution (``smp.profiling``).

The reference library ships profiling hooks as a first-class surface
(herring timers + the ``smp_timeline_*`` C API around every server
action); this module is the TPU build's equivalent, designed around the
fact that chip windows on this image are rare and flaky: when one opens,
a single run must capture a trace and attribute the MFU gap without
anyone re-running ad-hoc probes. Three cooperating pieces:

1. **Named regions** — one vocabulary for every profiling surface.
   ``region(name)`` brackets a host-side phase with
   ``jax.profiler.TraceAnnotation`` (so the region shows up, by the same
   name, in an XLA profiler trace, on the device trace's clock) AND a
   ``state.timeline`` span (so ``scripts/trace_fuse.py`` can align it
   cross-rank and report per-phase skew), and observes its duration in
   the one histogram family ``smp_host_phase_seconds{phase=<name>}``.
   Regions nest on a thread-local stack: each writes the step it belongs
   to and its parent into the annotation (``step=``, ``parent=``) and
   the timeline event. The regions in use:

   - ``step`` round ``StepFunction.__call__``, and its children, which
     together cover it: ``step/prepare`` (model extraction, bucketing,
     microbatch stacking, init/discover, begin-edge hooks),
     ``step/lookup`` (cache key and get; on a miss ``step/trace`` inside
     it), ``step/place`` (input placement up to the executable call),
     ``step/dispatch`` (the executable call alone; on a first call
     ``step/lower`` and ``step/compile`` with ``step/exec_cache_load`` /
     ``_store`` before it), ``step/install`` (from its return: update,
     finite flag, health word), ``step/bookkeeping`` (telemetry, flight
     recorder, goodput, memory telemetry, chaos / preemption /
     supervisor edges); ``step/fetch`` only where the timeline blocks;
   - ``optimizer/step``; ``collective/broadcast``, ``allgather``,
     ``barrier/<group>`` (host collectives); ``serve/compile_<kind>``,
     ``serve/decode_step``, ``serve/prefill_chunk``.

   ``named_region(name)`` is the in-graph twin: a ``jax.named_scope``
   whose name lands in the compiled HLO's op metadata. ``SCOPES`` lists
   every one the package writes (``smp/<subsystem>/<name>``) with the
   round it is put: the user's step function outermost
   (``smp/step/user``), embeddings, a looped stack's passes, head, exit
   gate and loss, each layer, its branch norms, its attention and its
   parts, the dense feed-forward, the expert layer's parts, the pipeline executors' segments and per-tick sub-steps (with
   the pass coordinate under split-backward schedules: ``tick_bwd`` vs
   ``tick_bwd_input`` / ``tick_bwd_weight``), gradient accumulation, the
   half-precision parameter cast and the optimizer update.
   ``hlo_audit.op_index`` reads them back per instruction, and
   ``hlo_audit.seconds_by_scope`` joins a device's time to the tree.

2. **On-demand capture** — ``SMP_PROFILE=steps=N:M`` brackets
   ``jax.profiler.start_trace``/``stop_trace`` around exactly steps
   N..M (inclusive) into a per-rank directory under ``SMP_PROFILE_PATH``
   (default ``smp_profile/rank<i>``). ``SIGUSR2`` arms a one-step capture
   on a live run. Disarmed cost is one attribute test per step edge; the
   start/stop overhead of an actual capture is recorded in
   ``smp_profile_overhead_seconds_total`` so always-on cost stays
   measurably zero. When a window stops, the program reduces its own
   trace (``device_op_seconds``: the first device's op line, self
   times), joins it to the step's op index and writes
   ``scope_report.json`` beside the ``.xplane.pb``: busy seconds, self
   seconds by scope path, the user's own, the unscoped with their ten
   largest ops, by phase, by mesh axis, by compiler-made kernel; the
   tree's first two levels are logged as a table, and the reduction is
   charged to the same overhead counter.

3. **Roofline / MFU attribution** — ``roofline(...)`` joins compiled-HLO
   ``cost_analysis``/``memory_analysis`` (FLOPs, bytes accessed) with a
   measured step wall time and the device's peak FLOP/s + HBM bandwidth
   (spec-sheet table by ``device_kind``; ``SMP_PEAK_TFLOPS`` /
   ``SMP_PEAK_GBPS`` override for unlisted backends) into MFU, achieved
   bytes/s, arithmetic intensity vs the ridge point, and a
   compute-vs-comm-vs-bubble decomposition of the step time (bubble from
   the pipeline occupancy gauges). Published as ``smp_mfu`` /
   ``smp_roofline_*`` gauges and rendered by the "performance" section of
   ``scripts/telemetry_report.py``. The caller brings the step time,
   measured to the end of the device's work: the step engine blocks no
   step to take one.

Import-hygiene contract: importing this module must never initialize an
accelerator backend (``jax.profiler``/``jax.named_scope`` are pure-host
imports; ``jax.devices()`` is only touched from ``device_peaks`` at
attribution time).
"""

import atexit
import json
import os
import signal
import threading
import time

import jax

from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import (
    HOST_PHASE_BUCKETS,
    telemetry,
)

logger = get_logger()

PROFILE_ENV = "SMP_PROFILE"
PROFILE_PATH_ENV = "SMP_PROFILE_PATH"
PEAK_TFLOPS_ENV = "SMP_PEAK_TFLOPS"
PEAK_GBPS_ENV = "SMP_PEAK_GBPS"

# Region names are prefixed so every surface (XLA profiler trace, our
# Perfetto timeline, trace_fuse's per-phase skew report, compiled-HLO op
# metadata) can recognize them by one convention:
#   host phases:    smp_phase/<name>   (region())
#   in-graph scopes: smp/<subsystem>/<name>  (named_region())
REGION_PREFIX = "smp_phase/"


#: Every in-graph scope the package writes (``named_region`` /
#: ``jax.named_scope``; ``tests/test_profiling.py`` greps), with the round
#: each is put. ``hlo_audit.op_index`` gives every instruction of a
#: compiled step the scopes round it, outermost first, and
#: ``hlo_audit.seconds_by_scope`` joins a device's time to them.
SCOPES = {
    "smp/step/user": "the user's step function as @smp.step traces it, "
                     "outermost; innermost only on what the function "
                     "wrote itself (a loss written out there)",
    "smp/step/cast_params": "the half-precision copy of the parameters "
                            "the forward reads",
    "smp/step/accumulate": "a microbatch's gradients added into the "
                           "accumulator",
    "smp/optimizer/update": "the optimizer update fused into the step",
    "smp/model/embed": "token, position and type embeddings, their norm "
                       "and dropout",
    "smp/model/stack": "the layer stack at pp = 1; innermost on the "
                       "scans' own work (a layer's slice of the stacked "
                       "parameters, the residuals stacked for the "
                       "backward pass)",
    "smp/model/loop": "a looped stack's passes; innermost on the passes' "
                      "own work (the norm after a pass, the carried "
                      "state, the states stacked for the head)",
    "smp/head/norm": "the final norm",
    "smp/head/logits": "the LM head's product (tied attend, untied "
                       "lm_head, its vocabulary split)",
    "smp/head/loss": "nn/cross_entropy's entry points and "
                     "nn/diffusion.masked_diffusion_loss",
    "smp/head/exit_gate": "a looped model's exit gate: its product after "
                          "each pass, and nn/exit_gate.exit_gated_loss",
    "smp/layer/<kind>": "a layer of a patterned stack, by its kind's name",
    "smp/layer/block": "a layer of a stack with no kind",
    "smp/layer/branch_norm": "inside a layer: the norm of a branch's "
                             "output before the residual add",
    "smp/attn/full": "a layer's attention with no window",
    "smp/attn/window": "a layer's attention under a window",
    "smp/attn/block_diffusion": "a layer's attention under the "
                                "block-diffusion mask",
    "smp/attn/qkv": "inside any attention: the q/k/v projections",
    "smp/attn/qk_norm": "inside any attention: the per-head norms of q "
                        "and k",
    "smp/attn/core": "inside any attention: rotary, the cache, the flash "
                     "kernels or the plain path",
    "smp/attn/out": "inside any attention: the head gate and the output "
                    "projection",
    "smp/conv/in_proj": "a short-convolution mixer: the input projection "
                        "to its three streams",
    "smp/conv/core": "a short-convolution mixer: the first gate, the "
                     "causal depthwise convolution, the second gate",
    "smp/conv/out_proj": "a short-convolution mixer: the output "
                         "projection",
    "smp/latent/q_down": "latent attention: the query's "
                              "down-projection and its latent's norm",
    "smp/latent/q_up": "latent attention: the query heads from "
                            "their latent",
    "smp/latent/kv_down": "latent attention: the key-value latent "
                               "and the shared rotary key, one product, "
                               "and the latent's norm",
    "smp/latent/kv_up": "latent attention: the heads' keys (no "
                             "position) and values from the latent",
    "smp/latent/rope": "latent attention: rotary on the queries' "
                            "and the shared key's rotary part, and the "
                            "heads put together",
    "smp/latent/out": "latent attention: the output projection",
    "smp/mhc/coeff": "hyper-connection: the streams' norm and the "
                     "products that give a token its pre, post and "
                     "residual coefficients",
    "smp/mhc/sinkhorn": "hyper-connection: the residual coefficients "
                        "made doubly stochastic",
    "smp/mhc/pre": "hyper-connection: the streams mixed into a "
                   "sub-layer's input",
    "smp/mhc/post_res": "hyper-connection: the streams mixed among "
                        "themselves and the sub-layer's output spread "
                        "over them",
    "smp/mlp/dense": "the dense feed-forward of a layer",
    "smp/moe/route": "dropless expert layer: router product, softmax, "
                     "top-k",
    "smp/moe/dispatch": "dropless expert layer: the sort by held expert "
                        "and each chunk's gathers",
    "smp/moe/experts": "dropless expert layer: each chunk's grouped gated "
                       "FFN, forward and written-out backward",
    "smp/moe/shared": "dropless expert layer: the shared expert",
    "smp/moe/combine": "dropless expert layer: the routed rows summed "
                       "back to their tokens, the sum with the shared "
                       "expert, the final cast",
    "smp/pipeline/embed": "pipeline executors: the embedding of every "
                          "microbatch before the tick loop",
    "smp/pipeline/head": "pipeline executors: head and loss of a "
                         "microbatch on the last stage's output",
    "smp/pipeline/tick_fwd": "a tick's forward sub-step",
    "smp/pipeline/tick_bwd": "a tick's backward sub-step (fused "
                             "executors)",
    "smp/pipeline/tick_bwd_input": "zero-bubble: a tick's input-gradient "
                                   "sub-step",
    "smp/pipeline/tick_bwd_weight": "zero-bubble: a tick's "
                                    "weight-gradient sub-step",
    "smp/pipeline/warmup": "the tick loop's forward-only segment",
    "smp/pipeline/steady": "the tick loop's segment with forward and "
                           "backward sub-steps",
    "smp/pipeline/cooldown": "the tick loop's backward-only segment",
    "smp/pipeline/cooldown_weight": "zero-bubble: the segment that "
                                    "drains weight gradients",
    "smp/pipeline/fill_drain": "the fill-drain executor's tick loop",
    "smp/pipeline/finish": "1F1B executors, after the last tick: the "
                           "embedding's backward over the microbatches "
                           "and the gradient tree laid out by layer",
}


def _timeline():
    """The live session timeline, or None. Resolved lazily: this module
    must not import backend.state at import time (state pulls in the whole
    core, and collectives/step import *us*)."""
    from smdistributed_modelparallel_tpu.backend.state import state

    return state.timeline


_open = threading.local()    # .stack: this thread's open regions, outermost first


def _open_regions():
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    return stack


class _Region:
    """One named host-side profiler region (see ``region``)."""

    __slots__ = ("name", "track", "step", "parent", "_ta", "_tl",
                 "_begin_us", "_t0")

    def __init__(self, name, track, step=None):
        self.name = name
        self.track = track
        self.step = step
        self.parent = None
        self._ta = None
        self._tl = None
        self._begin_us = 0.0
        self._t0 = 0.0

    def __enter__(self):
        stack = _open_regions()
        if stack:
            self.parent = stack[-1].name
            if self.step is None:
                self.step = stack[-1].step
        stack.append(self)
        stats = {}
        if self.step is not None:
            stats["step"] = self.step
        if self.parent is not None:
            stats["parent"] = self.parent
        # TraceAnnotation is a TraceMe under the hood: near-free when no
        # profiler session is active, and a named host event when one is —
        # exactly the "same region names in the XLA trace" contract. The
        # step and the enclosing region ride along as the event's stats.
        try:
            self._ta = jax.profiler.TraceAnnotation(self.name, **stats)
            self._ta.__enter__()
        except Exception:  # pragma: no cover - profiler backend quirks
            self._ta = None
        tl = _timeline()
        if tl is not None and tl.enabled:
            self._tl = tl
            self._begin_us = tl._now_us()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        if self._tl is not None:
            self._tl.record_event(
                self.name, self._begin_us, self._tl._now_us(),
                track=self.track, parent=self.parent,
            )
        if self._ta is not None:
            self._ta.__exit__(*exc)
        stack = _open_regions()
        if self in stack:            # and whatever was left open inside it
            del stack[stack.index(self):]
        telemetry.histogram(
            "smp_host_phase_seconds",
            "host wall time of each profiling.region() by phase name "
            "(the step engine's phases cover StepFunction.__call__)",
            buckets=HOST_PHASE_BUCKETS,
        ).labels(phase=self.name[len(REGION_PREFIX):]).observe(seconds)
        return False


def region(name, track="phase", step=None):
    """Context manager: one named host-side profiler region.

    Emits the region under ``smp_phase/<name>`` to BOTH observability
    surfaces at once: a ``jax.profiler.TraceAnnotation`` (visible in an
    XLA profiler capture) and a ``state.timeline`` span on the ``phase``
    track (visible in the fused Perfetto view; ``trace_fuse.py`` computes
    per-phase cross-rank skew from these), and observes its duration in
    the ``smp_host_phase_seconds{phase=<name>}`` histogram. Regions nest:
    each knows the region open round it on its thread (``parent``) and
    the training step it belongs to (``step``: given, else its
    parent's), and writes both into the annotation's stats and the
    timeline event. Microseconds when neither a profiler session nor the
    timeline is active.
    """
    return _Region(REGION_PREFIX + name, track, step)


def named_region(name):
    """In-graph region: a ``jax.named_scope`` wrapper. The name lands in
    the compiled HLO's op metadata (``op_name`` paths), so XLA profiler
    device timelines and HLO dumps carry the same region vocabulary as the
    host-side ``region`` spans."""
    return jax.named_scope(name)


# ----------------------------------------------------------------------
# On-demand capture (SMP_PROFILE / SIGUSR2)
# ----------------------------------------------------------------------


def _parse_profile_spec(spec):
    """``steps=N:M`` / ``steps=N`` / bare ``N:M`` -> (first, last)
    inclusive step window. Raises ValueError on anything else."""
    body = spec.strip()
    if body.startswith("steps="):
        body = body[len("steps="):]
    parts = body.split(":")
    if not body or len(parts) > 2:
        raise ValueError(f"unparseable {PROFILE_ENV} spec {spec!r}")
    first = int(parts[0])
    last = int(parts[1]) if len(parts) == 2 else first
    if first < 0 or last < first:
        raise ValueError(
            f"{PROFILE_ENV} window {spec!r} must satisfy 0 <= N <= M"
        )
    return first, last


class ProfileCapture:
    """Programmatic ``jax.profiler`` capture bracketed at step edges.

    The step engine calls ``on_step_begin(step)`` / ``on_step_end(step)``
    around every dispatch. When a window is armed (``SMP_PROFILE=
    steps=N:M`` at init, or a SIGUSR2 received on a live run — which arms
    a one-step window at the next step edge), the capture starts at the
    begin edge of step N and stops at the end edge of step M, writing the
    trace into ``<SMP_PROFILE_PATH>/rank<i>`` so multi-process runs never
    clobber each other. Disarmed, both hooks are a single attribute test.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._parsed_env = False
        self._window = None          # (first, last) inclusive, or None
        self._sig_request = False    # set by the SIGUSR2 handler
        self._installed = False
        self.active = False
        self.last_window = None      # (first, last) of the last capture
        self.last_report = None      # path of its scope_report.json
        self._started_at = None
        self._last_step = None       # most recent step edge seen
        self._forced_dir = None      # per-capture base dir override

    # -- configuration --------------------------------------------------

    def _ensure_spec(self):
        if self._parsed_env:
            return
        self._parsed_env = True
        spec = os.environ.get(PROFILE_ENV, "")
        if not spec:
            return
        try:
            self._window = _parse_profile_spec(spec)
        except ValueError as e:
            logger.warning("%s ignored: %s", PROFILE_ENV, e)

    @property
    def window(self):
        self._ensure_spec()
        return self._window

    def rank_dir(self):
        base = self._forced_dir or os.environ.get(
            PROFILE_PATH_ENV, "smp_profile"
        )
        rank = telemetry.process_index
        return os.path.join(base, f"rank{0 if rank is None else rank}")

    def request_capture(self, path=None):
        """Arm a one-step capture at the next step edge — the SIGUSR2
        path, callable in-process (auto-forensics uses it; ``path``
        overrides the SMP_PROFILE_PATH base for this capture only). Like
        the signal, it defers to a capture already running or a
        configured window still pending."""
        if path is not None and not self.active and self._window is None:
            self._forced_dir = path
        self._sig_request = True

    def install_signal(self):
        """Install the SIGUSR2 trigger (main thread only; re-entrant)."""
        if self._installed:
            return
        try:
            signal.signal(signal.SIGUSR2, self._on_sigusr2)
            self._installed = True
        except (ValueError, OSError, AttributeError) as e:
            # Non-main thread, or a platform without SIGUSR2.
            logger.debug("SIGUSR2 profile trigger unavailable: %s", e)

    def _on_sigusr2(self, signum, frame):
        # Async-signal context: only set a flag; the next step edge arms.
        self._sig_request = True

    # -- step-edge hooks (called by the step engine) --------------------

    def on_step_begin(self, step):
        self._ensure_spec()
        self._last_step = step
        if self._sig_request:
            self._sig_request = False
            if self.active or self._window is not None:
                # A capture is running or a configured window is still
                # pending — the signal must not cancel it (the armed
                # window may be the chip-window trace the run exists to
                # collect).
                logger.info(
                    "SIGUSR2 ignored: profiler capture %s.",
                    "already running" if self.active
                    else f"window {self._window} already armed",
                )
            else:
                # One-step window at the step about to run.
                self._window = (step, step)
                logger.info(
                    "SIGUSR2: profiler capture armed for step %d.", step
                )
        win = self._window
        if win is None or self.active or not (win[0] <= step <= win[1]):
            return
        with self._lock:
            if self.active:
                return
            t0 = time.perf_counter()
            path = self.rank_dir()
            try:
                os.makedirs(path, exist_ok=True)
                jax.profiler.start_trace(path)
            except Exception as e:
                logger.warning(
                    "profiler capture start failed (%s); window disarmed.", e
                )
                self._window = None
                self._forced_dir = None
                return
            self.active = True
            self._started_at = step
            self._record_overhead(time.perf_counter() - t0)
            telemetry.gauge(
                "smp_profile_active", "1 while a profiler capture is running"
            ).set(1)
            logger.info(
                "profiler capture started at step %d (window %d..%d) -> %s",
                step, win[0], win[1], path,
            )

    def on_step_end(self, step, outputs=None):
        if not self.active:
            return
        win = self._window
        if win is not None and step < win[1]:
            return
        # Make the captured window actually contain this step's device
        # execution (dispatch is async): block before stopping the trace.
        if outputs is not None:
            try:
                jax.block_until_ready(outputs)
            except Exception:  # pragma: no cover - donated/consumed buffers
                pass
        self._stop(step)

    def _stop(self, step):
        with self._lock:
            if not self.active:
                return
            t0 = time.perf_counter()
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # pragma: no cover
                logger.warning("profiler capture stop failed: %s", e)
            self.active = False
            first = self._started_at if self._started_at is not None else step
            self.last_window = (first, step)
            self._window = None       # window consumed; SIGUSR2 can re-arm
            self._record_overhead(time.perf_counter() - t0)
            telemetry.gauge(
                "smp_profile_active", "1 while a profiler capture is running"
            ).set(0)
            telemetry.counter(
                "smp_profile_captures_total", "completed profiler captures"
            ).inc()
            telemetry.gauge(
                "smp_profile_last_first_step",
                "first step of the last profiler capture",
            ).set(first)
            telemetry.gauge(
                "smp_profile_last_last_step",
                "last step of the last profiler capture",
            ).set(step)
            logger.info(
                "profiler capture stopped: steps %d..%d -> %s",
                first, step, self.rank_dir(),
            )
            # The window's own report: after stop_trace, outside any
            # step, charged to the capture's overhead like its start/stop.
            t0 = time.perf_counter()
            try:
                self.last_report = write_scope_report(
                    self.rank_dir(), window=(first, step))
            except Exception as e:  # a report must never fail the run
                logger.warning("scope report of the capture failed: %s", e)
            self._record_overhead(time.perf_counter() - t0)
            self._forced_dir = None

    @staticmethod
    def _record_overhead(seconds):
        telemetry.counter(
            "smp_profile_overhead_seconds_total",
            "host seconds spent starting/stopping profiler captures "
            "(zero unless a capture ran)",
        ).inc(seconds)

    def stop_if_active(self):
        """Shutdown/atexit hook: a run that ends mid-window still gets a
        usable trace rather than a torn session. The recorded window ends
        at the last step edge this capture actually saw."""
        if self.active:
            last = self._last_step
            if last is None:
                last = self._started_at if self._started_at is not None else -1
            self._stop(last)

    def reset(self):
        """Testing hook: stop any live capture and re-read the env."""
        self.stop_if_active()
        self._parsed_env = False
        self._window = None
        self._sig_request = False
        self.last_window = None
        self.last_report = None
        self._started_at = None
        self._last_step = None
        self._forced_dir = None


capture = ProfileCapture()
atexit.register(capture.stop_if_active)


# ----------------------------------------------------------------------
# A capture's own report: device time by scope (scope_report.json)
# ----------------------------------------------------------------------

REPORT_NAME = "scope_report.json"
_OP_LINE = "XLA Ops"


def _self_seconds(events):
    """``{name: seconds}`` of one line's ``(name, start_ns, dur_ns)``
    events, each charged its duration less what the events nested inside
    it cover (a ``while`` is left what its body does not take), so the
    values sum to the line's busy time."""
    totals = {}
    stack = []          # [end, name, self_ns]

    def close(done):
        totals[done[1]] = totals.get(done[1], 0) + done[2]

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(dur, stack[-1][0] - start)
        stack.append([start + dur, name, dur])
    for done in stack:
        close(done)
    return {name: ns / 1e9 for name, ns in totals.items()}


def device_op_seconds(xplane_path):
    """``(plane name, {HLO instruction name: self seconds})`` of the first
    device in a profiler trace (``.xplane.pb``): the op line of the
    lowest-numbered ``/device:`` plane, as the device's own clock timed
    it. A CPU run has no device plane; there the host plane's events that
    carry an ``hlo_op`` stat stand in (the CPU client's threads), so the
    join can be tried without a chip. ``(None, {})`` when neither is
    there."""
    from jax.profiler import ProfileData

    def short(full):
        # A device op's event name is its whole instruction text.
        return full.split(" = ", 1)[0].lstrip("%")

    def ordinal(plane):
        tail = plane.name.rsplit(":", 1)[-1].split()
        return int(tail[0]) if tail and tail[0].isdigit() else 0

    planes = list(ProfileData.from_file(xplane_path).planes)
    for plane in sorted((p for p in planes if p.name.startswith("/device:")),
                        key=ordinal):
        for line in plane.lines:
            if line.name == _OP_LINE:
                return plane.name, _self_seconds(
                    (short(ev.name), int(ev.start_ns), int(ev.duration_ns))
                    for ev in line.events)
    seconds = {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ops = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events
                   if any(key == "hlo_op" for key, _ in ev.stats)]
            for name, s in _self_seconds(ops).items():
                seconds[name] = seconds.get(name, 0.0) + s
        if seconds:
            return plane.name, seconds
    return None, {}


def newest_xplane(trace_dir):
    """The newest ``.xplane.pb`` a capture wrote under ``trace_dir``."""
    import glob

    found = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def scope_report(xplane_path, program=None):
    """``hlo_audit.seconds_by_scope`` of a trace's first device as plain
    data (``tree`` as rows ``{"path", "seconds"}``, largest first), with
    the plane and program it joined; ``None`` where the trace holds no
    device ops or no step program was audited (``SMP_HLO_AUDIT=off``)."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    if program is None:
        program = hlo_audit.step_program()
    plane, seconds = device_op_seconds(xplane_path)
    joined = hlo_audit.seconds_by_scope(seconds, program) if seconds else None
    if joined is None:
        return None
    tree = joined.pop("tree")
    joined["unscoped"]["near"] = [
        {"path": list(path), "seconds": s} for path, s in sorted(
            joined["unscoped"]["near"].items(), key=lambda kv: -kv[1])]
    return {
        "version": 1, "trace": os.path.basename(xplane_path),
        "device": plane, "program": program, **joined,
        "tree": [{"path": list(path), "seconds": s} for path, s in
                 sorted(tree.items(), key=lambda kv: -kv[1])],
    }


def scope_table(report):
    """The first two levels of a report's tree as text: one row a scope
    path, its seconds (subtree) and share of busy, then the seconds under
    no scope and the user's own."""
    busy = report["busy_s"] or 1.0
    levels = {}
    for row in report["tree"]:
        for n in range(1, min(2, len(row["path"])) + 1):
            key = tuple(row["path"][:n])
            levels[key] = levels.get(key, 0.0) + row["seconds"]
    rows = [("  " * (len(path) - 1) + path[-1], s)
            for path, s in sorted(levels.items())]
    rows += [("(no scope)", report["unscoped"]["seconds"]),
             ("(user's own code)", report["user_only_s"])]
    width = max(len(name) for name, _ in rows)
    return "\n".join(
        f"{name:<{width}}  {s:10.4f} s  {100.0 * s / busy:6.2f} %"
        for name, s in rows)


def write_scope_report(trace_dir, window=None):
    """Reduce the newest trace under ``trace_dir`` (``scope_report``),
    write ``scope_report.json`` beside its ``.xplane.pb`` and log the
    tree's first two levels. Returns the file's path, or ``None`` where
    there was nothing to report."""
    t0 = time.perf_counter()
    path = newest_xplane(trace_dir)
    report = scope_report(path) if path else None
    if report is None:
        return None
    report["window"] = list(window) if window else None
    report["reduce_seconds"] = time.perf_counter() - t0
    out = os.path.join(os.path.dirname(path), REPORT_NAME)
    with open(out, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=1)
    logger.info(
        "device time by scope, %s of program %s, steps %s (%.3f s busy) "
        "-> %s\n%s", report["device"], report["program"], report["window"],
        report["busy_s"], out, scope_table(report))
    return out


# ----------------------------------------------------------------------
# Roofline / MFU attribution
# ----------------------------------------------------------------------

# Peak dense bf16 TFLOP/s and HBM GB/s per chip, by device_kind fragment
# (public spec sheets), read through device_peaks.
_PEAK_TFLOPS = (
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5 lite", 197.0),
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 46.0),
)
_PEAK_GBPS = (
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5 lite", 819.0),
    ("v5e", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)

_DEVICE_KIND_CACHE = []  # [kind] once resolved (jax.devices() is sticky)


def _env_float(name):
    raw = os.environ.get(name, "")
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        logger.warning("invalid %s=%r (want a number); ignored.", name, raw)
        return None


def _device_kind(device):
    if device is not None:
        return getattr(device, "device_kind", "").lower()
    if not _DEVICE_KIND_CACHE:
        try:
            _DEVICE_KIND_CACHE.append(
                getattr(jax.devices()[0], "device_kind", "").lower()
            )
        except Exception:  # pragma: no cover - backend bring-up failure
            _DEVICE_KIND_CACHE.append("")
    return _DEVICE_KIND_CACHE[0]


def device_peaks(device=None):
    """(peak FLOP/s, peak bytes/s) for the attribution denominator.

    ``SMP_PEAK_TFLOPS`` / ``SMP_PEAK_GBPS`` override (required on
    backends the spec table does not know, e.g. the CPU test mesh);
    otherwise looked up by ``device_kind``. Unknown entries are None —
    callers must treat MFU as unavailable rather than fabricate one.
    """
    flops = _env_float(PEAK_TFLOPS_ENV)
    flops = flops * 1e12 if flops is not None else None
    bps = _env_float(PEAK_GBPS_ENV)
    bps = bps * 1e9 if bps is not None else None
    if flops is None or bps is None:
        kind = _device_kind(device)
        if flops is None:
            for frag, v in _PEAK_TFLOPS:
                if frag in kind:
                    flops = v * 1e12
                    break
        if bps is None:
            for frag, v in _PEAK_GBPS:
                if frag in kind:
                    bps = v * 1e9
                    break
    return flops, bps


def cost_of(compiled):
    """(flops, bytes_accessed) from a compiled executable's
    ``cost_analysis`` — (None, None) when the backend won't say."""
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0] if cost else {}
        flops = cost.get("flops")
        nbytes = cost.get("bytes accessed")
        return (
            float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None,
        )
    except Exception:
        return None, None


class RooflineReport:
    """One step program's roofline attribution (plain attributes +
    ``as_dict``). ``None`` fields mean "not attributable" (unknown peak,
    missing cost analysis), never a guess."""

    def __init__(self, **kw):
        self.name = kw.get("name")
        self.step_time_s = kw.get("step_time_s")
        self.flops = kw.get("flops")
        self.bytes_accessed = kw.get("bytes_accessed")
        self.peak_flops_per_s = kw.get("peak_flops_per_s")
        self.peak_bytes_per_s = kw.get("peak_bytes_per_s")
        self.mfu = kw.get("mfu")
        self.achieved_flops_per_s = kw.get("achieved_flops_per_s")
        self.achieved_bytes_per_s = kw.get("achieved_bytes_per_s")
        self.arithmetic_intensity = kw.get("arithmetic_intensity")
        self.ridge_intensity = kw.get("ridge_intensity")
        self.bound = kw.get("bound")        # "compute" | "memory" | None
        self.compute_s = kw.get("compute_s")
        self.memory_s = kw.get("memory_s")
        self.bubble_fraction = kw.get("bubble_fraction")
        self.bubble_s = kw.get("bubble_s")
        self.comm_s = kw.get("comm_s")

    def as_dict(self):
        return {
            k: getattr(self, k)
            for k in (
                "name", "step_time_s", "flops", "bytes_accessed",
                "peak_flops_per_s", "peak_bytes_per_s", "mfu",
                "achieved_flops_per_s", "achieved_bytes_per_s",
                "arithmetic_intensity", "ridge_intensity", "bound",
                "compute_s", "memory_s", "bubble_fraction", "bubble_s",
                "comm_s",
            )
        }


def _live_gauge_max(name):
    """Max value across a live gauge family's series (None when absent)."""
    fam = telemetry._families.get(name)
    if fam is None:
        return None
    with fam._lock:
        children = list(fam._children.values())
    return max((c.value for c in children), default=None)


def roofline(name="step", *, step_time_s, flops=None, bytes_accessed=None,
             compiled=None, bubble_fraction=None, device=None,
             peak_flops=None, peak_bytes_per_s=None, publish=True):
    """Join program cost with measured wall time into a roofline report.

    Args:
      name: label for the published gauges (``step=<name>``).
      step_time_s: measured wall time of one step of this program.
      flops / bytes_accessed: explicit program cost; missing pieces are
        filled from ``compiled.cost_analysis()`` when given.
      compiled: a compiled executable (``jax.jit(...).lower().compile()``
        or the step runner's AOT executable).
      bubble_fraction: pipeline idle fraction; defaults to the live
        ``smp_pipeline_bubble_fraction`` gauge (0.0 when no pipeline).
      device / peak_flops / peak_bytes_per_s: attribution denominators;
        default to ``device_peaks`` (spec table + the peak env overrides).
      publish: set the ``smp_mfu`` / ``smp_roofline_*`` gauges.

    Decomposition (published per label): ``compute_s`` is the ideal
    compute-bound time ``flops / peak_flops``; ``bubble_s`` is
    ``bubble_fraction * step_time``; ``comm_s`` is the residual — time
    the roofline model cannot attribute to ideal compute or schedule
    bubble (collectives, memory-bound stalls, host overhead).
    ``memory_s`` (``bytes / peak_bw``) is reported alongside as the
    bandwidth bound.
    """
    if compiled is not None and (flops is None or bytes_accessed is None):
        c_flops, c_bytes = cost_of(compiled)
        flops = flops if flops is not None else c_flops
        bytes_accessed = (
            bytes_accessed if bytes_accessed is not None else c_bytes
        )
    if peak_flops is None or peak_bytes_per_s is None:
        d_flops, d_bps = device_peaks(device)
        peak_flops = peak_flops if peak_flops is not None else d_flops
        peak_bytes_per_s = (
            peak_bytes_per_s if peak_bytes_per_s is not None else d_bps
        )
    if bubble_fraction is None:
        bubble_fraction = _live_gauge_max("smp_pipeline_bubble_fraction")
        bubble_fraction = 0.0 if bubble_fraction is None else bubble_fraction

    dt = float(step_time_s) if step_time_s else None
    achieved_f = flops / dt if (flops is not None and dt) else None
    achieved_b = bytes_accessed / dt if (bytes_accessed is not None and dt) else None
    mfu = (
        achieved_f / peak_flops
        if (achieved_f is not None and peak_flops) else None
    )
    ai = (
        flops / bytes_accessed
        if (flops is not None and bytes_accessed) else None
    )
    ridge = (
        peak_flops / peak_bytes_per_s
        if (peak_flops and peak_bytes_per_s) else None
    )
    bound = None
    if ai is not None and ridge is not None:
        bound = "compute" if ai >= ridge else "memory"
    compute_s = flops / peak_flops if (flops is not None and peak_flops) else None
    memory_s = (
        bytes_accessed / peak_bytes_per_s
        if (bytes_accessed is not None and peak_bytes_per_s) else None
    )
    bubble_s = bubble_fraction * dt if dt is not None else None
    comm_s = None
    if dt is not None and compute_s is not None and bubble_s is not None:
        comm_s = max(dt - compute_s - bubble_s, 0.0)

    report = RooflineReport(
        name=name, step_time_s=dt, flops=flops,
        bytes_accessed=bytes_accessed, peak_flops_per_s=peak_flops,
        peak_bytes_per_s=peak_bytes_per_s, mfu=mfu,
        achieved_flops_per_s=achieved_f, achieved_bytes_per_s=achieved_b,
        arithmetic_intensity=ai, ridge_intensity=ridge, bound=bound,
        compute_s=compute_s, memory_s=memory_s,
        bubble_fraction=bubble_fraction, bubble_s=bubble_s, comm_s=comm_s,
    )
    if publish:
        _publish(report)
    return report


def _publish(r):
    lab = dict(step=r.name)
    for value, metric, help_ in (
        (r.mfu, "smp_mfu",
         "model FLOPs utilization of the last measured step"),
        (r.flops, "smp_roofline_flops",
         "program FLOPs joined into the roofline report"),
        (r.bytes_accessed, "smp_roofline_bytes",
         "program bytes accessed joined into the roofline report"),
        (r.step_time_s, "smp_roofline_step_seconds",
         "measured step wall time of the roofline report"),
        (r.achieved_flops_per_s, "smp_roofline_achieved_flops_per_s",
         "achieved FLOP/s of the last measured step"),
        (r.achieved_bytes_per_s, "smp_roofline_achieved_bytes_per_s",
         "achieved HBM bytes/s of the last measured step"),
        (r.arithmetic_intensity, "smp_roofline_arithmetic_intensity",
         "program FLOPs per byte accessed"),
        (r.ridge_intensity, "smp_roofline_ridge_intensity",
         "device ridge point (peak FLOP/s / peak bytes/s)"),
        (r.compute_s, "smp_roofline_compute_seconds",
         "ideal compute-bound time (flops / peak FLOP/s)"),
        (r.memory_s, "smp_roofline_memory_seconds",
         "ideal bandwidth-bound time (bytes / peak bytes/s)"),
        (r.bubble_s, "smp_roofline_bubble_seconds",
         "pipeline-bubble share of the step time"),
        (r.comm_s, "smp_roofline_comm_seconds",
         "residual step time not attributed to ideal compute or bubble "
         "(collectives, memory stalls, host overhead)"),
        (r.peak_flops_per_s, "smp_roofline_peak_flops_per_s",
         "peak FLOP/s used as the MFU denominator"),
        (r.peak_bytes_per_s, "smp_roofline_peak_bytes_per_s",
         "peak bytes/s used as the bandwidth denominator"),
    ):
        if value is not None:
            telemetry.gauge(metric, help_).labels(**lab).set(float(value))
    if r.bound is not None:
        telemetry.gauge(
            "smp_roofline_compute_bound",
            "1 when arithmetic intensity sits above the ridge point",
        ).labels(**lab).set(1.0 if r.bound == "compute" else 0.0)
