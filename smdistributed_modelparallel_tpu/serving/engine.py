"""Continuous-batching serving engine (``smp.serving``).

``smp.generate`` is a one-shot compiled program per (batch, prompt-len,
max-new-tokens) shape: no request queue, no cache reuse across requests,
and every ragged shape churns the program cache. This engine is the
serving tier the ROADMAP's "millions of users, heavy traffic" north star
asks for, built on three pieces:

**Paged KV cache.** One pool of fixed-size token blocks per layer
(``SMP_KV_BLOCK_TOKENS`` tokens each; ``nn/utils.PagedKVCache``), shared
by every in-flight sequence through per-sequence block tables that a
host-side allocator (``serving/kv_cache.BlockAllocator``) maintains.
Sequences of wildly different lengths share the pool, and a finished
sequence's blocks are reusable the moment it completes — no
[slots, max_len] worst-case rectangle.

**Continuous batching.** Requests queue; at every engine tick the
scheduler admits arrivals into free decode slots, runs ONE batched
decode step over every in-flight stream, and runs ONE prefill slice
(``SMP_PREFILL_CHUNK`` prompt tokens) of at most one admitting request —
chunked prefill interleaves with decode so a long prompt never stalls
the streams already flowing. Exactly TWO programs compile for the whole
workload (a bucket-keyed prefill-chunk and a decode-step), AOT-lowered
through ``exec_cache.aot_compile`` so the PR-11 persistent cache
warm-starts them and the PR-9 X-ray audits them (including the serving-
specific replicated-KV-pool detector).

**SLO observability.** Every latency the SLOs care about — queue wait,
TTFT, ITL, prefill wall, decode-step wall — streams into log-bucketed
histograms (``utils/telemetry.record_serve_latency``) with p50/p90/p99
gauges; queue depth and KV-pool occupancy are gauges; windowed rates
(req/s, tok/s over the last ``SMP_TIMESERIES_INTERVAL`` window, not
lifetime averages) come from the metrics time-series snapshotter
(``utils/timeseries.MetricsTimeSeries`` — the autoscaler feed, with
``SMP_SLO`` verdicts per window). Each request also carries a trace id
through queued → admitted → prefill chunk → first token → finished as
flight-recorder events, fused by ``scripts/trace_fuse.py`` into one
Perfetto span lane per decode slot. All timestamps are host-side reads
taken after the device call returns — tracing adds no per-token device
sync. Per-request logs (prompt + sampled tokens) are retained while a
request is in flight, which is what makes requests RESTARTABLE — the
replica-failover layer (``serving/replica.py``) re-admits a dead
replica's unfinished requests from its mirrored logs (trace id
included, so the resumed stream continues the same trace), idempotent
by request id.

Sampling parity contract: a request served here produces token-for-token
what ``smp.generate`` produces for the same prompt at batch size 1 with
``rng=jax.random.key(seed)`` — same key schedule
(``split(key, max_new_tokens)``), same filter composition (temperature,
then top-k, then top-p), same greedy argmax — across the paged vs
contiguous cache layouts (asserted in ``tests/test_serving.py``).

Model support: the ``TransformerLM`` zoo family (the paged decode path
is threaded through ``models/transformer_lm.py``); other families keep
``smp.generate``.
"""

import collections
import dataclasses
import time
from typing import Optional, Sequence, Tuple

import numpy as np

from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.resilience.chaos import chaos
from smdistributed_modelparallel_tpu.serving.kv_cache import (
    TRASH_BLOCK,
    BlockAllocator,
    block_tokens,
    prefill_chunk_tokens,
    serve_slots,
)
from smdistributed_modelparallel_tpu.utils import exec_cache, profiling
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import (
    record_quant_dispatch,
    record_serve_latency,
    record_serve_occupancy,
    record_serve_programs,
    record_serve_request,
    record_serve_tokens,
    record_serve_trace,
    record_weight_update,
)
from smdistributed_modelparallel_tpu.utils.fleet import fleet
from smdistributed_modelparallel_tpu.utils.timeseries import (
    MetricsTimeSeries,
)

logger = get_logger()


@dataclasses.dataclass
class ServeRequest:
    """One generation request.

    ``seed`` fixes the sampling key schedule
    (``jax.random.split(jax.random.key(seed), max_new_tokens)`` — the
    exact schedule ``smp.generate`` uses, so serving output is
    reproducible and restartable). ``arrival_s`` is the request's arrival
    offset relative to the engine's start (synthetic traces); the
    scheduler never admits a request before it "arrives".
    ``resume_tokens`` carries already-sampled tokens when a failover
    re-admits a dead replica's in-flight request: the engine prefills
    prompt+resume and continues the key schedule at index
    ``len(resume_tokens)``, reproducing the exact tokens the dead replica
    would have produced. ``trace_id`` names the request's span trace in
    the flight-recorder ring (defaults to the request id at submit);
    failover re-admission carries the original id through the mirror
    log, so the resumed stream continues the SAME trace on the
    surviving replica.
    """

    request_id: str
    prompt: Sequence[int]
    max_new_tokens: int
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    eos_token_id: Optional[int] = None
    seed: int = 0
    arrival_s: float = 0.0
    deadline_s: Optional[float] = None
    resume_tokens: Tuple[int, ...] = ()
    trace_id: Optional[str] = None


def serve_request_from_record(rec):
    """Rebuild a restartable ``ServeRequest`` from a mirror-log record
    (the wire format ``_mirror`` writes). Used by replica failover and
    by the controller's drain protocol: the already-sampled tokens ride
    as ``resume_tokens`` so the re-admitting engine continues the key
    schedule exactly where the record left off, and the original trace
    id rides along so the fused timeline shows ONE request."""
    return ServeRequest(
        request_id=rec["rid"],
        prompt=rec["prompt"],
        max_new_tokens=rec["max_new_tokens"],
        temperature=rec.get("temperature", 0.0),
        top_k=rec.get("top_k"),
        top_p=rec.get("top_p"),
        eos_token_id=rec.get("eos_token_id"),
        seed=rec.get("seed", 0),
        deadline_s=rec.get("deadline_s"),
        resume_tokens=tuple(rec.get("tokens", ())),
        trace_id=rec.get("trace_id"),
    )


def serve_request_to_record(req):
    """Inverse of ``serve_request_from_record``: serialize a
    ``ServeRequest`` into the mirror-record wire format so the router
    can ship it to a remote replica as plain JSON."""
    return {
        "rid": req.request_id,
        "prompt": list(map(int, req.prompt)),
        "max_new_tokens": int(req.max_new_tokens),
        "temperature": req.temperature,
        "top_k": req.top_k,
        "top_p": req.top_p,
        "eos_token_id": req.eos_token_id,
        "seed": req.seed,
        "deadline_s": req.deadline_s,
        "tokens": list(map(int, req.resume_tokens)),
        "done": False,
        "trace_id": req.trace_id,
    }


class _Slot:
    __slots__ = (
        "req", "sid", "idx", "prompt_full", "resume_len", "pos",
        "new_tokens", "state", "rng_data", "t_arrival", "t_admit",
        "t_first_token", "t_last_token",
    )

    def __init__(self, req, rng_data, t_arrival, t_admit, idx):
        self.req = req
        self.sid = req.request_id
        self.idx = idx                   # decode-slot index (trace lane)
        self.prompt_full = list(map(int, req.prompt)) + list(
            map(int, req.resume_tokens)
        )
        self.resume_len = len(req.resume_tokens)
        self.pos = 0                     # tokens cached so far
        self.new_tokens = []             # sampled THIS incarnation
        self.state = "prefill"
        self.rng_data = rng_data         # [max_new, 2] uint32
        self.t_arrival = t_arrival
        self.t_admit = t_admit
        self.t_first_token = None
        self.t_last_token = None

    @property
    def sample_index(self):
        """Index into the request's key schedule for the NEXT sample."""
        return self.resume_len + len(self.new_tokens)

    @property
    def remaining(self):
        return self.req.max_new_tokens - self.sample_index

    @property
    def total_tokens(self):
        """Worst-case sequence length at completion."""
        return len(self.req.prompt) + self.req.max_new_tokens

    @property
    def all_tokens(self):
        return list(self.req.resume_tokens) + self.new_tokens


def _sample_rows(logits, temps, top_ks, top_ps, key_data):
    """Per-row sampler over [B, V] fp32 logits with traced per-row
    sampling parameters (one compiled program serves every request mix).

    Composition mirrors ``generation._make_sampler`` exactly —
    temperature scale, then top-k, then top-p on the k-filtered logits,
    then ``jax.random.categorical`` on a [1, V] row — so a single-request
    stream is token-for-token identical to ``smp.generate`` at batch 1.
    ``top_ks <= 0`` and ``top_ps >= 1`` disable the filters;
    ``temps <= 0`` is greedy argmax (keys unused).
    """
    import jax
    import jax.numpy as jnp

    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)

    def stochastic(_):
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sorted_desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        k_idx = jnp.clip(top_ks, 1, V) - 1
        kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
        keep_k = (top_ks[:, None] <= 0) | (scaled >= kth)
        filtered = jnp.where(keep_k, scaled, -jnp.inf)
        sorted_p = jnp.sort(filtered, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_p, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_p = (cum - probs) < top_ps[:, None]
        thresh = jnp.min(
            jnp.where(keep_p, sorted_p, jnp.inf), axis=-1, keepdims=True
        )
        filtered = jnp.where(filtered >= thresh, filtered, -jnp.inf)

        def row(kd, lg):
            key = jax.random.wrap_key_data(kd)
            return jax.random.categorical(key, lg[None, :], axis=-1)[0]

        return jax.vmap(row)(key_data, filtered)

    # All-greedy batches (the serving default) skip the two full-vocab
    # sorts + softmax/cumsum at runtime — still ONE compiled program.
    sampled = jax.lax.cond(
        jnp.all(temps <= 0.0), lambda _: greedy, stochastic, None
    )
    return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)


class ServingEngine:
    """Continuous-batching serving engine over a paged KV cache.

    Args:
      model: a ``TransformerLM`` (zoo family) module, or a
        ``DistributedModel`` wrapping one (pp-trained stacks regather for
        decode exactly like ``smp.generate``).
      params: parameter tree override (required for a raw module unless
        it was initialized through a ``DistributedModel``).
      max_slots: concurrent decode streams (default ``SMP_SERVE_SLOTS``).
      num_blocks: KV-pool size in blocks, INCLUDING the reserved trash
        block. Default fully provisions ``max_slots`` worst-case
        sequences; size it below that to let paging earn its keep —
        admission then waits for free blocks instead of OOMing.
      block_tokens / prefill_chunk: geometry overrides (default
        ``SMP_KV_BLOCK_TOKENS`` / ``SMP_PREFILL_CHUNK``).
    """

    def __init__(self, model, params=None, *, max_slots=None,
                 num_blocks=None, block_tokens_override=None,
                 prefill_chunk=None):
        import jax

        if hasattr(model, "module"):  # DistributedModel
            module = model.module
            if params is None:
                pp_active = (
                    state.cfg is not None
                    and state.cfg.pipeline_parallel_degree > 1
                )
                params = (
                    model.regather_for_decode() if pp_active
                    else model.params
                )
        else:
            module = model
        if params is None:
            raise SMPValidationError(
                "ServingEngine(module, ...) requires params=... (or pass "
                "an initialized DistributedModel)."
            )
        if "paged_blocks" not in getattr(module, "__dataclass_fields__", {}):
            raise SMPValidationError(
                f"{type(module).__name__} does not support paged decoding;"
                " smp.serving drives the TransformerLM zoo family (other "
                "families keep smp.generate)."
            )
        self.module = module
        from smdistributed_modelparallel_tpu import quant as quant_mod

        # SMP_DECODE_WEIGHTS=int8: weight-only quantization, applied ONCE
        # here (and at adopt_params) — the resident tree is int8 + per-
        # output-channel scales; programs dequantize on the way in.
        self._wq = quant_mod.decode_weights_mode() == "int8"
        if self._wq:
            params = quant_mod.quantize_decode_params(params)
            record_quant_dispatch("decode_weights", "int8")
        if quant_mod.kv_quant_mode() == "int8":
            record_quant_dispatch("kv_cache", "int8")
        self.params = params
        self.max_len = int(module.max_len)
        self.bt = int(block_tokens_override or block_tokens())
        self.chunk = int(prefill_chunk or prefill_chunk_tokens())
        self.slots_n = int(max_slots or serve_slots())
        self.max_blocks_per_seq = -(-self.max_len // self.bt)
        if num_blocks is None:
            num_blocks = 1 + self.slots_n * self.max_blocks_per_seq
        self.alloc = BlockAllocator(
            int(num_blocks), self.bt, self.max_blocks_per_seq
        )
        self.half = state.cfg.half_dtype if state.cfg is not None else None
        self.decode_mod = module.clone(
            paged_blocks=int(num_blocks), paged_block_tokens=self.bt,
            deterministic=True, decode=False, decode_cache_len=None,
        )
        self._mesh = state.mesh if state.initialized else None
        if self._mesh is not None:
            me = jax.process_index()
            if any(
                d.process_index != me for d in self._mesh.devices.flat
            ):
                # Multi-process world: serving runs dp-REPLICATED — each
                # replica compiles process-local programs (a cross-process
                # mesh would lockstep every replica into one collective
                # program, defeating independent streams and failover).
                self._mesh = None
        self._slots = [None] * self.slots_n
        self._queue = collections.deque()
        self._prefill_rr = 0
        self.results = {}
        self.finished = set()
        self._arrival_s = {}     # rid -> effective arrival (engine clock)
        self._occupancy_snap = None
        self.last_tick_worked = True
        self.mirror_log = {}     # rid -> restartable record (failover)
        self._dirty = set()      # rids with unmirrored progress
        self._admit_order = []   # rids in admission order (chaos seam)
        self._programs = {}
        self.audits = {}         # program kind -> ProgramAudit | None
        self._admitting = True   # drain protocol: False = quiesced
        self.weights_version = 0  # bumped by adopt_params (live updates)
        self.stats = collections.Counter()
        self._t0 = None
        self._gen_tokens = 0
        self._cache = self._init_cache()
        # Per-block KV bytes, summed over every cache leaf keyed by pool
        # block (all layers' K/V pools + any int8 scale sidecars) — the
        # multiplier behind the smp_serve_kv_bytes gauges, so the pool-
        # bytes halving under SMP_KV_QUANT=int8 is observable, not
        # inferred.
        nb = self.alloc.num_blocks
        self.kv_block_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(self._cache)
            if nb in getattr(leaf, "shape", ())
        ) // nb
        # The chips this engine runs on: its mesh's, or the one default
        # device of a process-local engine — not every chip of the host.
        self._chips = self._mesh.devices.size if self._mesh is not None else 1
        # Metrics time-series snapshotter (the autoscaler feed):
        # SMP_TIMESERIES_INTERVAL=0 (the default) constructs NOTHING —
        # no ring, no thread. When armed, the engine also polls it from
        # the tick path so window edges stay sharp while the loop is
        # busy; the thread only covers idle gaps.
        self.timeseries = MetricsTimeSeries.from_env(chips=self._chips)
        if self.timeseries is not None:
            self.timeseries.start()

    def close(self):
        """Stop the time-series snapshotter thread, if armed, and stop
        admitting. Idempotent; the engine remains usable for draining
        (sampling continues via tick polling).

        A close with work still queued or in flight must not silently
        abandon it: every unfinished request's restartable record is
        re-marked dirty so the replica layer's next ``drain_dirty`` ships
        a final mirror frame — a peer can re-admit what this engine never
        served — and the abandonment is counted
        (``smp_serve_requests_total{event="abandoned"}``)."""
        self.quiesce()
        abandoned = [q.request_id for q in self._queue] + [
            s.sid for s in self._slots if s is not None
        ]
        for rid in abandoned:
            if rid in self.mirror_log:
                self._dirty.add(rid)
            record_serve_trace("abandoned", rid, detail="close")
        if abandoned:
            record_serve_request("abandoned", len(abandoned))
            logger.warning(
                "[serving] close() with %d unfinished request(s); their "
                "restartable records are mirror-logged for re-admission "
                "elsewhere.", len(abandoned),
            )
        if self.timeseries is not None:
            self.timeseries.stop()

    # -- drain protocol (scale-down / weight adoption / clean close) ----

    @property
    def in_flight(self):
        """Admitted, unfinished streams (excludes the queue)."""
        return sum(1 for s in self._slots if s is not None)

    def quiesce(self):
        """Stop admission: queued requests stay queued, in-flight streams
        keep decoding. ``submit`` refuses new work while quiesced (the
        router must not route to a draining replica). Idempotent."""
        if self._admitting:
            self._admitting = False
            record_serve_trace("quiesce", "-", detail="admission stopped")

    def resume_admission(self):
        """Reopen admission after a quiesce/drain (weight adoption and
        canary flows drain to idle, adopt, then resume)."""
        if not self._admitting:
            self._admitting = True
            record_serve_trace("resume_admission", "-")

    def drain(self, timeout_s=120.0):
        """The scale-down drain protocol: stop admitting, finish every
        IN-FLIGHT stream to completion, and hand back the queued-but-
        never-admitted requests as restartable straggler records for
        re-admission elsewhere (router/controller re-route them; submit
        idempotency guarantees zero duplicated tokens, the finished
        streams guarantee zero dropped ones).

        Returns the list of straggler mirror records (possibly empty).
        The engine stays usable afterwards — ``resume_admission()``
        reopens intake."""
        self.quiesce()
        stragglers = []
        while self._queue:
            req = self._queue.popleft()
            self._arrival_s.pop(req.request_id, None)
            rec = self.mirror_log.get(req.request_id)
            if rec is None:  # pragma: no cover - submit always mirrors
                self._mirror(req, list(req.resume_tokens), done=False)
                rec = self.mirror_log[req.request_id]
            stragglers.append(dict(rec, tokens=list(rec["tokens"])))
            self._dirty.add(req.request_id)
            record_serve_trace(
                "drained_straggler", req.request_id, trace=req.trace_id,
            )
        if stragglers:
            record_serve_request("drained_straggler", len(stragglers))
        deadline = time.monotonic() + timeout_s
        while self.in_flight:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"drain exceeded {timeout_s:g}s with "
                    f"{self.in_flight} stream(s) still in flight."
                )
            self.step()
            if not self.last_tick_worked:
                time.sleep(0.001)
        record_serve_trace(
            "drained", "-", detail=f"stragglers={len(stragglers)}",
        )
        return stragglers

    def adopt_params(self, params, *, version=None):
        """Live weight update: swap the parameter tree between ticks with
        ZERO recompile. The compiled programs take params as a call
        argument and their cache keys are weight-free (shapes, knobs,
        topology — never values), so adoption is a pointer swap; the
        compile-event ledger proves it (``compile_fresh`` must stay flat
        across the adoption — asserted in tests, gated by
        ``smp_weight_update_seconds``).

        Streams must not be mid-flight (their KV holds the OLD weights'
        activations): quiesce + drain to idle first — queued requests are
        fine, they prefill under the new weights. Raises on a tree whose
        structure/shapes/dtypes differ from the serving programs' avals
        (that WOULD recompile; re-shard the checkpoint instead)."""
        import jax

        if self.in_flight:
            raise SMPValidationError(
                f"adopt_params with {self.in_flight} stream(s) in flight "
                "would mix weights mid-stream; quiesce() and drain to "
                "idle first."
            )
        t0 = time.perf_counter()
        mark = exec_cache.compile_event_mark()
        new_version = (
            int(version) if version is not None else self.weights_version + 1
        )
        params = chaos.on_weight_update(new_version, params)
        if self._wq:
            # Quantize BEFORE the aval comparison: the resident tree is
            # the quantized layout, so like compares with like and the
            # compiled programs' input avals stay satisfied.
            from smdistributed_modelparallel_tpu import quant as quant_mod

            params = quant_mod.quantize_decode_params(params)
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params)
        new_leaves, new_def = jax.tree_util.tree_flatten(params)
        if old_def != new_def or [
            (getattr(a, "shape", None), getattr(a, "dtype", None))
            for a in old_leaves
        ] != [
            (getattr(a, "shape", None), getattr(a, "dtype", None))
            for a in new_leaves
        ]:
            raise SMPValidationError(
                "adopt_params: the new checkpoint's tree does not match "
                "the serving programs' parameter avals (structure/shape/"
                "dtype) — adopting it would force a recompile. Load the "
                "checkpoint through the shard catalog for this topology."
            )
        self.params = params
        self.weights_version = new_version
        fresh = sum(
            1 for e in exec_cache.compile_events_since(mark)
            if e.get("source") == "fresh"
        )
        seconds = time.perf_counter() - t0
        record_weight_update(seconds, self.weights_version, fresh=fresh)
        logger.info(
            "[serving] adopted weights version %s in %.3fs "
            "(fresh compiles: %d)", self.weights_version, seconds, fresh,
        )
        return seconds

    # -- device state ---------------------------------------------------

    def _init_cache(self):
        import jax
        import jax.numpy as jnp

        paged0 = {
            "block_tables": jnp.zeros(
                (1, self.max_blocks_per_seq), jnp.int32
            ),
            "positions": jnp.zeros((1,), jnp.int32),
            "valid": jnp.zeros((1,), jnp.int32),
        }

        def shape_fn(p):
            # Cast as the programs do: the pools take the dtype of the K/V
            # the programs will write (bf16 under ``bf16: True``).
            return self.decode_mod.apply(
                {"params": self._half_params(self._deq_params(p))},
                jnp.zeros((1, 1), jnp.int32), paged=paged0,
                mutable=["cache"],
            )[1]["cache"]

        shapes = jax.eval_shape(shape_fn, self.params)
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes
        )

    # -- compiled programs ---------------------------------------------

    def _deq_params(self, params):
        """Weight-only int8: expand the resident {q, s} tree back to the
        module's float params INSIDE the program (the dequant fuses into
        the consuming matmuls' HBM reads). No-op at the default."""
        if self._wq:
            from smdistributed_modelparallel_tpu import quant as quant_mod

            params = quant_mod.dequantize_decode_params(params)
        return params

    def _half_params(self, params):
        from smdistributed_modelparallel_tpu.nn.utils import half_cast

        return half_cast(params, self.half)

    def _program(self, kind):
        """The two bucket-keyed programs: ``prefill`` ([1, chunk] tokens)
        and ``decode`` ([slots] single tokens). AOT-compiled through
        ``exec_cache.aot_compile`` (persistent warm start + X-ray audit,
        including the replicated-KV-pool detector)."""
        prog = self._programs.get(kind)
        if prog is not None:
            return prog
        import functools

        import jax
        import jax.numpy as jnp

        from smdistributed_modelparallel_tpu.utils import hlo_audit

        S, MB, C = self.slots_n, self.max_blocks_per_seq, self.chunk

        if kind == "decode":
            def fn(params, cache, toks, positions, tables, temps, top_ks,
                   top_ps, key_data):
                params = self._half_params(self._deq_params(params))
                logits, mut = self.decode_mod.apply(
                    {"params": params, "cache": cache}, toks[:, None],
                    paged={"block_tables": tables, "positions": positions},
                    mutable=["cache"],
                )
                nxt = _sample_rows(
                    logits[:, -1].astype(jnp.float32), temps, top_ks,
                    top_ps, key_data,
                )
                return nxt, mut["cache"]

            args = (
                self.params, self._cache,
                jnp.zeros((S,), jnp.int32), jnp.zeros((S,), jnp.int32),
                jnp.zeros((S, MB), jnp.int32), jnp.zeros((S,), jnp.float32),
                jnp.zeros((S,), jnp.int32), jnp.ones((S,), jnp.float32),
                jnp.zeros((S, 2), jnp.uint32),
            )
        elif kind == "prefill":
            def fn(params, cache, toks, table, start, valid, temps,
                   top_ks, top_ps, key_data):
                params = self._half_params(self._deq_params(params))
                logits, mut = self.decode_mod.apply(
                    {"params": params, "cache": cache}, toks,
                    paged={"block_tables": table, "positions": start,
                           "valid": valid},
                    mutable=["cache"],
                )
                last = jnp.take_along_axis(
                    logits, (valid - 1)[:, None, None], axis=1
                )[:, 0].astype(jnp.float32)
                tok = _sample_rows(last, temps, top_ks, top_ps, key_data)
                return tok, mut["cache"]

            args = (
                self.params, self._cache,
                jnp.zeros((1, C), jnp.int32), jnp.zeros((1, MB), jnp.int32),
                jnp.zeros((1,), jnp.int32), jnp.ones((1,), jnp.int32),
                jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32),
                jnp.ones((1,), jnp.float32), jnp.zeros((1, 2), jnp.uint32),
            )
        else:  # pragma: no cover - internal misuse
            raise ValueError(kind)

        name = f"serving_{kind}"
        from smdistributed_modelparallel_tpu import quant as quant_mod

        key_src = (
            "serving", kind, repr(self.decode_mod), S, MB, C, self.bt,
            str(self.half),
            tuple(sorted(self._mesh.shape.items())) if self._mesh else None,
        ) + quant_mod.serving_key_suffix()
        findings_fn = functools.partial(
            hlo_audit.serving_kv_findings, cache_template=self._cache
        )
        with profiling.region(f"serve/compile_{kind}"):
            jitted = jax.jit(fn, donate_argnums=(1,))
            if self._mesh is not None:
                with jax.set_mesh(self._mesh):
                    lowered = jitted.lower(*args)
                    compiled, audit, source = exec_cache.aot_compile(
                        name, key_src, lowered, params=self.params,
                        extra_findings_fn=findings_fn,
                        tp_ring_expected=False,
                    )
            else:
                lowered = jitted.lower(*args)
                compiled, audit, source = exec_cache.aot_compile(
                    name, key_src, lowered, params=self.params,
                    extra_findings_fn=findings_fn,
                    tp_ring_expected=False,
                )
        self.audits[kind] = audit
        self._programs[kind] = compiled
        record_serve_programs(len(self._programs))
        logger.info(
            "[serving] %s program ready (%s): slots=%d chunk=%d "
            "block_tokens=%d pool_blocks=%d", kind, source, S, C, self.bt,
            self.alloc.num_blocks,
        )
        return compiled

    # -- request intake -------------------------------------------------

    def submit(self, req):
        """Queue a request. Idempotent by request id: a rid that already
        finished (or is queued/in flight) is skipped — re-admitting the
        same request after a failover must not double-serve it."""
        if req.request_id in self.finished:
            return False
        if not self._admitting:
            # Quiesced/draining: new work belongs on another replica (the
            # router never routes here; a direct submit is refused so the
            # drain's "stop admitting" contract holds).
            return False
        if any(s is not None and s.sid == req.request_id
               for s in self._slots):
            return False
        if any(q.request_id == req.request_id for q in self._queue):
            return False
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            raise SMPValidationError(
                f"request {req.request_id!r}: prompt + max_new_tokens "
                f"({total}) exceeds the model's position limit "
                f"({self.max_len})."
            )
        if total > self.max_blocks_per_seq * self.bt:
            raise SMPValidationError(
                f"request {req.request_id!r}: {total} tokens exceed the "
                f"per-sequence table capacity "
                f"({self.max_blocks_per_seq * self.bt})."
            )
        req.trace_id = req.trace_id or req.request_id
        if len(req.resume_tokens) >= req.max_new_tokens:
            # Nothing left to generate: the dead replica had finished
            # sampling but not reported — complete it locally.
            self.results[req.request_id] = list(req.resume_tokens)
            self.finished.add(req.request_id)
            self._mirror(req, list(req.resume_tokens), done=True)
            record_serve_request("finished")
            record_serve_trace("queued", req.request_id, trace=req.trace_id)
            record_serve_trace(
                "finished", req.request_id, trace=req.trace_id,
                pos=len(req.resume_tokens), detail="fully_resumed",
            )
            return True
        self._queue.append(req)
        # A live submission "arrives" NOW (long-lived engine clock);
        # synthetic traces may place the arrival later. TTFT/deadline
        # measure from this instant, never from engine start.
        self._arrival_s[req.request_id] = max(
            self._now(), float(req.arrival_s)
        )
        # Mirrored from SUBMIT time, not admission: a replica dying with
        # requests still queued must not lose them — the survivor
        # re-admits queued and in-flight requests alike.
        self._mirror(req, list(req.resume_tokens), done=False)
        record_serve_trace("queued", req.request_id, trace=req.trace_id)
        return True

    def _rng_schedule(self, req):
        import jax

        keys = jax.random.split(
            jax.random.key(req.seed), req.max_new_tokens
        )
        data = np.asarray(jax.random.key_data(keys))
        if data.shape != (req.max_new_tokens, 2):  # pragma: no cover
            raise SMPValidationError(
                "unexpected PRNG key layout; smp.serving needs the "
                "2-word threefry key schedule smp.generate uses."
            )
        return data.astype(np.uint32)

    def _mirror(self, req, tokens, done):
        rid = req.request_id
        self.mirror_log[rid] = {
            "rid": rid,
            "prompt": list(map(int, req.prompt)),
            "max_new_tokens": int(req.max_new_tokens),
            "temperature": float(req.temperature),
            "top_k": req.top_k,
            "top_p": req.top_p,
            "eos_token_id": req.eos_token_id,
            "seed": int(req.seed),
            "deadline_s": req.deadline_s,
            "tokens": list(map(int, tokens)),
            "done": bool(done),
            # Trace continuity across failover: the surviving replica
            # re-admits under the SAME trace id, so the fused timeline
            # shows one request spanning both replicas' rings.
            "trace_id": req.trace_id or rid,
        }
        self._dirty.add(rid)

    def drain_dirty(self):
        """(rid, record) pairs with unmirrored progress — the replica
        layer ships these to peers and clears the dirty set."""
        out = [(rid, self.mirror_log[rid]) for rid in sorted(self._dirty)]
        self._dirty.clear()
        return out

    # -- scheduling -----------------------------------------------------

    @property
    def busy(self):
        return bool(self._queue) or any(
            s is not None for s in self._slots
        )

    def _now(self):
        if self._t0 is None:
            self._t0 = time.monotonic()
        return time.monotonic() - self._t0

    def _admit(self, now):
        if not self._admitting:
            return 0  # quiesced: the queue holds for drain/stragglers
        admitted = 0
        while self._queue:
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                break
            # Arrival-ordered admission; requests that haven't "arrived"
            # yet (synthetic traces) gate everything behind them.
            req = self._queue[0]
            arrival = self._arrival_s.get(
                req.request_id, max(req.arrival_s, 0.0)
            )
            if arrival > now:
                break
            need = len(req.prompt) + req.max_new_tokens
            if not self.alloc.can_reserve(need):
                break
            self._queue.popleft()
            self._arrival_s.pop(req.request_id, None)
            self.alloc.reserve(req.request_id, need)
            idx = free[0]
            slot = _Slot(
                req, self._rng_schedule(req),
                t_arrival=arrival, t_admit=now, idx=idx,
            )
            self._slots[idx] = slot
            self._admit_order.append(req.request_id)
            self._mirror(req, slot.all_tokens, done=False)
            record_serve_request("admitted")
            record_serve_latency("queue_wait", max(now - arrival, 0.0))
            record_serve_trace(
                "readmitted" if slot.resume_len else "admitted",
                req.request_id, trace=req.trace_id, slot=idx,
                pos=slot.resume_len,
            )
            self.stats["admitted"] += 1
            admitted += 1
        return admitted

    def _sampling_row(self, slot):
        req = slot.req
        return (
            float(req.temperature),
            int(req.top_k or 0),
            float(req.top_p if req.top_p is not None else 1.0),
        )

    def _finish(self, idx, now):
        slot = self._slots[idx]
        rid = slot.sid
        self.results[rid] = slot.all_tokens
        self.finished.add(rid)
        self._slots[idx] = None
        self.alloc.release(rid)
        self._mirror(slot.req, slot.all_tokens, done=True)
        record_serve_request("finished")
        if slot.req.deadline_s is not None and (
            now - slot.t_arrival > slot.req.deadline_s
        ):
            record_serve_request("deadline_miss")
        self.stats["finished"] += 1
        # Throughput gauges (req/s, tok/s) are owned by the time-series
        # snapshotter now: counter deltas over its window, not lifetime
        # or ad-hoc sliding averages.
        record_serve_trace(
            "finished", rid, trace=slot.req.trace_id, slot=slot.idx,
            pos=len(slot.all_tokens),
        )

    def _on_token(self, slot, tok, now):
        first = slot.t_first_token is None
        if first:
            slot.t_first_token = now
            record_serve_latency("ttft", now - slot.t_arrival)
            record_serve_latency("prefill", now - slot.t_admit)
            record_serve_trace(
                "first_token", slot.sid, trace=slot.req.trace_id,
                slot=slot.idx, pos=slot.sample_index,
            )
        else:
            record_serve_latency("itl", now - slot.t_last_token)
        slot.t_last_token = now
        slot.new_tokens.append(int(tok))
        self._gen_tokens += 1
        record_serve_tokens("generated", 1)
        self._mirror(slot.req, slot.all_tokens, done=False)
        req = slot.req
        return (
            (req.eos_token_id is not None and int(tok) == req.eos_token_id)
            or slot.remaining <= 0
        )

    def _decode_step(self):
        active = [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and s.state == "decode"
        ]
        if not active:
            return False
        S, MB = self.slots_n, self.max_blocks_per_seq
        toks = np.zeros((S,), np.int32)
        positions = np.zeros((S,), np.int32)
        tables = np.full((S, MB), TRASH_BLOCK, np.int32)
        temps = np.zeros((S,), np.float32)
        top_ks = np.zeros((S,), np.int32)
        top_ps = np.ones((S,), np.float32)
        kd = np.zeros((S, 2), np.uint32)
        for i, slot in active:
            # The decode input is the latest sampled token; its K/V are
            # written at `pos`, so the allocator must cover pos+1 tokens.
            self.alloc.ensure(slot.sid, slot.pos + 1)
            toks[i] = slot.all_tokens[-1]
            positions[i] = slot.pos
            tables[i] = self.alloc.table(slot.sid)
            temps[i], top_ks[i], top_ps[i] = self._sampling_row(slot)
            kd[i] = slot.rng_data[slot.sample_index]
        program = self._program("decode")
        t_dispatch = self._now()
        with profiling.region("serve/decode_step"):
            sampled, self._cache = program(
                self.params, self._cache, toks, positions, tables, temps,
                top_ks, top_ps, kd,
            )
        sampled = np.asarray(sampled)
        self.stats["decode_steps"] += 1
        # Token timestamps read the clock AFTER the device call — the
        # dispatch+compute wall belongs to this token's latency. (The
        # np.asarray transfer above is the step's natural sync point; no
        # extra block_until_ready is ever issued on this path.)
        now = self._now()
        record_serve_latency("decode_step", max(now - t_dispatch, 0.0))
        for i, slot in active:
            slot.pos += 1
            if self._on_token(slot, sampled[i], now):
                self._finish(i, now)
        return True

    def _prefill_tick(self):
        prefilling = [
            (i, s) for i, s in enumerate(self._slots)
            if s is not None and s.state == "prefill"
        ]
        if not prefilling:
            return False
        # Round-robin across admitting requests so two long prompts make
        # progress together.
        self._prefill_rr += 1
        i, slot = prefilling[self._prefill_rr % len(prefilling)]
        P = len(slot.prompt_full)
        C = self.chunk
        valid = min(C, P - slot.pos)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :valid] = slot.prompt_full[slot.pos:slot.pos + valid]
        self.alloc.ensure(slot.sid, slot.pos + valid)
        table = np.asarray([self.alloc.table(slot.sid)], np.int32)
        temps, top_ks, top_ps = self._sampling_row(slot)
        kd = slot.rng_data[slot.sample_index][None, :]
        program = self._program("prefill")
        with profiling.region("serve/prefill_chunk"):
            tok, self._cache = program(
                self.params, self._cache, chunk, table,
                np.asarray([slot.pos], np.int32),
                np.asarray([valid], np.int32),
                np.asarray([temps], np.float32),
                np.asarray([top_ks], np.int32),
                np.asarray([top_ps], np.float32),
                kd.astype(np.uint32),
            )
        slot.pos += valid
        self.stats["prefill_chunks"] += 1
        record_serve_tokens("prompt", valid)
        record_serve_trace(
            "prefill_chunk", slot.sid, trace=slot.req.trace_id,
            slot=slot.idx, pos=slot.pos, detail=f"valid={valid}",
        )
        if slot.pos >= P:
            # Prompt fully cached: the program's sample from the last
            # real position is the stream's first token (TTFT).
            slot.state = "decode"
            now = self._now()
            if self._on_token(slot, int(np.asarray(tok)[0]), now):
                self._finish(i, now)
        return True

    def _publish_occupancy(self):
        snap = (
            len(self._queue),
            sum(1 for s in self._slots if s is not None),
            self.alloc.used_blocks,
            self.alloc.reserved_unallocated,
        )
        if snap == self._occupancy_snap:
            return  # idle ticks must not spam the gauge registry
        self._occupancy_snap = snap
        record_serve_occupancy(
            queue_depth=snap[0],
            active_slots=snap[1],
            total_slots=self.slots_n,
            kv_used=snap[2],
            kv_free=self.alloc.free_blocks,
            kv_reserved=snap[3],
            kv_total=self.alloc.num_blocks,
            block_bytes=self.kv_block_bytes,
        )

    def _progress_of_admitted(self, n):
        """Chaos probe: (tokens emitted, finished?) of the n-th admitted
        request (1-based), or None when fewer than n were admitted."""
        if n < 1 or n > len(self._admit_order):
            return None
        rid = self._admit_order[n - 1]
        if rid in self.finished:
            return (len(self.results[rid]), True)
        for s in self._slots:
            if s is not None and s.sid == rid:
                return (len(s.all_tokens), False)
        return (0, False)

    def step(self):
        """One engine tick: admit arrivals into free slots, run one
        batched decode step, run one prefill chunk. Returns True while
        work remains; ``last_tick_worked`` says whether this tick did
        anything (False = waiting on arrivals or KV blocks — callers
        should back off instead of spinning)."""
        now = self._now()
        worked = bool(self._admit(now))
        worked = self._decode_step() or worked
        chaos.on_serve_decode(self._progress_of_admitted)
        worked = self._prefill_tick() or worked
        self._publish_occupancy()
        if self.timeseries is not None:
            self.timeseries.maybe_sample()
        # Same idle-gap contract as the time-series poll above: the
        # fleet publisher/aggregator ticks inline so a busy decode loop
        # keeps the fleet feed fresh (no-op when SMP_FLEET_INTERVAL is
        # off).
        fleet.tick()
        self.last_tick_worked = worked
        return self.busy

    def run(self, requests=(), timeout_s=300.0):
        """Submit ``requests`` and tick until every queued/in-flight
        request completes (or ``timeout_s`` elapses). Returns
        ``{request_id: generated token list}``."""
        for req in requests:
            self.submit(req)
        deadline = time.monotonic() + timeout_s
        while self.busy:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"serving run exceeded {timeout_s:g}s with "
                    f"{len(self._queue)} queued and "
                    f"{sum(1 for s in self._slots if s)} in flight."
                )
            self.step()
            if not self.last_tick_worked:
                # Waiting on an arrival or on KV blocks: don't burn a
                # host core polling.
                time.sleep(0.001)
        return dict(self.results)
