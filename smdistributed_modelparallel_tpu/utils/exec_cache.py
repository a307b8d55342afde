"""Persistent AOT executable cache (``smp.exec_cache``): fingerprint-
verified warm starts + shape bucketing.

The suite is XLA compile-bound (~10-12 s per step-program compile on
XLA:CPU), and since the in-job recovery supervisor landed, compile time
directly bounds availability: every shrink-to-survivors recovery and
every elastic resume pays a full world recompile inside the
``reshard_load``/``first_step`` MTTR phases. The reference SMP ships
pre-built executables to avoid exactly this class of cost (SURVEY §L0);
the pjit/TPUv4 line of work treats compilation as an offline, cacheable
artifact rather than a per-boot tax. This module makes the step engine's
compiled programs that artifact:

**Disk cache.** After each ``lowered.compile()`` the step engine
(``step.py::_make_runner``) serializes the executable with
``jax.experimental.serialize_executable`` into ``SMP_EXEC_CACHE_DIR``,
keyed by the step-cache key hash (generation-stripped, address-scrubbed —
the same digest family as ``hlo_audit.cache_key_hash``) joined with the
topology (pp/tp/rdp, mesh shape, process index/count, platform,
device_kind). The entry's ``meta.json`` additionally records the jax and
jaxlib versions, donation/health/pipeline knobs, the payload's sha256,
and the program's PR-9 X-ray fingerprint. On the next cold start — same
process restart, elastic resume, or supervisor recovery — the engine
deserializes instead of recompiling.

**Verified, not trusted.** A hit is accepted only after (1) the version/
knob facts in ``meta.json`` match the live environment
(``reject_version`` otherwise), (2) the payload hashes clean
(``corrupt`` otherwise — the entry is deleted and the fresh compile
overwrites it), and (3), when the X-ray is enabled, a fresh
``hlo_audit`` of the *deserialized* executable diffs clean against the
entry's stored fingerprint on the semantic subset (config / collectives
/ replication / remat) — ``reject_fingerprint`` otherwise. Verified hits
re-publish the ``smp_hlo_*`` gauges and the flight-recorder compile
event from that audit, so a cache hit never silently bypasses the PR-9
drift gates. ``SMP_HLO_AUDIT=off`` + cache on still works: the audit leg
is skipped and the hit rests on the integrity + version checks.

**Shape bucketing.** ``SMP_SHAPE_BUCKETS`` (e.g.
``"batch:16,32,64;seq:128,256;seq_pad=0"``) makes variable-shaped
batches map onto a small set of cached executables instead of retracing
per shape: the step engine pads the batch dim up to the next bucket
boundary and masks the padding at *microbatch granularity* — padded rows
fill whole trailing microbatches whose gradient/loss contributions are
multiplied by a 0/1 weight vector (a device input, so one executable
serves every occupancy), and the gradient mean divides by the number of
active microbatches. That makes batch bucketing exact, not approximate:
padded-run losses/grads equal the exact-shape run's. Sequence-dim
bucketing right-pads with ``seq_pad`` (default 0); masking those
positions is the model's contract (causal attention + ignore-index
losses are unaffected by appended positions). Bucketed keys land in the
same disk cache.

Everything is **off by default** (``SMP_EXEC_CACHE=off``): the compile
path is byte-identical to a build without this module until the knob is
turned on. ``SMP_EXEC_CACHE_MAX_BYTES`` bounds the cache directory with
LRU eviction (meta-file mtime, touched on every verified hit).

Observability: ``smp_exec_cache_total{result=hit|miss|reject_fingerprint
|reject_version|corrupt}`` counters, a ``source=fresh|disk_cache`` label
on ``smp_step_compile_seconds``, ``smp_exec_cache_entries`` (candidate
entries seen by the last warm-start consult), and a module-level compile
event ledger the recovery supervisor reads to split the ``first_step``
MTTR phase into ``compile_from_cache`` vs ``compile_fresh``.

Import-hygiene contract: importing this module must never initialize an
accelerator backend (jax device queries happen only inside the runtime
entry points).
"""

import hashlib
import json
import os
import pickle
import re
import shutil
import time

from smdistributed_modelparallel_tpu.utils.logger import get_logger
from smdistributed_modelparallel_tpu.utils.telemetry import (
    record_exec_cache,
    telemetry,
)

logger = get_logger()

ENV = "SMP_EXEC_CACHE"
DIR_ENV = "SMP_EXEC_CACHE_DIR"
MAX_BYTES_ENV = "SMP_EXEC_CACHE_MAX_BYTES"
BUCKETS_ENV = "SMP_SHAPE_BUCKETS"

_META_NAME = "meta.json"
_PAYLOAD_NAME = "payload.bin"
_META_VERSION = 1

# Object reprs embed heap addresses ("<... object at 0x7f...>"); the step
# cache key may contain such objects, and the disk key must be stable
# across processes.
_ADDR_RE = re.compile(r"0x[0-9a-f]+")


def enabled():
    """Cache gate: default OFF — ``SMP_EXEC_CACHE=on``/``1`` enables."""
    return os.environ.get(ENV, "off").lower() in ("on", "1", "true")


def cache_dir():
    return os.environ.get(DIR_ENV) or os.path.join(
        os.path.expanduser("~"), ".cache", "smp_exec_cache"
    )


def max_bytes():
    try:
        return int(os.environ.get(MAX_BYTES_ENV, "0") or "0")
    except ValueError:
        return 0


# ----------------------------------------------------------------------
# Keys
# ----------------------------------------------------------------------


def stable_key_hash(key):
    """Digest of a step compile-cache key that survives process restarts:
    heap addresses in object reprs are scrubbed before hashing. Callers
    pass the key WITHOUT its generation component (``key[1:]``) — the
    generation counts re-inits within one process and can never match
    across a restart."""
    return hashlib.sha256(
        _ADDR_RE.sub("0x", repr(tuple(key))).encode()
    ).hexdigest()[:16]


def module_hash(lowered):
    """Content hash of a lowered (pre-optimization) step module. The
    shape-derived disk key cannot see program CONTENT — edited user step
    code, a changed optimizer learning rate (a baked-in constant under
    ``fused_optimizer_step``) — so every load is verified against the
    entry's stored module hash: tracing+lowering always runs, only the
    expensive XLA compile is skipped on a hit. Falls back to None (cache
    bypassed) if the text form is unavailable."""
    try:
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()
    except Exception as e:  # pragma: no cover - backend-specific
        logger.debug("[exec_cache] lowered module text unavailable: %s", e)
        return None


def _env_facts():
    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def _topology_facts():
    """The placement facts an executable is welded to: degrees, mesh
    shape, process coordinates, platform/device_kind. Part of the entry
    id — executables for different topologies must coexist in one cache
    directory (the elastic/recovery story shrinks worlds)."""
    import jax

    from smdistributed_modelparallel_tpu.backend.state import state

    try:
        cfg = state.cfg
        mesh = state.mesh
    except Exception:  # uninitialized framework (direct/offline callers)
        cfg = mesh = None
    dev = jax.devices()[0]
    return {
        "pp": int(getattr(cfg, "pipeline_parallel_degree", 1) or 1) if cfg else 1,
        "tp": int(getattr(cfg, "tensor_parallel_degree", 1) or 1) if cfg else 1,
        "rdp": int(getattr(cfg, "sharded_data_parallel_degree", 1) or 1)
        if cfg else 1,
        "mesh": [[a, int(s)] for a, s in mesh.shape.items()]
        if mesh is not None else [],
        "devices": len(jax.devices()),
        "process_index": int(jax.process_index()),
        "process_count": int(jax.process_count()),
        "platform": dev.platform,
        "device_kind": str(dev.device_kind),
    }


def _knob_facts():
    """Knobs that change program semantics without necessarily moving the
    step key's shape components; version-checked at load (belt and
    braces — most are also folded into the step key itself)."""
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.utils import health

    try:
        cfg = state.cfg
    except Exception:  # uninitialized framework (direct/offline callers)
        cfg = None
    return {
        "pipeline": getattr(cfg, "pipeline", None) if cfg else None,
        "virtual": int(getattr(cfg, "virtual_pipeline_degree", 1) or 1)
        if cfg else 1,
        "microbatches": int(getattr(cfg, "microbatches", 1) or 1) if cfg else 1,
        "fused_optimizer_step": bool(getattr(cfg, "fused_optimizer_step", False))
        if cfg else False,
        "fused_step_donation": bool(getattr(cfg, "fused_step_donation", False))
        if cfg else False,
        "health": health.mode(),
        # ZeRO-3 knobs: mode/bucket/threshold reshape the compiled program
        # (param sharding layout, slice-grad restructuring, reduce-scatter
        # bucket boundaries) at identical input shapes — a knob flip must
        # version-mismatch, never warm-hit a stale executable. Sub-knobs
        # idle under the current mode are canonicalized (0 / "-") so a
        # stray env var never spuriously rejects entries of byte-identical
        # programs; mirrors the step engine's zero_key.
        **_zero_knob_facts(cfg),
        # Recompute-planner knobs, same canonicalization contract: the
        # default mode omits both facts entirely (entries stored before
        # the knob existed keep verifying), and the budget is recorded
        # only under "auto" — the one mode whose program reads it — so a
        # stray SMP_RECOMPUTE_BUDGET_MB never invalidates anything.
        **_recompute_knob_facts(cfg),
        # Overlapped-tp knobs, same contract: defaults omit the facts
        # (pre-knob disk entries keep verifying); a knob flip is a
        # version mismatch, never a warm hit of the other program.
        **_tp_overlap_knob_facts(cfg),
        # Quantization knobs (matmul_precision / SMP_KV_QUANT /
        # SMP_DECODE_WEIGHTS), same contract: bf16/none contribute no
        # facts at all.
        **_quant_knob_facts(cfg),
    }


def _quant_knob_facts(cfg):
    from smdistributed_modelparallel_tpu import quant

    facts = {}
    mode = quant.matmul_precision_mode(cfg)
    if mode != "bf16":
        facts["matmul_precision"] = mode
    if quant.kv_quant_mode() != "none":
        facts["kv_quant"] = quant.kv_quant_mode()
    if quant.decode_weights_mode() != "none":
        facts["decode_weights"] = quant.decode_weights_mode()
    return facts


def _tp_overlap_knob_facts(cfg):
    from smdistributed_modelparallel_tpu.ops.collective_matmul import (
        fused_qkv_effective,
        tp_overlap_mode,
    )

    mode = tp_overlap_mode(cfg)
    fused = fused_qkv_effective(cfg)
    facts = {}
    if mode != "off":
        facts["tp_overlap"] = mode
    if fused:
        facts["fused_qkv"] = True
    return facts


def _recompute_knob_facts(cfg):
    from smdistributed_modelparallel_tpu.parallel import remat_plan

    mode = remat_plan.resolve(cfg)
    if mode == "full":
        return {}
    facts = {"recompute": mode}
    if mode == "auto":
        # Unset (-1) vs explicit 0 are different programs (the planner's
        # fallback budget vs degrade-everything); mirror the step key.
        budget = getattr(cfg, "recompute_budget_mb", None)
        facts["recompute_budget_mb"] = -1 if budget is None else int(budget)
    return facts


def _zero_knob_facts(cfg):
    zero3 = bool(getattr(cfg, "zero3_enabled", False))
    zero2d = bool(getattr(cfg, "zero2d_enabled", False))
    prefetch = "-"
    if zero3:
        from smdistributed_modelparallel_tpu.parallel.zero import (
            prefetch_knob,
        )

        prefetch = prefetch_knob()
    return {
        "sharded_params": getattr(cfg, "sharded_params", "none")
        if cfg else "none",
        "zero3_bucket_mb": int(getattr(cfg, "zero3_bucket_mb", 0) or 0)
        if zero3 else 0,
        "sdp_param_persistence_threshold": int(
            getattr(cfg, "sdp_param_persistence_threshold", 0) or 0
        ) if (zero3 or zero2d) else 0,
        "zero3_prefetch": prefetch,
    }


def _entry_dir(name, key_hash, topo):
    ident = hashlib.sha256(
        json.dumps(
            {"name": name, "key": key_hash, "topology": topo},
            sort_keys=True,
        ).encode()
    ).hexdigest()[:24]
    return os.path.join(cache_dir(), f"{name}-{ident}")


# ----------------------------------------------------------------------
# Load / store
# ----------------------------------------------------------------------


def _delete_entry(path):
    try:
        shutil.rmtree(path)
    except OSError:
        pass


def load(name, key_hash, module_sha=None, params=None,
         expected_param_shardings=None, extra_findings_fn=None,
         tp_ring_expected=None):
    """Deserialize a cached step executable, or None.

    Returns ``(compiled, audit)``; ``audit`` is the fresh post-load X-ray
    of the deserialized executable when the audit pass is enabled (its
    gauges/flight event are already re-published), else None. Every
    outcome lands in ``smp_exec_cache_total{result=}``.
    """
    if module_sha is None:
        # Without a lowered-module hash a hit cannot be content-verified;
        # treat the lookup as a miss rather than trust blindly.
        record_exec_cache("miss")
        return None, None
    path = _entry_dir(name, key_hash, _topology_facts())
    meta_path = os.path.join(path, _META_NAME)
    payload_path = os.path.join(path, _PAYLOAD_NAME)
    if not os.path.exists(meta_path) or not os.path.exists(payload_path):
        record_exec_cache("miss")
        return None, None
    try:
        with open(meta_path, encoding="utf-8") as f:
            meta = json.load(f)
    except (OSError, ValueError) as e:
        logger.warning("[exec_cache] %s: unreadable meta (%s); evicting.",
                       name, e)
        _delete_entry(path)
        record_exec_cache("corrupt")
        return None, None
    skew = _version_skew(meta)
    if skew:
        logger.info("[exec_cache] %s: entry rejected (version skew: %s); "
                    "recompiling.", name, skew)
        record_exec_cache("reject_version")
        return None, None
    if meta.get("module_sha") != module_sha:
        logger.warning(
            "[exec_cache] %s: entry's lowered-module hash differs from "
            "the live program (changed step code / optimizer constants?); "
            "recompiling.", name,
        )
        record_exec_cache("reject_fingerprint")
        return None, None
    t0 = time.perf_counter()
    try:
        with open(payload_path, "rb") as f:
            raw = f.read()
        if hashlib.sha256(raw).hexdigest() != meta.get("payload_sha256"):
            raise ValueError("payload sha256 mismatch")
        payload, in_tree, out_tree = pickle.loads(raw)
        from jax.experimental import serialize_executable

        import jax

        by_id = {d.id: d for d in jax.devices()}
        compiled = serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=[by_id[i] for i in meta["device_ids"]],
        )
    except Exception as e:  # corrupt/truncated/undeserializable entry
        logger.warning(
            "[exec_cache] %s: corrupt cache entry (%s); evicting and "
            "recompiling.", name, e,
        )
        _delete_entry(path)
        record_exec_cache("corrupt")
        return None, None
    audit = _verify_and_republish(
        name, key_hash, compiled, meta, params, expected_param_shardings,
        t0, extra_findings_fn=extra_findings_fn,
        tp_ring_expected=tp_ring_expected,
    )
    if audit is False:  # fingerprint veto
        record_exec_cache("reject_fingerprint")
        return None, None
    try:  # LRU clock: verified hits refresh the entry's eviction rank
        os.utime(meta_path, None)
    except OSError:
        pass
    dt = time.perf_counter() - t0
    record_exec_cache("hit", seconds=dt)
    logger.info(
        "[exec_cache] %s: warm start from %s in %.3fs (saved compile "
        "measured at %.1fs).", name, path, dt,
        meta.get("compile_seconds", 0.0) or 0.0,
    )
    return compiled, (audit or None)


def _version_skew(meta):
    """Human-readable mismatch description, or None when the entry's
    environment facts match the live process."""
    env = _env_facts()
    for k, v in env.items():
        if meta.get("env", {}).get(k) != v:
            return f"{k}: {meta.get('env', {}).get(k)} != {v}"
    knobs = _knob_facts()
    stored = meta.get("knobs", {})
    for k, v in knobs.items():
        if stored.get(k) != v:
            return f"knob {k}: {stored.get(k)} != {v}"
    if meta.get("version") != _META_VERSION:
        return f"entry format {meta.get('version')} != {_META_VERSION}"
    return None


def _verify_and_republish(name, key_hash, compiled, meta, params,
                          expected_param_shardings, t0,
                          extra_findings_fn=None, tp_ring_expected=None):
    """X-ray the deserialized executable and diff it against the entry's
    stored fingerprint. Returns the fresh audit on success (gauges +
    flight event re-published — cache hits do not bypass the PR-9
    gates), ``None`` when the audit pass is disabled, and ``False`` on a
    semantic mismatch (the caller rejects the hit)."""
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    if not hlo_audit.enabled():
        return None
    stored_fp = meta.get("audit")
    try:
        fresh = hlo_audit.audit_compiled(
            name, compiled, key=key_hash, params=params,
            expected_param_shardings=expected_param_shardings,
            publish=False, persist=False,
            extra_findings_fn=extra_findings_fn,
            tp_ring_expected=tp_ring_expected,
        )
    except Exception as e:  # pragma: no cover - defensive
        logger.warning("[exec_cache] %s: post-load audit failed (%s); "
                       "rejecting the cached executable.", name, e)
        return False
    if stored_fp:
        changes = hlo_audit.diff(
            stored_fp, fresh.fingerprint, fields=hlo_audit.SEMANTIC_FIELDS
        )
        if changes:
            logger.warning(
                "[exec_cache] %s: cached executable's fingerprint drifted "
                "from the entry's stored audit (%s); recompiling.",
                name, changes,
            )
            return False
    hlo_audit.republish(fresh, seconds=time.perf_counter() - t0)
    return fresh


def aot_compile(name, key_src, lowered, params=None,
                extra_findings_fn=None, tp_ring_expected=None):
    """Compile a lowered program through the full warm-start sequence the
    step engine runs — consult the disk cache (content-verified by the
    lowered-module hash, fingerprint-diffed on hit), else
    ``lowered.compile()`` + X-ray audit + store — packaged for other
    program owners (the serving engine's prefill/decode programs).

    ``key_src`` is any repr-stable tuple identifying the program family
    (shapes, knobs, topology facts the caller deems key-worthy); the
    topology itself is folded in by the entry path as usual. Returns
    ``(compiled, audit, source)`` with ``source`` in
    {"fresh", "disk_cache"}; the compile event lands in the module
    ledger either way (the supervisor's MTTR split reads it).
    """
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    key_hash = stable_key_hash(key_src)
    compiled = None
    audit = None
    source = "fresh"
    module_sha = None
    t0 = time.perf_counter()
    if enabled():
        module_sha = module_hash(lowered)
        compiled, audit = load(
            name, key_hash, module_sha=module_sha, params=params,
            extra_findings_fn=extra_findings_fn,
            tp_ring_expected=tp_ring_expected,
        )
        if compiled is not None:
            source = "disk_cache"
    if compiled is None:
        compiled = lowered.compile()
        audit = hlo_audit.maybe_audit(
            name, compiled, key=key_hash, params=params,
            extra_findings_fn=extra_findings_fn,
            tp_ring_expected=tp_ring_expected,
        )
        if enabled():
            store(
                name, key_hash, compiled, module_sha=module_sha,
                audit=audit, compile_seconds=time.perf_counter() - t0,
            )
    record_compile_event(name, source, time.perf_counter() - t0)
    return compiled, audit, source


def store(name, key_hash, compiled, module_sha=None, audit=None,
          compile_seconds=None):
    """Serialize one compiled step executable into the cache. Failures
    are logged, never raised into the step path. Returns the entry dir
    or None."""
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        raw = pickle.dumps((payload, in_tree, out_tree))
        # The devices the program was compiled for: a reload executes on
        # these and no others (left to itself it takes every device of the
        # backend and then wants one shard per device).
        device_ids = [
            int(d.id) for d in compiled.runtime_executable().local_devices()
        ]
    except Exception as e:
        logger.warning("[exec_cache] %s: executable not serializable on "
                       "this backend (%s); entry not written.", name, e)
        return None
    topo = _topology_facts()
    path = _entry_dir(name, key_hash, topo)
    meta = {
        "version": _META_VERSION,
        "name": name,
        "key": key_hash,
        "created_unix": time.time(),
        "env": _env_facts(),
        "topology": topo,
        "knobs": _knob_facts(),
        "payload_sha256": hashlib.sha256(raw).hexdigest(),
        "payload_bytes": len(raw),
        "device_ids": device_ids,
        "module_sha": module_sha,
        "compile_seconds": compile_seconds,
        "audit": audit.fingerprint if audit is not None else None,
    }
    try:
        os.makedirs(path, exist_ok=True)
        tmp = os.path.join(path, _PAYLOAD_NAME + ".tmp")
        with open(tmp, "wb") as f:
            f.write(raw)
        os.replace(tmp, os.path.join(path, _PAYLOAD_NAME))
        tmp = os.path.join(path, _META_NAME + ".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(meta, f, indent=1, default=str)
        os.replace(tmp, os.path.join(path, _META_NAME))
    except OSError as e:
        logger.warning("[exec_cache] %s: cache write failed (%s).", name, e)
        return None
    logger.info("[exec_cache] %s: stored %d-byte executable at %s.",
                name, len(raw), path)
    _evict_lru(keep=path)
    return path


def _entries():
    """[(entry_dir, meta_mtime, total_bytes)] for every cache entry."""
    root = cache_dir()
    out = []
    try:
        names = os.listdir(root)
    except OSError:
        return out
    for n in names:
        path = os.path.join(root, n)
        meta = os.path.join(path, _META_NAME)
        if not os.path.isdir(path) or not os.path.exists(meta):
            continue
        size = 0
        try:
            mtime = os.path.getmtime(meta)
            for f in os.listdir(path):
                size += os.path.getsize(os.path.join(path, f))
        except OSError:
            continue
        out.append((path, mtime, size))
    return out


def _evict_lru(keep=None):
    """Drop least-recently-used entries until the directory fits
    ``SMP_EXEC_CACHE_MAX_BYTES`` (0 = unbounded). The entry named by
    ``keep`` (normally the one just written) is evicted last."""
    cap = max_bytes()
    if cap <= 0:
        return
    entries = sorted(_entries(), key=lambda e: (e[0] == keep, e[1]))
    total = sum(e[2] for e in entries)
    for path, _, size in entries:
        if total <= cap:
            break
        if path == keep and len(entries) > 1:
            continue
        _delete_entry(path)
        total -= size
        logger.info("[exec_cache] LRU-evicted %s (%d bytes; cap %d).",
                    path, size, cap)


def note_warm_start(what):
    """Recovery/elastic-resume consult hook: count the candidate entries
    in the cache directory so the availability story is measured before
    the first step compiles. One gauge + one flight-recorder event; a
    disabled cache records nothing and returns 0."""
    if not enabled():
        return 0
    n = len(_entries())
    telemetry.gauge(
        "smp_exec_cache_entries",
        "executable-cache entries present at the last warm-start consult",
    ).set(n)
    from smdistributed_modelparallel_tpu.utils.flight_recorder import (
        flight_recorder,
    )

    flight_recorder.record_compile("exec_cache_consult", what, 0.0)
    logger.info(
        "[exec_cache] %s: consulting %s before first_step — %d cached "
        "executable(s) available.", what, cache_dir(), n,
    )
    return n


# ----------------------------------------------------------------------
# Compile-event ledger (read by the recovery supervisor to split the
# first_step MTTR phase into compile_from_cache vs compile_fresh)
# ----------------------------------------------------------------------

compile_events = []


def record_compile_event(name, source, seconds):
    compile_events.append(
        {"name": name, "source": source, "seconds": float(seconds),
         "t": time.monotonic()}
    )
    # The goodput ledger attributes compile phases compile_fresh
    # tentatively (the source is only known here, once the load/compile
    # resolved): a disk_cache event moves its seconds to compile_cache.
    try:
        from smdistributed_modelparallel_tpu.utils.goodput import goodput

        goodput.note_compile(source, seconds)
    except Exception:
        pass


def compile_event_mark():
    return len(compile_events)


def compile_events_since(mark):
    return compile_events[int(mark):]


# ----------------------------------------------------------------------
# Shape bucketing policy
# ----------------------------------------------------------------------

_policy_cache = {}


def bucket_policy():
    """Parse ``SMP_SHAPE_BUCKETS`` into ``{"batch": [...], "seq": [...],
    "seq_pad": int}`` (ascending, deduped), or None when unset/empty.
    Malformed specs log once and disable bucketing rather than raise."""
    spec = os.environ.get(BUCKETS_ENV, "").strip()
    if not spec:
        return None
    cached = _policy_cache.get(spec)
    if cached is not None:
        return cached or None
    policy = {"seq_pad": 0}
    try:
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            if part.startswith("seq_pad="):
                policy["seq_pad"] = int(part.split("=", 1)[1])
                continue
            dim, _, vals = part.partition(":")
            dim = dim.strip()
            if dim not in ("batch", "seq") or not vals:
                raise ValueError(f"unknown bucket spec part {part!r}")
            sizes = sorted({int(v) for v in vals.split(",") if v.strip()})
            if not sizes or any(s <= 0 for s in sizes):
                raise ValueError(f"bad bucket sizes in {part!r}")
            policy.setdefault(dim, [])
            policy[dim] = sorted(set(policy[dim]) | set(sizes))
    except (ValueError, TypeError) as e:
        logger.warning(
            "[exec_cache] malformed %s=%r (%s); shape bucketing disabled.",
            BUCKETS_ENV, spec, e,
        )
        _policy_cache[spec] = False
        return None
    if "batch" not in policy and "seq" not in policy:
        _policy_cache[spec] = False
        return None
    _policy_cache[spec] = policy
    return policy


def bucket_for(n, sizes):
    """Smallest bucket >= n, or None (n exceeds every bucket -> compile
    exact)."""
    for s in sizes:
        if s >= int(n):
            return int(s)
    return None


def record_bucket(result):
    telemetry.counter(
        "smp_shape_bucket_total",
        "shape-bucketing decisions by outcome "
        "(exact / padded / unbucketable)",
    ).labels(result=result).inc()
