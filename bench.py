"""Benchmark: GPT-2 training throughput (tokens/sec/chip) with MFU accounting.

Runs on the TPU chip JAX finds, and only there: with no chip it fails (a CPU
timing is never written under a device metric's name). Single-chip benchmark =
BASELINE config #1 (GPT-2 124M); ``python chip_smoke.py`` is the quicker proof
that the train and serve paths start on the chip at all.

Methodology notes:
- Timing forces a device->host readback per boundary, so a timed block ends
  when the device has finished, not when the dispatch returned.
- ``vs_baseline``: the reference ships no numbers in-tree (BASELINE.md), so
  the baseline is a hand-written plain-JAX train step of the same model,
  same microbatching, measured in the same run — the framework's "without
  smp" comparison, mirroring the reference's with/without-SMP parity tests.
  1.0 means zero framework overhead; >1.0 means faster than plain JAX.
- MFU = model matmul FLOPs (analytic; full, non-causal attention scores, as
  executed) / step time / chip peak bf16 FLOPs.
- A phase that fails raises and the run exits non-zero: no probe, audit or
  attribution block is skipped in silence.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import dataclasses
import functools
import json
import os
import sys
import time


def _chip_peak_tflops(device):
    """Peak dense bf16 TFLOP/s of ``device``, from the spec table in
    utils/profiling.py keyed by device kind. A device the table does not
    know is an error: an MFU over a guessed peak is not a measurement."""
    from smdistributed_modelparallel_tpu.utils.profiling import device_peaks

    flops, _ = device_peaks(device)
    if not flops:
        raise RuntimeError(
            f"bench: no peak FLOP/s known for device kind "
            f"{device.device_kind!r}."
        )
    return flops / 1e12


def _model_flops_per_step(n_layers, d_model, vocab, batch, seq):
    """Analytic train-step matmul FLOPs (fwd*3 for fwd+bwd)."""
    tokens = batch * seq
    per_layer = 2 * tokens * 12 * d_model * d_model   # qkv+proj+mlp fwd
    attn = 4 * tokens * seq * d_model                 # QK^T + PV fwd (full scores)
    head = 2 * tokens * d_model * vocab               # tied lm head fwd
    return 3 * (n_layers * (per_layer + attn) + head)


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _readback(x):
    import numpy as np

    return float(np.asarray(x.ravel()[0] if hasattr(x, "ravel") else x))


def _health_overhead_probe(train_step, model, optimizer, ids, iters,
                           deadline):
    """SMP_BENCH_HEALTH_PROBE=1: measure the cheap-sentinel overhead.

    Same interleaved-A/B methodology as the main timing (off/cheap blocks
    alternate, medians of 3 — comparing one later cheap block against the
    earlier off median would fold clock/thermal drift straight into the
    overhead number). Both step programs stay cached across the env flips
    (the step cache keys on the health mode), so only the first cheap
    block pays a compile. The target is <2% (BENCH_NOTES.md); a miss logs
    a warning but never fails the bench. Respects the remaining probe
    window (``deadline``): skipped (or cut short between block pairs)
    rather than allowed to overrun the driver's cap.
    """
    if deadline - time.time() < 120:
        sys.stderr.write(
            f"bench: skipping health-overhead probe "
            f"({deadline - time.time():.0f}s left in window < 120s floor).\n")
        return
    prev = os.environ.get("SMP_HEALTH_CHECK")

    def set_mode(mode):
        if mode is None:
            os.environ.pop("SMP_HEALTH_CHECK", None)
        else:
            os.environ["SMP_HEALTH_CHECK"] = mode

    def timed_block(mode):
        set_mode(mode)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = train_step(model, ids)
            optimizer.step()
        _readback(out.reduce_mean())
        return (time.perf_counter() - t0) / iters

    off_times, cheap_times = [], []
    try:
        set_mode("cheap")
        out = train_step(model, ids)          # one-time recompile under cheap
        optimizer.step()
        _readback(out.reduce_mean())
        for _ in range(3):
            off_times.append(timed_block(None))
            cheap_times.append(timed_block("cheap"))
            if time.time() > deadline:
                sys.stderr.write(
                    "bench: health probe hit the window deadline; using the "
                    f"{len(cheap_times)} block pair(s) measured so far.\n")
                break
    finally:
        set_mode(prev)
    off_dt = sorted(off_times)[len(off_times) // 2]
    cheap_dt = sorted(cheap_times)[len(cheap_times) // 2]
    overhead = cheap_dt / off_dt - 1.0
    ok = overhead < 0.02
    sys.stderr.write(json.dumps({
        "component": "health_overhead",
        "off_ms": round(off_dt * 1e3, 3),
        "cheap_ms": round(cheap_dt * 1e3, 3),
        "overhead_frac": round(overhead, 4),
        "blocks": len(cheap_times),
        "ok": ok,
    }) + "\n")
    if not ok:
        sys.stderr.write(
            f"bench: WARNING cheap health mode cost {overhead * 100:.1f}% "
            "step time (target < 2%).\n")
    sys.stderr.flush()


def _pipeline_interleave_probe(deadline):
    """SMP_BENCH_PIPELINE_PROBE=1: 3-way pipeline-schedule A/B at pp=2,
    mb=8 — plain 1F1B (v=1) vs interleaved (v=2) vs zero-bubble ZB-H1
    (v=2, split backward).

    Same interleaved-pairs methodology as the health probe (alternating
    blocks, medians of up to 3 rounds, window-capped) with one forced
    difference: the variants cannot share a compiled program — the
    schedule kind and virtual degree change the partitioning and the
    baked schedule — so each block re-inits the framework and pays its
    compile during the per-block warmup steps, OUTSIDE the timed region.
    Emits one stderr JSON line {"component": "pipeline_schedule",
    schedules: {name: ms}, speedup_v2, speedup_zb, schedule_best, ...}
    (plus the legacy v1_ms/v2_ms/speedup fields); the pass criterion is a
    TPU criterion recorded in BENCH_NOTES.md.
    """
    import jax

    if len(jax.devices()) < 2:
        sys.stderr.write(
            "bench: skipping pipeline probe (needs >= 2 devices for "
            "pp=2).\n")
        return
    if deadline - time.time() < 240:
        sys.stderr.write(
            f"bench: skipping pipeline probe ({deadline - time.time():.0f}s "
            "left in window < 240s floor).\n")
        return
    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )

    n_layers, d_model, n_heads, seq, batch, vocab = (
        8, 512, 8, 512, 16, 8192
    )
    iters = 10

    def build(v, schedule="interleaved"):
        smp.reset()
        smp.init({
            "pipeline_parallel_degree": 2, "microbatches": 8, "ddp": True,
            "virtual_pipeline_degree": v, "bf16": True,
            "pipeline": schedule,
        })
        model = smp.DistributedModel(TransformerLM(
            vocab_size=vocab, max_len=seq, d_model=d_model,
            n_layers=n_layers, n_heads=n_heads,
        ))
        optimizer = smp.DistributedOptimizer(optax.sgd(1e-3), model)
        ids = jax.random.randint(jax.random.key(0), (batch, seq), 0, vocab)

        @smp.step
        def train_step(model, b):
            logits = model(b)
            lg = logits[:, :-1].astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, b[:, 1:, None], axis=-1)[..., 0]
            loss = jnp.mean(lse - tgt)
            model.backward(loss)
            return loss

        return model, optimizer, train_step, ids

    def timed_block(v, schedule="interleaved"):
        model, optimizer, train_step, ids = build(v, schedule)
        out = None
        for _ in range(2):      # warmup: compile + first dispatch
            out = train_step(model, ids)
            optimizer.step()
        _readback(out.reduce_mean())
        t0 = time.perf_counter()
        for _ in range(iters):
            out = train_step(model, ids)
            optimizer.step()
        _readback(out.reduce_mean())
        dt = (time.perf_counter() - t0) / iters
        # FLOP-weighted remat fraction + fingerprint from the compiled
        # program's X-ray: the schedule-level recompute cost of each
        # variant becomes ledger-verifiable on CPU (the wall-clock A/B
        # needs a chip; the census does not).
        remat = fp = None
        from smdistributed_modelparallel_tpu.utils import hlo_audit

        audit = hlo_audit.of_step_function(train_step)
        if audit is not None:
            remat = audit.remat.get("fraction")
            fp = audit.fingerprint_hash
        return dt, remat, fp

    # Variant order inside a round keeps the A/B/C blocks interleaved so
    # clock/thermal drift hits all three schedules alike.
    variants = (("1f1b", 1, "interleaved"),
                ("interleaved_v2", 2, "interleaved"),
                ("zb_h1", 2, "zero_bubble"))
    times = {name: [] for name, _, _ in variants}
    remats = {}
    fps = {}
    for _ in range(3):
        for name, v, schedule in variants:
            dt, remat, fp = timed_block(v, schedule)
            times[name].append(dt)
            if remat is not None:
                remats[name] = remat
            if fp is not None:
                fps[name] = fp
        if time.time() > deadline:
            sys.stderr.write(
                "bench: pipeline probe hit the window deadline; using the "
                f"{len(times['zb_h1'])} block round(s) measured so far.\n")
            break
    smp.reset()

    med = {name: _median(ts) for name, ts in times.items()}
    best = min(med, key=med.get)
    result = {
        "component": "pipeline_schedule",
        "pp": 2, "microbatches": 8,
        "schedules": {name: round(dt * 1e3, 3) for name, dt in med.items()},
        "schedule_best": best,
        # Per-schedule FLOP-weighted remat fraction + program fingerprint
        # from the compile-time X-ray (scripts/perf_ledger.py schema-checks
        # and renders these; empty dicts when no AOT executable exists).
        "remat_fraction": remats,
        "fingerprints": fps,
        "speedup_v2": round(med["1f1b"] / med["interleaved_v2"], 4),
        "speedup_zb": round(med["1f1b"] / med["zb_h1"], 4),
        # Legacy fields (round <= 5 consumers of the v1-vs-v2 probe).
        "v1_ms": round(med["1f1b"] * 1e3, 3),
        "v2_ms": round(med["interleaved_v2"] * 1e3, 3),
        "speedup": round(med["1f1b"] / med["interleaved_v2"], 4),
        "blocks": len(times["zb_h1"]),
    }
    sys.stderr.write(json.dumps(result) + "\n")
    sys.stderr.flush()
    return result


def _zero_probe(deadline):
    """SMP_BENCH_ZERO_PROBE=1: zero2d vs zero3 A/B at full-rdp data
    parallelism — per-step wall time plus the memory story (per-device
    parameter bytes from the realized shardings, program argument/temp
    bytes from the X-ray memory breakdown).

    zero2d is the GSPMD-scheduled baseline (persistence-thresholded param
    sharding, implicit collectives); zero3 adds the explicit machinery
    this probe is for: just-in-time per-layer gathers, the double-buffered
    prefetch registers, and the bucketed reduce-scatter grad path. Same
    interleaved-blocks methodology as the pipeline probe (each block
    re-inits — the sharding mode changes the compiled program — and pays
    its compile in warmup, outside the timed region). Emits one stderr
    JSON line {"component": "zero_probe", zero2d_ms, zero3_ms, speedup,
    ...} and returns the dict for the stdout result block; the pass
    criterion is a TPU criterion recorded in BENCH_NOTES.md.
    """
    import jax

    if len(jax.devices()) < 2:
        sys.stderr.write(
            "bench: skipping zero probe (needs >= 2 devices for rdp).\n")
        return None
    if deadline - time.time() < 180:
        sys.stderr.write(
            f"bench: skipping zero probe ({deadline - time.time():.0f}s "
            "left in window < 180s floor).\n")
        return None
    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    rdp = len(jax.devices())
    n_layers, d_model, n_heads, seq, vocab = 8, 512, 8, 512, 8192
    # Per-microbatch batch must divide by rdp for the explicit
    # slice-grad + reduce-scatter path (mb=4 below).
    batch = 4 * rdp
    iters = 10
    threshold = 4096

    def build(extra):
        smp.reset()
        cfg = {"microbatches": 4, "ddp": True, "bf16": True,
               "sdp_param_persistence_threshold": threshold}
        cfg.update(extra)
        smp.init(cfg)
        model = smp.DistributedModel(TransformerLM(
            vocab_size=vocab, max_len=seq, d_model=d_model,
            n_layers=n_layers, n_heads=n_heads,
        ))
        optimizer = smp.DistributedOptimizer(optax.sgd(1e-3), model)
        ids = jax.random.randint(jax.random.key(0), (batch, seq), 0, vocab)

        @smp.step
        def train_step(model, b):
            logits = model(b)
            lg = logits[:, :-1].astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, b[:, 1:, None], axis=-1)[..., 0]
            loss = jnp.mean(lse - tgt)
            model.backward(loss)
            return loss

        return model, optimizer, train_step, ids

    def param_bytes(model):
        """(per-device shard bytes, logical total bytes): both variants
        shard at the same threshold, so the 1/rdp memory claim reads off
        the per-device/total ratio."""
        per_device = total = 0
        for leaf in jax.tree_util.tree_leaves(model.params):
            try:
                shard_shape = leaf.sharding.shard_shape(leaf.shape)
            except Exception:
                shard_shape = leaf.shape
            n = 1
            for d in shard_shape:
                n *= int(d)
            per_device += n * leaf.dtype.itemsize
            total += int(leaf.size) * leaf.dtype.itemsize
        return per_device, total

    variants = (
        ("zero2d", {"sharded_data_parallel_degree": rdp}),
        ("zero3", {"sharded_params": "zero3"}),
    )
    times = {name: [] for name, _ in variants}
    memory = {}
    zero_block = None
    for _round in range(3):
        for name, extra in variants:
            model, optimizer, train_step, ids = build(extra)
            out = None
            for _ in range(2):     # warmup: compile + first dispatch
                out = train_step(model, ids)
                optimizer.step()
            _readback(out.reduce_mean())
            if name not in memory:
                audit = hlo_audit.of_step_function(train_step)
                per_device, total = param_bytes(model)
                memory[name] = {
                    "param_bytes_per_device": per_device,
                    "param_bytes_total": total,
                    "program_memory": (audit.memory if audit else {}),
                }
                if name == "zero3" and audit is not None:
                    zero_block = audit.zero
            t0 = time.perf_counter()
            for _ in range(iters):
                out = train_step(model, ids)
                optimizer.step()
            _readback(out.reduce_mean())
            times[name].append((time.perf_counter() - t0) / iters)
        if time.time() > deadline:
            sys.stderr.write(
                "bench: zero probe hit the window deadline; using the "
                f"{len(times['zero3'])} block round(s) measured so far.\n")
            break
    smp.reset()

    med = {name: _median(ts) for name, ts in times.items()}
    result = {
        "component": "zero_probe",
        "rdp": rdp,
        "zero2d_ms": round(med["zero2d"] * 1e3, 3),
        "zero3_ms": round(med["zero3"] * 1e3, 3),
        "speedup": round(med["zero2d"] / med["zero3"], 4),
        "memory": memory,
        "zero": zero_block,
        "blocks": len(times["zero3"]),
    }
    sys.stderr.write(json.dumps(result) + "\n")
    sys.stderr.flush()
    return result


def _tp_probe(deadline):
    """SMP_BENCH_TP_PROBE=1: overlapped-tensor-parallelism A/B at tp=2 —
    GSPMD (tp_overlap off) vs the ring decomposition vs ring + fused
    kernels (Pallas fused QKV + bias-GELU), on the smp.nn transformer
    family the ring lives in.

    Same interleaved-blocks methodology as the pipeline/zero probes
    (each block re-inits — the knob changes the compiled program — and
    pays its compile in warmup, outside the timed region). Emits one
    stderr JSON line {"component": "tp_overlap", off_ms, ring_ms,
    ring_fused_ms, speedup_ring, ...} plus the ring leg's X-ray
    ``tp_overlap`` block, and returns the dict for the stdout result
    block. The pass criterion is a TPU criterion recorded in
    BENCH_NOTES.md Round 15.
    """
    import jax

    if len(jax.devices()) < 2:
        sys.stderr.write(
            "bench: skipping tp probe (needs >= 2 devices for tp=2).\n")
        return None
    if deadline - time.time() < 180:
        sys.stderr.write(
            f"bench: skipping tp probe ({deadline - time.time():.0f}s "
            "left in window < 180s floor).\n")
        return None
    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.nn.cross_entropy import (
        vocab_parallel_cross_entropy,
    )
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    n_layers, d_model, n_heads, hd, ff, seq, vocab = (
        8, 1024, 16, 64, 4096, 1024, 32000
    )
    batch = 8
    iters = 10

    def build(extra, fused_model=False):
        smp.reset()
        cfg = {"microbatches": 2, "ddp": True,
               "tensor_parallel_degree": 2, "bf16": True}
        cfg.update(extra)
        smp.init(cfg)
        model = smp.DistributedModel(DistributedTransformerLMHead(
            num_layers=n_layers, num_attention_heads=n_heads,
            attention_head_size=hd, hidden_size=d_model,
            intermediate_size=ff, vocab_size=vocab, num_positions=seq,
            causal_mask_size=seq, pre_layernorm=True,
            post_layernorm=False, final_layernorm=True,
            attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0, fused_bias_gelu=fused_model,
        ))
        optimizer = smp.DistributedOptimizer(optax.sgd(1e-3), model)
        ids = jax.random.randint(jax.random.key(0), (batch, seq), 0, vocab)

        @smp.step
        def train_step(model, b):
            logits = model(b)
            loss = jnp.mean(
                vocab_parallel_cross_entropy(logits[:, :-1], b[:, 1:])
            )
            model.backward(loss)
            return loss

        return model, optimizer, train_step, ids

    variants = (
        ("off", {}, False),
        ("ring", {"tp_overlap": "ring"}, False),
        ("ring_fused", {"tp_overlap": "ring", "fused_qkv": True}, True),
    )
    times = {name: [] for name, _, _ in variants}
    tp_block = None

    def _pallas_qkv_dispatches():
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            telemetry,
        )

        fam = telemetry.report()["metrics"].get(
            "smp_fused_kernel_dispatch_total"
        )
        return sum(
            s["value"] for s in (fam["series"] if fam else ())
            if s["labels"].get("kernel") == "qkv"
            and s["labels"].get("path") == "pallas"
        )

    # Measured, not assumed: did the ring_fused leg's trace actually
    # dispatch the Pallas QKV kernel? (It won't off-TPU, or when
    # use_pallas_kernels is disabled, or when no VMEM tile fits.)
    fused_engaged = False
    for _round in range(3):
        for name, extra, fused_model in variants:
            model, optimizer, train_step, ids = build(
                extra, fused_model=fused_model
            )
            out = None
            d0 = _pallas_qkv_dispatches() if fused_model else 0
            for _ in range(2):     # warmup: compile + first dispatch
                out = train_step(model, ids)
                optimizer.step()
            _readback(out.reduce_mean())
            if fused_model and _pallas_qkv_dispatches() > d0:
                fused_engaged = True
            if name == "ring" and tp_block is None:
                audit = hlo_audit.of_step_function(train_step)
                if audit is not None:
                    tp_block = audit.tp_overlap
            t0 = time.perf_counter()
            for _ in range(iters):
                out = train_step(model, ids)
                optimizer.step()
            _readback(out.reduce_mean())
            times[name].append((time.perf_counter() - t0) / iters)
        if time.time() > deadline:
            sys.stderr.write(
                "bench: tp probe hit the window deadline; using the "
                f"{len(times['ring'])} block round(s) measured so far.\n")
            break
    smp.reset()

    med = {name: _median(ts) for name, ts in times.items()}
    result = {
        "component": "tp_overlap",
        "tp": 2,
        "off_ms": round(med["off"] * 1e3, 3),
        "ring_ms": round(med["ring"] * 1e3, 3),
        "ring_fused_ms": round(med["ring_fused"] * 1e3, 3),
        "speedup_ring": round(med["off"] / med["ring"], 4),
        "speedup_fused": round(med["off"] / med["ring_fused"], 4),
        "tp_overlap": tp_block,
        "fused_engaged": fused_engaged,
        "blocks": len(times["ring"]),
    }
    sys.stderr.write(json.dumps(result) + "\n")
    sys.stderr.flush()
    return result


def _compile_cache_probe(deadline):
    """SMP_BENCH_COMPILE_PROBE=1: cold/warm compile A/B through the
    persistent executable cache (smp.exec_cache).

    Builds one small step config twice: the first build compiles fresh
    and stores the executable; the second (after a full smp.reset, the
    in-process analogue of a cold start) deserializes it from disk.
    ``cold_s``/``warm_s`` are the compile-phase walls (XLA compile vs
    deserialize+verify — the cost the cache removes; trace+lower is paid
    identically by both legs and reported as ``lower_s``);
    ``cold_wall_s``/``warm_wall_s`` are the full first-call walls a
    recovering/resuming job actually waits. Emits one stderr JSON line
    and returns the block stamped into BENCH_r*.json as ``"exec_cache"``
    (schema-checked by scripts/perf_ledger.py)."""
    import shutil
    import tempfile

    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.gpt2 import gpt2_124m
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    if time.time() > deadline - 30:
        sys.stderr.write(
            "bench: compile probe skipped (probe window exhausted)\n"
        )
        return None
    user_dir = os.environ.get("SMP_EXEC_CACHE_DIR")
    prev_on = os.environ.get("SMP_EXEC_CACHE")
    tmp = None
    if user_dir is None:
        tmp = tempfile.mkdtemp(prefix="smp_exec_cache_bench_")
    os.environ["SMP_EXEC_CACHE"] = "on"
    os.environ["SMP_EXEC_CACHE_DIR"] = user_dir or tmp
    try:
        seq, batch = 64, 4
        ids = None

        def run_once():
            nonlocal ids
            smp.reset()
            smp.init({"microbatches": 2})
            import jax as _jax

            model = smp.DistributedModel(gpt2_124m(
                max_len=seq, d_model=128, n_layers=2, n_heads=4,
            ))
            optimizer = smp.DistributedOptimizer(optax.adamw(1e-4), model)

            @smp.step
            def train_step(model, batch_ids):
                logits = model(batch_ids)
                loss = jnp.mean(logits.astype(jnp.float32) ** 2)
                model.backward(loss)
                return loss

            if ids is None:
                ids = _jax.random.randint(
                    _jax.random.key(0), (batch, seq), 0, 50257
                )
            t0 = time.perf_counter()
            out = train_step(model, ids)
            optimizer.step()
            loss = _readback(out.reduce_mean())
            wall = time.perf_counter() - t0
            # Per-leg telemetry (run_once reset the registry on entry, so
            # only this leg's series exist).
            rep = telemetry.report()["metrics"]

            def _hsum(name, **labels):
                for s in rep.get(name, {"series": []})["series"]:
                    if all(s["labels"].get(k) == v
                           for k, v in labels.items()):
                        return s.get("sum", 0.0)
                return 0.0

            fam = rep.get("smp_exec_cache_total", {"series": []})
            outcomes = {
                s["labels"]["result"]: s["value"] for s in fam["series"]
            }
            return {
                "wall": wall, "loss": loss, "outcomes": outcomes,
                "fresh": _hsum("smp_step_compile_seconds", source="fresh"),
                "cached": _hsum(
                    "smp_step_compile_seconds", source="disk_cache"
                ),
                "lower": _hsum("smp_step_lower_seconds"),
            }

        cold = run_once()   # fresh compile + store
        warm = run_once()   # deserialize from disk
        hit = warm["outcomes"].get("hit", 0) >= 1
        if not hit:
            sys.stderr.write(
                "bench: compile probe's warm leg did NOT hit the cache "
                f"(outcomes {warm['outcomes']}) — speedup below reflects "
                "a recompile, not a warm start.\n"
            )
        cold_s = cold["fresh"]
        warm_s = warm["cached"] if hit else warm["fresh"]
        result = {
            "component": "exec_cache",
            "cold_s": round(cold_s, 3),
            "warm_s": round(warm_s, 3),
            "speedup": round(cold_s / warm_s, 3) if warm_s > 0 else None,
            "lower_s": round(warm["lower"], 3),
            "cold_wall_s": round(cold["wall"], 3),
            "warm_wall_s": round(warm["wall"], 3),
            "cache_hit": bool(hit),
            "bit_identical": bool(cold["loss"] == warm["loss"]),
        }
        sys.stderr.write(json.dumps(result) + "\n")
        sys.stderr.flush()
        return result
    finally:
        smp.reset()
        if prev_on is None:
            os.environ.pop("SMP_EXEC_CACHE", None)
        else:
            os.environ["SMP_EXEC_CACHE"] = prev_on
        if user_dir is None:
            os.environ.pop("SMP_EXEC_CACHE_DIR", None)
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


def _serve_probe(deadline):
    """SMP_BENCH_SERVE_PROBE=1: static-batch ``smp.generate`` vs
    continuous batching (``smp.serving``) on a synthetic ragged-arrival
    trace.

    The trace is 12 greedy requests with ragged decode lengths arriving
    ``gap_s`` apart. The static baseline serves them the only way
    ``smp.generate`` can: FIFO batches of ``slots`` requests, each batch
    waiting for its last member to arrive and running to the batch's MAX
    max_new_tokens (short rows ride along as wasted steps, and nothing
    streams until the batch completes). Continuous batching admits each
    request on arrival, backfills freed slots, and retires rows at their
    own length. Token parity is asserted row-for-row (greedy), compile is
    excluded from both legs (warmed up beforehand), and the block stamped
    into BENCH_r*.json as ``"serving"`` carries
    ttft/itl mean + p50/p95/p99 and tokens_per_sec/speedup
    (schema-checked by scripts/perf_ledger.py). The probe also arms the
    observability artifacts: the metrics time-series JSONL
    (smp_serve_timeseries.jsonl, with idle tail windows so windowed
    tok/s visibly diverges from the lifetime rate) and the fused
    per-request span trace (smp_serve_trace.json via scripts/trace_fuse
    over the flight-ring dump). TPU criterion in BENCH_NOTES.md: same
    structure at serving batch sizes."""
    import numpy as np

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )

    if time.time() > deadline - 30:
        sys.stderr.write(
            "bench: serve probe skipped (probe window exhausted)\n"
        )
        return None
    # Arm the time-series feed AND the fleet metrics plane for the probe
    # run (caller env wins); restored in the finally so the probe leaves
    # no trace in os.environ. SMP_METRICS_PORT=0 binds an ephemeral port
    # so the probe can round-trip the /fleet scrape endpoint.
    ts_env_prev = {
        k: os.environ.get(k)
        for k in ("SMP_TIMESERIES_INTERVAL", "SMP_TIMESERIES_PATH",
                  "SMP_FLEET_INTERVAL", "SMP_FLEET_PATH",
                  "SMP_METRICS_PORT")
    }
    os.environ.setdefault("SMP_TIMESERIES_INTERVAL", "0.1")
    os.environ.setdefault(
        "SMP_TIMESERIES_PATH", "smp_serve_timeseries.jsonl"
    )
    os.environ.setdefault("SMP_FLEET_INTERVAL", "0.1")
    os.environ.setdefault("SMP_FLEET_PATH", "smp_fleet_windows.jsonl")
    os.environ.setdefault("SMP_METRICS_PORT", "0")
    if ts_env_prev["SMP_FLEET_PATH"] is None:
        # The fleet feed is append-only by design (it must survive
        # aggregator failover); when the probe owns the path, start it
        # fresh so the stamped window count is this run's.
        try:
            os.remove(os.environ["SMP_FLEET_PATH"])
        except OSError:
            pass
    engine = None
    try:
        import jax as _jax

        smp.reset()
        smp.init({})
        mod = TransformerLM(
            vocab_size=512, max_len=64, d_model=384, n_layers=4,
            n_heads=4,
        )
        # Extreme decode raggedness is where continuous batching earns
        # its keep: each FIFO batch of 4 carries one long stream, so the
        # static baseline burns batch-max steps on three retired rows AND
        # serializes the long streams across batches — the engine runs
        # the longs concurrently and backfills retired slots from the
        # queue.
        plen, slots, gap_s = 8, 4, 0.01
        max_news = [28, 4, 4, 4, 28, 4, 4, 4, 28, 4, 4, 4]
        prompts = [
            np.asarray(_jax.random.randint(
                _jax.random.key(100 + i), (plen,), 0, 128
            ))
            for i in range(len(max_news))
        ]
        params = mod.init(
            _jax.random.key(0), _jax.numpy.asarray(prompts[0])[None]
        )["params"]

        # -- static leg: FIFO batches, batch-max decode length ----------
        batches = [
            list(range(i, min(i + slots, len(max_news))))
            for i in range(0, len(max_news), slots)
        ]
        for b in batches:  # compile warmup (excluded from both legs)
            ids = _jax.numpy.asarray(np.stack([prompts[i] for i in b]))
            smp.generate(mod, ids, max(max_news[i] for i in b),
                         params=params)
        for m in set(max_news):
            # The engine's per-request key schedule is
            # split(key(seed), max_new) — prime the per-count threefry
            # compile the same way the static leg's generates were.
            _jax.random.split(_jax.random.key(0), m)
        t0 = time.perf_counter()
        static_out = {}
        static_ttft = []
        for b in batches:
            last_arrival = max(i * gap_s for i in b)
            now = time.perf_counter() - t0
            if now < last_arrival:
                time.sleep(last_arrival - now)
            ids = _jax.numpy.asarray(np.stack([prompts[i] for i in b]))
            out = np.asarray(smp.generate(
                mod, ids, max(max_news[i] for i in b), params=params
            ))
            done = time.perf_counter() - t0
            for row, i in enumerate(b):
                static_out[i] = list(out[row, plen:plen + max_news[i]])
                static_ttft.append(done - i * gap_s)
        static_wall = time.perf_counter() - t0
        useful_tokens = sum(max_news)
        static_tps = useful_tokens / static_wall

        # -- continuous leg ---------------------------------------------
        engine = smp.serving.ServingEngine(
            mod, params=params, max_slots=slots,
            block_tokens_override=8, prefill_chunk=8,
        )
        engine._program("prefill")   # compile warmup
        engine._program("decode")
        reqs = [
            smp.serving.ServeRequest(
                f"b{i}", list(map(int, prompts[i])), max_news[i],
                arrival_s=i * gap_s,
            )
            for i in range(len(max_news))
        ]
        t0 = time.perf_counter()
        results = engine.run(reqs, timeout_s=deadline - time.time())
        cont_wall = time.perf_counter() - t0
        cont_tps = useful_tokens / cont_wall

        parity = all(
            list(results[f"b{i}"]) == static_out[i]
            for i in range(len(max_news))
        )

        ts = engine.timeseries
        if ts is not None:
            # Two idle tail windows after the burst: windowed tok/s
            # decays to ~0 while the lifetime rate stays positive — the
            # divergence the autoscaler feed exists to carry.
            for _ in range(2):
                time.sleep(ts.interval)
                ts.maybe_sample()

        from smdistributed_modelparallel_tpu.utils.telemetry import (
            serve_latency_summary,
        )

        qs = (0.5, 0.95, 0.99)
        ttft = serve_latency_summary("ttft", qs=qs)
        itl = serve_latency_summary("itl", qs=qs)

        def _pct(summ, q):
            if not summ:
                return 0.0
            return round(1e3 * summ["quantiles_s"][q], 3)

        snaps = ts.snapshots() if ts is not None else []
        result = {
            "component": "serving",
            "ttft_ms": round(1e3 * ttft["mean_s"], 2) if ttft else 0.0,
            "itl_ms": round(1e3 * itl["mean_s"], 2) if itl else 0.0,
            "ttft_p50_ms": _pct(ttft, 0.5),
            "ttft_p95_ms": _pct(ttft, 0.95),
            "ttft_p99_ms": _pct(ttft, 0.99),
            "itl_p50_ms": _pct(itl, 0.5),
            "itl_p95_ms": _pct(itl, 0.95),
            "itl_p99_ms": _pct(itl, 0.99),
            "tokens_per_sec": round(cont_tps, 2),
            "static_tokens_per_sec": round(static_tps, 2),
            "static_ttft_ms": round(
                1e3 * sum(static_ttft) / len(static_ttft), 2
            ),
            "speedup": round(cont_tps / static_tps, 3),
            "requests": len(max_news),
            "decode_steps": int(engine.stats["decode_steps"]),
            "prefill_chunks": int(engine.stats["prefill_chunks"]),
            "token_parity": bool(parity),
            "timeseries_windows": len(snaps),
        }
        if snaps:
            result["tokens_per_sec_last_window"] = round(
                snaps[-1]["tokens_per_s"], 2
            )
            result["tokens_per_sec_lifetime"] = round(
                snaps[-1]["lifetime_tokens_per_s"], 2
            )

        # Fused span trace: dump the flight ring and run trace_fuse over
        # it.
        from smdistributed_modelparallel_tpu.utils.flight_recorder import (
            flight_recorder,
        )

        ring_path = flight_recorder.dump("smp_serve_flight.jsonl")
        if ring_path:
            scripts_dir = os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "scripts"
            )
            if scripts_dir not in sys.path:
                sys.path.insert(0, scripts_dir)
            import trace_fuse

            trace_fuse.main(
                ["-o", "smp_serve_trace.json", "--no-report",
                 ring_path]
            )
            stream = trace_fuse.load_stream(ring_path)
            spans, _, findings = trace_fuse.serve_request_spans(
                [e for e in stream.events if e.get("kind") == "serve"]
            )
            result["trace_slot_lanes"] = len({
                sp["tid"] for sp in spans
                if sp["tid"].startswith("slot ")
            })
            result["trace_open_spans"] = sum(
                1 for f in findings if "left open" in f
            )

        # Fleet metrics plane block: windows aggregated, straggler
        # verdicts, and a live round-trip of the /fleet scrape endpoint.
        from smdistributed_modelparallel_tpu.utils.fleet import (
            fleet as _fleet,
        )

        plane = _fleet.plane
        if plane is not None:
            plane.tick()  # ensure at least one window post-burst
            fleet_block = {
                "windows": len(plane.windows()),
                "ranks": plane.world,
                "stragglers": sorted(plane.straggling),
            }
            if plane.bound_port:
                import urllib.request

                t_rt = time.perf_counter()
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{plane.bound_port}/fleet",
                    timeout=10,
                ) as resp:
                    doc = json.loads(resp.read())
                fleet_block["endpoint_roundtrip_ms"] = round(
                    1e3 * (time.perf_counter() - t_rt), 3
                )
                ttft_doc = doc.get("percentiles", {}).get("ttft")
                if ttft_doc and ttft_doc.get("p99_s") is not None:
                    fleet_block["endpoint_ttft_p99_ms"] = round(
                        1e3 * ttft_doc["p99_s"], 3
                    )
            last = (plane.windows() or [{}])[-1]
            if last.get("slo"):
                fleet_block["goodput"] = last["slo"].get("goodput")
            result["fleet"] = fleet_block

        sys.stderr.write(json.dumps(result) + "\n")
        sys.stderr.flush()
        return result
    finally:
        for k, v in ts_env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if engine is not None:
            engine.close()
        smp.reset()


def _autoscale_probe(deadline):
    """SMP_BENCH_AUTOSCALE_PROBE=1: the same bursty ragged-arrival trace
    served by a STATIC single replica vs the SLO-driven autoscaler
    (``smp.serving.ServingController``) allowed to grow to two.

    The burst overruns one replica's two decode slots, the queue-depth
    SLO breaches for the hysteresis count, and the controller activates
    the standby replica (exec-cache warm start — the activation report's
    compile sources ride in the scale-event record); once the burst
    drains, sustained headroom scales back to one via the drain
    protocol. Token parity is asserted request-for-request against the
    static leg (zero dropped or duplicated tokens across the scale
    events), then a canaried LIVE weight update runs on the quiesced
    fleet (identical params under a new version: the parity gate must
    pass and promotion land with ZERO fresh compiles — the weight-free
    program-cache keys at work). The block stamped into BENCH_r*.json as
    ``"autoscale"`` carries scale_events / p99_ttft_ms_static /
    p99_ttft_ms_auto / weight_update_s / canary_verdict
    (schema-checked by scripts/perf_ledger.py). TPU criterion in
    BENCH_NOTES.md: same structure at serving batch sizes."""
    import numpy as np

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )

    if time.time() > deadline - 30:
        sys.stderr.write(
            "bench: autoscale probe skipped (probe window exhausted)\n"
        )
        return None
    env_prev = {
        k: os.environ.get(k)
        for k in ("SMP_AUTOSCALE", "SMP_SLO", "SMP_AUTOSCALE_COOLDOWN",
                  "SMP_AUTOSCALE_MIN", "SMP_AUTOSCALE_MAX",
                  "SMP_AUTOSCALE_HYSTERESIS", "SMP_CANARY_WINDOWS",
                  "SMP_CONTROLLER_PATH", "SMP_EXEC_CACHE",
                  "SMP_EXEC_CACHE_DIR")
    }
    os.environ["SMP_AUTOSCALE"] = "on"
    os.environ.setdefault("SMP_SLO", "queue_depth=2")
    os.environ.setdefault("SMP_AUTOSCALE_COOLDOWN", "0.3")
    os.environ.setdefault("SMP_AUTOSCALE_MIN", "1")
    os.environ.setdefault("SMP_AUTOSCALE_MAX", "2")
    os.environ.setdefault("SMP_AUTOSCALE_HYSTERESIS", "2")
    os.environ.setdefault("SMP_CANARY_WINDOWS", "1")
    os.environ.setdefault("SMP_CONTROLLER_PATH", "smp_controller.jsonl")
    os.environ.setdefault("SMP_EXEC_CACHE", "on")
    os.environ.setdefault("SMP_EXEC_CACHE_DIR", ".smp_bench_exec_cache")
    if env_prev["SMP_CONTROLLER_PATH"] is None:
        try:
            os.remove(os.environ["SMP_CONTROLLER_PATH"])
        except OSError:
            pass
    engines = []

    def _engine(mod, params, slots):
        eng = smp.serving.ServingEngine(
            mod, params=params, max_slots=slots,
            block_tokens_override=8, prefill_chunk=8,
        )
        eng._program("prefill")
        eng._program("decode")
        engines.append(eng)
        return eng

    try:
        import jax as _jax

        smp.reset()
        smp.init({})
        mod = TransformerLM(
            vocab_size=512, max_len=64, d_model=256, n_layers=2,
            n_heads=4,
        )
        plen, slots = 8, 2
        max_news = [20] * 32
        prompts = [
            np.asarray(_jax.random.randint(
                _jax.random.key(300 + i), (plen,), 0, 128
            ))
            for i in range(len(max_news))
        ]
        params = mod.init(
            _jax.random.key(0), _jax.numpy.asarray(prompts[0])[None]
        )["params"]

        # Calibrate the burst against THIS host's service rate: arrivals
        # land at 60% of the measured per-request service interval, so
        # one replica is reliably ~1.7x oversubscribed whatever the
        # machine — the queue-depth SLO must breach and the controller
        # must scale, on a laptop or a TPU host alike.  The first pass
        # only warms the engine (first-dispatch overhead inflates its
        # interval ~3x); only the second, warmed pass is timed.
        calib_eng = _engine(mod, params, slots)
        for tag in ("w", "c"):
            calib = [
                smp.serving.ServeRequest(
                    f"{tag}{i}", list(map(int, prompts[i])), max_news[i],
                )
                for i in range(4)
            ]
            t0 = time.perf_counter()
            calib_eng.run(
                calib, timeout_s=max(deadline - time.time(), 30.0)
            )
            gap_s = 0.6 * (time.perf_counter() - t0) / len(calib)

        def _reqs():
            return [
                smp.serving.ServeRequest(
                    f"a{i}", list(map(int, prompts[i])), max_news[i],
                )
                for i in range(len(max_news))
            ]

        from smdistributed_modelparallel_tpu.utils.telemetry import (
            serve_latency_summary,
        )

        def _p99_ms():
            summ = serve_latency_summary("ttft", qs=(0.5, 0.99))
            return round(1e3 * summ["quantiles_s"][0.99], 3) if summ else 0.0

        # -- static leg: ONE replica, no controller ---------------------
        static_eng = calib_eng
        static_reqs = [
            dataclasses.replace(r, arrival_s=i * gap_s)
            for i, r in enumerate(_reqs())
        ]
        static_results = static_eng.run(
            static_reqs, timeout_s=max(deadline - time.time(), 30.0)
        )
        static_tokens = {
            f"a{i}": list(static_results[f"a{i}"])
            for i in range(len(max_news))
        }
        p99_static = _p99_ms()

        # -- autoscaled leg: controller may grow 1 -> 2 -----------------
        smp.reset()   # fresh telemetry so the auto leg's p99 is its own
        smp.init({})
        eng_a = _engine(mod, params, slots)

        def _activate():
            return smp.serving.LocalReplicaHandle(
                "replica1", _engine(mod, params, slots), version=0,
            )

        wseq = [0]
        wlast = [0.0]

        def _win(ctl_router):
            now = time.perf_counter()
            if now - wlast[0] < 0.025:
                return None   # one synthetic window per 25ms
            wlast[0] = now
            wseq[0] += 1
            depth = max(
                (len(h.engine._queue) for h in ctl_router.live_handles()),
                default=0,
            )
            return {"seq": wseq[0], "t_wall": time.time(),
                    "queue_depth": depth}

        router = smp.serving.RequestRouter()
        ctl = smp.serving.ServingController.from_env(
            router=router, window_source=lambda: _win(router),
        )
        ctl.register_live(smp.serving.LocalReplicaHandle(
            "replica0", eng_a, version=0,
        ))
        ctl.add_standby("replica1", _activate)
        auto_reqs = _reqs()
        t0 = time.perf_counter()
        pending = list(range(len(auto_reqs)))
        loop_deadline = min(deadline, time.time() + 120.0)
        while time.time() < loop_deadline:
            now = time.perf_counter() - t0
            while pending and now >= pending[0] * gap_s:
                router.dispatch(auto_reqs[pending.pop(0)])
            busy = router.step_all()
            ctl.tick()
            if not pending and not busy \
                    and len(ctl.results()) >= len(auto_reqs):
                break
            if not busy:
                time.sleep(0.001)
        # Idle-tick long enough for the comfort streak to trigger the
        # drain-protocol scale-down (cooldown 0.3s + 2 windows).
        down_deadline = time.time() + 5.0
        while (ctl.replicas > 1 and time.time() < down_deadline
               and time.time() < loop_deadline):
            router.step_all()
            ctl.tick()
            time.sleep(0.01)
        p99_auto = _p99_ms()
        auto_results = ctl.results()
        parity = all(
            list(auto_results.get(rid, ())) == toks
            for rid, toks in static_tokens.items()
        )

        # -- canaried live weight update on the quiesced fleet ----------
        from smdistributed_modelparallel_tpu.utils import exec_cache

        new_params = _jax.tree_util.tree_map(lambda x: x, params)
        pinned = [
            dataclasses.replace(_reqs()[i], request_id=f"pin{i}")
            for i in (0, 1)
        ]
        mark = exec_cache.compile_event_mark()
        ctl.start_canary(new_params, version=1, pinned=pinned)
        while ctl.canary is not None and time.time() < loop_deadline:
            ctl.tick()
            time.sleep(0.01)
        fresh = sum(
            1 for e in exec_cache.compile_events_since(mark)
            if e.get("source") == "fresh"
        )
        if ctl.promotions:
            canary_verdict = "promoted"
        elif ctl.rollbacks:
            canary_verdict = "rolled_back"
        else:
            canary_verdict = "none"
        weight_update_s = 0.0
        try:
            with open(os.environ["SMP_CONTROLLER_PATH"]) as fh:
                for line in fh:
                    rec = json.loads(line)
                    if rec.get("kind") == "weight_update":
                        weight_update_s = float(rec["seconds"])
        except (OSError, ValueError):
            pass
        ctl.stop()

        result = {
            "component": "autoscale",
            "scale_events": len(ctl.scale_events),
            "p99_ttft_ms_static": p99_static,
            "p99_ttft_ms_auto": p99_auto,
            "weight_update_s": round(weight_update_s, 6),
            "canary_verdict": canary_verdict,
            "fresh_compiles": fresh,
            "token_parity": bool(parity),
            "requests": len(max_news),
            "replicas_max": max(
                (e["replicas"] for e in ctl.scale_events), default=1
            ),
        }
        sys.stderr.write(json.dumps(result) + "\n")
        sys.stderr.flush()
        return result
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        for eng in engines:
            try:
                eng.close()
            except Exception:
                pass
        smp.reset()


def _quant_probe(deadline):
    """SMP_BENCH_QUANT_PROBE=1: the low-precision A/Bs behind smp.quant.

    Two legs, each window-capped and compile-excluded:

    - **train**: bf16 vs ``matmul_precision: fp8`` (delayed-scaling e4m3
      fwd / e5m2 grad) on the smp.nn transformer family the fp8 seams
      live in — median step ms per leg, the max relative loss deviation
      over the measured trajectory (the parity number the tolerance in
      docs/README quotes), and the fp8 leg's X-ray ``quant`` census.
    - **decode**: bf16 KV pool vs ``SMP_KV_QUANT=int8`` (per-block-per-
      head scales) through the serving engine on the same greedy request
      trace — tokens/sec per leg, per-block pool bytes per leg (the
      ``smp_serve_kv_bytes`` multiplier, so the ~2x concurrency claim is
      a measured byte ratio, not an inference), and row-for-row greedy
      token parity.

    The block stamped into BENCH_r*.json as ``"quant"`` is
    schema-checked by scripts/perf_ledger.py. The pass criterion is a
    TPU criterion recorded in BENCH_NOTES.md Round 20."""
    import jax
    import numpy as np

    if time.time() > deadline - 30:
        sys.stderr.write(
            "bench: quant probe skipped (probe window exhausted)\n"
        )
        return None
    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.nn.cross_entropy import (
        vocab_parallel_cross_entropy,
    )
    from smdistributed_modelparallel_tpu.nn.transformer import (
        DistributedTransformerLMHead,
    )
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    n_layers, d_model, n_heads, hd, ff, seq, vocab = (
        8, 1024, 16, 64, 4096, 1024, 32000
    )
    batch = 8
    iters = 10
    env_prev = {k: os.environ.get(k)
                for k in ("SMP_KV_QUANT", "SMP_DECODE_WEIGHTS")}
    try:
        # ---- train leg: bf16 vs fp8 -----------------------------------
        def build(precision):
            smp.reset()
            smp.init({"microbatches": 2, "ddp": True,
                      "bf16": True,
                      "matmul_precision": precision})
            model = smp.DistributedModel(DistributedTransformerLMHead(
                num_layers=n_layers, num_attention_heads=n_heads,
                attention_head_size=hd, hidden_size=d_model,
                intermediate_size=ff, vocab_size=vocab,
                num_positions=seq, causal_mask_size=seq,
                pre_layernorm=True, post_layernorm=False,
                final_layernorm=True, attention_dropout_prob=0.0,
                hidden_dropout_prob=0.0, embedding_dropout_prob=0.0,
            ))
            optimizer = smp.DistributedOptimizer(optax.sgd(1e-3), model)
            ids = jax.random.randint(
                jax.random.key(0), (batch, seq), 0, vocab
            )

            @smp.step
            def train_step(model, b):
                logits = model(b)
                loss = jnp.mean(
                    vocab_parallel_cross_entropy(logits[:, :-1], b[:, 1:])
                )
                model.backward(loss)
                return loss

            return model, optimizer, train_step, ids

        times = {"bf16": [], "fp8": []}
        losses = {"bf16": [], "fp8": []}
        quant_xray = None
        for _round in range(3):
            for precision in ("bf16", "fp8"):
                model, optimizer, train_step, ids = build(precision)
                out = None
                for _ in range(2):   # warmup: compile + first dispatch
                    out = train_step(model, ids)
                    optimizer.step()
                _readback(out.reduce_mean())
                if precision == "fp8" and quant_xray is None:
                    audit = hlo_audit.of_step_function(train_step)
                    if audit is not None:
                        quant_xray = audit.quant
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = train_step(model, ids)
                    optimizer.step()
                    if _round == 0:
                        losses[precision].append(
                            float(out.reduce_mean())
                        )
                if _round > 0:
                    _readback(out.reduce_mean())
                times[precision].append(
                    (time.perf_counter() - t0) / iters
                )
            if time.time() > deadline:
                sys.stderr.write(
                    "bench: quant train leg hit the window deadline; "
                    f"using the {len(times['fp8'])} round(s) measured "
                    "so far.\n")
                break
        med = {k: _median(v) for k, v in times.items()}
        n_cmp = min(len(losses["bf16"]), len(losses["fp8"]))
        loss_rel = max(
            (abs(losses["fp8"][i] - losses["bf16"][i])
             / max(abs(losses["bf16"][i]), 1e-12)
             for i in range(n_cmp)),
            default=0.0,
        )
        train_block = {
            "bf16_ms": round(med["bf16"] * 1e3, 3),
            "fp8_ms": round(med["fp8"] * 1e3, 3),
            "speedup_fp8": round(med["bf16"] / med["fp8"], 4),
            "loss_rel_diff": round(loss_rel, 6),
            "steps_compared": n_cmp,
            "quant_xray": quant_xray,
        }

        # ---- decode leg: bf16 KV vs int8 KV ---------------------------
        from smdistributed_modelparallel_tpu.models.transformer_lm import (
            TransformerLM,
        )

        plen = 8
        max_news = [16, 12, 16, 12, 16, 12]
        prompts = [
            list(map(int, np.asarray(jax.random.randint(
                jax.random.key(200 + i), (plen,), 0, 128
            ))))
            for i in range(len(max_news))
        ]

        def serve(kv_mode):
            if kv_mode == "none":
                os.environ.pop("SMP_KV_QUANT", None)
            else:
                os.environ["SMP_KV_QUANT"] = kv_mode
            smp.reset()
            smp.init({})
            mod = TransformerLM(
                vocab_size=512, max_len=64,
                d_model=384, n_layers=4, n_heads=4,
            )
            params = mod.init(
                jax.random.key(0), jnp.asarray(prompts[0])[None]
            )["params"]
            engine = smp.serving.ServingEngine(
                mod, params=params, max_slots=3,
                block_tokens_override=8, prefill_chunk=8,
            )
            engine._program("prefill")   # compile warmup
            engine._program("decode")
            reqs = [
                smp.serving.ServeRequest(f"q{i}", prompts[i], max_news[i])
                for i in range(len(max_news))
            ]
            t0 = time.perf_counter()
            results = engine.run(
                reqs, timeout_s=max(deadline - time.time(), 30)
            )
            wall = time.perf_counter() - t0
            toks = {
                rid: list(map(int, results[rid])) for rid in results
            }
            tps = sum(max_news) / wall
            bb = engine.kv_block_bytes
            engine.close()
            return toks, tps, bb

        base_toks, base_tps, base_bb = serve("none")
        kv_toks, kv_tps, kv_bb = serve("int8")
        decode_block = {
            "bf16_tokens_per_sec": round(base_tps, 2),
            "int8_kv_tokens_per_sec": round(kv_tps, 2),
            "speedup_kv": round(kv_tps / base_tps, 4),
            "kv_block_bytes_bf16": int(base_bb),
            "kv_block_bytes_int8": int(kv_bb),
            "kv_bytes_ratio": round(kv_bb / base_bb, 4),
            "token_parity": bool(kv_toks == base_toks),
            "requests": len(max_news),
        }

        result = {
            "component": "quant",
            "train": train_block,
            "decode": decode_block,
            }
        sys.stderr.write(json.dumps(result) + "\n")
        sys.stderr.flush()
        return result
    finally:
        for k, v in env_prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        smp.reset()


# Seconds from the start of a run inside which the optional probes may
# still begin; one that would start later is skipped and says so.
_PROBE_WINDOW_S = 1200


def main():
    probe_deadline = time.time() + _PROBE_WINDOW_S
    # Arm the wall-clock attribution ledger for the whole bench run; the
    # "goodput" block stamped below is schema-checked by perf_ledger.py.
    os.environ.setdefault("SMP_GOODPUT", "1")
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"bench: found no TPU (JAX reports platform {device.platform!r});"
            " a benchmark number comes from the chip or not at all."
        )
    from smdistributed_modelparallel_tpu.utils import compile_cache

    compile_cache.configure()
    import jax.numpy as jnp
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.models.gpt2 import gpt2_124m

    n_chips = len(jax.devices())
    seq_len = 1024
    batch = 8
    num_mb = 4
    d_model, n_layers, vocab = (768, 12, 50257)
    iters = 10

    def ce_loss(logits, ids):
        # logsumexp form: the [N, V] fp32 log-softmax is never materialized
        # (the cast+reduce fuse); only the [N] lse and gathered target
        # logits are. Used by BOTH the plain-JAX baseline and the framework.
        lg = logits[:, :-1]
        tgt = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
        lse = jax.scipy.special.logsumexp(lg.astype(jnp.float32), axis=-1)
        return jnp.mean(lse - tgt.astype(jnp.float32))

    ids = jax.random.randint(jax.random.key(0), (batch, seq_len), 0, vocab)

    # ---- plain-JAX baseline (the "without framework" reference point) ----
    module = gpt2_124m(max_len=seq_len)
    params0 = jax.jit(module.init)(jax.random.key(0), ids)["params"]
    tx = optax.adamw(1e-4)

    def base_loss(params, mb):
        params = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.bfloat16)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)
        return ce_loss(module.apply({"params": params}, mb), mb)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def base_train(params, opt_state, ids):
        mbs = ids.reshape(num_mb, batch // num_mb, seq_len)

        def body(acc, mb):
            loss, g = jax.value_and_grad(base_loss)(params, mb)
            return jax.tree_util.tree_map(jnp.add, acc, g), loss

        acc0 = jax.tree_util.tree_map(jnp.zeros_like, params)
        grads, losses = jax.lax.scan(body, acc0, mbs)
        grads = jax.tree_util.tree_map(lambda g: g / num_mb, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, jnp.mean(losses)

    opt_state0 = jax.jit(tx.init)(params0)
    p, o, l = base_train(params0, opt_state0, ids)
    _readback(l)

    # ---- framework setup ----
    # fused_step_donation: the plain-JAX baseline donates params/opt_state
    # through its step (donate_argnums above); the framework plays by the
    # same rules — one launch, donated buffers.
    smp.reset()
    smp.init({"microbatches": num_mb, "bf16": True,
              "fused_step_donation": True})
    model = smp.DistributedModel(gpt2_124m(max_len=seq_len))
    optimizer = smp.DistributedOptimizer(optax.adamw(1e-4), model)

    @smp.step
    def train_step(model, batch_ids):
        # Loss mode (model(ids, targets=...)): per-token losses from the
        # model's own head — same mean-over-predicted-positions loss as
        # the baseline. Which CE path runs is `fused_ce: auto`'s call
        # (nn/cross_entropy._want_fused_ce): the blockwise Pallas kernel
        # only once the logits would pass its capacity threshold (2 GB by
        # default). At this shape (2 x 1024 rows a microbatch x 50,257,
        # about 0.2 GB in bf16) auto materializes the logits, so the fused
        # CE kernel is NOT in this program; chip_smoke.py runs it once
        # under `fused_ce: True`.
        tgt = jnp.concatenate(
            [batch_ids[:, 1:], jnp.full_like(batch_ids[:, :1], -100)],
            axis=1,
        )
        per = model(batch_ids, targets=tgt)
        loss = jnp.sum(per) / (per.shape[0] * (per.shape[1] - 1))
        model.backward(loss)
        return loss

    out = None
    for _ in range(2):
        out = train_step(model, ids)
        optimizer.step()
    _readback(out.reduce_mean())

    # Pipeline schedule of the headline config, captured NOW (the probes
    # below re-init and reset the framework): "none" while the headline
    # runs unpipelined, the cfg knob once it moves to pp >= 2.
    from smdistributed_modelparallel_tpu.backend.state import state as _state

    headline_schedule = (
        _state.cfg.pipeline
        if _state.cfg is not None and _state.cfg.pipeline_parallel_degree > 1
        else "none"
    )

    # ---- interleaved timing (A/B/A/B) ----
    # Chip clock/thermal state drifts over tens of seconds; timing all
    # baseline iterations then all framework iterations folds that drift
    # straight into vs_baseline. Alternating blocks exposes both paths to
    # the same conditions; medians are robust to one slow block.
    base_times, times = [], []
    final_loss = None
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            p, o, l = base_train(p, o, ids)
        _readback(l)
        base_times.append((time.perf_counter() - t0) / iters)

        t0 = time.perf_counter()
        for _ in range(iters):
            out = train_step(model, ids)
            optimizer.step()
        final_loss = _readback(out.reduce_mean())
        times.append((time.perf_counter() - t0) / iters)
    base_dt = sorted(base_times)[1]  # median of 3 repeats
    dt = sorted(times)[1]
    del p, o

    if os.environ.get("SMP_BENCH_HEALTH_PROBE", "0") == "1":
        # The optional probes share one window from the start of the run.
        _health_overhead_probe(
            train_step, model, optimizer, ids, iters,
            deadline=probe_deadline,
        )

    tokens = batch * seq_len
    tok_per_sec_chip = tokens / dt / max(n_chips, 1)
    base_tok_per_sec = tokens / base_dt / max(n_chips, 1)

    flops = _model_flops_per_step(n_layers, d_model, vocab, batch, seq_len)
    peak = _chip_peak_tflops(device)
    mfu = (flops / dt / 1e12) / peak

    # Roofline attribution (smp.profiling): analytic model FLOPs (the MFU
    # definition above, unchanged across rounds) joined with the compiled
    # step's bytes-accessed and the measured step time into the
    # compute/comm/bubble decomposition — recorded in every BENCH_r*.json
    # block so rounds feed scripts/perf_ledger.py without hand arithmetic.
    from smdistributed_modelparallel_tpu.utils import profiling

    compiled_exec = next(iter(train_step._cache.values())).holder["compiled"]
    rep = profiling.roofline(
        "bench", step_time_s=dt, flops=float(flops),
        compiled=compiled_exec,
        peak_flops=peak * 1e12,
    )
    rd = rep.as_dict()
    roofline_out = {
        k: (round(v, 6) if isinstance(v, float) else v)
        for k, v in rd.items()
        if k in ("mfu", "bytes_accessed", "arithmetic_intensity",
                 "ridge_intensity", "bound", "compute_s", "memory_s",
                 "bubble_fraction", "bubble_s", "comm_s",
                 "achieved_flops_per_s", "achieved_bytes_per_s")
    }

    # Compiled-program X-ray (smp.xray): the headline program's audit
    # summary — collective ops/bytes by kind, remat fraction, replication
    # findings, and the program fingerprint — stamped into every
    # BENCH_r*.json so scripts/perf_ledger.py can flag fingerprint drift
    # between rounds (a schedule/sharding change that nobody documented).
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    hlo_audit_out = hlo_audit.bench_summary(
        hlo_audit.of_step_function(train_step)
    )

    # Optional component breakdown (stderr; stdout stays one JSON line).
    # SMP_BENCH_BREAKDOWN=1 localizes the MFU gap: fwd-only vs fwd+bwd vs
    # full step isolates optimizer+update cost; the attention and LM-head
    # microbenches bound the two dominant matmul groups. SMP_BENCH_PROFILE
    # =<dir> additionally captures an XLA trace of the framework loop.
    if os.environ.get("SMP_BENCH_BREAKDOWN", "0") == "1":
        def timeit(f, *a, reps=20):
            f(*a)
            _readback(jax.tree_util.tree_leaves(f(*a))[0])
            t0 = time.perf_counter()
            for _ in range(reps):
                out_ = f(*a)
            _readback(jax.tree_util.tree_leaves(out_)[0])
            return (time.perf_counter() - t0) / reps * 1e3

        bp = jax.tree_util.tree_map(
            lambda p_: p_.astype(jnp.bfloat16)
            if jnp.issubdtype(p_.dtype, jnp.floating) else p_, model.params)
        mb = ids[: batch // num_mb]

        # Same loss path as the timed step (model loss mode, so the CE
        # dispatch policy applies identically) — the microbench must
        # decompose the step it is compared against.
        def _loss(p_, i_):
            tgt = jnp.concatenate(
                [i_[:, 1:], jnp.full_like(i_[:, :1], -100)], axis=1)
            per = model.module.apply({"params": p_}, i_, targets=tgt)
            return jnp.sum(per) / (per.shape[0] * (per.shape[1] - 1))

        fwd = jax.jit(_loss)
        fwdbwd = jax.jit(jax.grad(_loss))

        from smdistributed_modelparallel_tpu.ops.attention import attention_core

        # Random operands passed as ARGUMENTS: zeros (or closed-over
        # constants) let XLA fold the matmuls away and time nothing.
        kq = jax.random.key(7)
        qkv = jax.random.normal(
            kq, (batch // num_mb, seq_len, 12, 64), jnp.bfloat16)
        attn = jax.jit(jax.grad(lambda q_: jnp.sum(
            attention_core(q_, q_, q_, causal=True).astype(jnp.float32))))

        h = jax.random.normal(
            kq, (batch // num_mb * seq_len, d_model), jnp.bfloat16)
        wte = jax.random.normal(kq, (vocab, d_model), jnp.bfloat16)
        tgt = ids[: batch // num_mb].reshape(-1)
        head_fn = jax.jit(jax.grad(lambda h_, w_: jnp.sum(
            ce_loss((h_ @ w_.T)[None], tgt[None])), argnums=(0, 1)))

        for name_, ms in [
            ("fwd_only_microbatch", timeit(fwd, bp, mb)),
            ("fwd_bwd_microbatch", timeit(fwdbwd, bp, mb)),
            ("attention_fwdbwd_microbatch", timeit(attn, qkv)),
            ("lmhead_ce_fwdbwd_microbatch", timeit(head_fn, h, wte)),
        ]:
            sys.stderr.write(json.dumps(
                {"component": name_, "ms": round(ms, 3)}) + "\n")
        sys.stderr.flush()

    prof_dir = os.environ.get("SMP_BENCH_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            for _ in range(3):
                out = train_step(model, ids)
                optimizer.step()
            _readback(out.reduce_mean())
        sys.stderr.write(f"bench: profile written to {prof_dir}\n")

    pipeline_probe_out = None
    if os.environ.get("SMP_BENCH_PIPELINE_PROBE", "0") == "1":
        # Last probe: it re-inits the framework (virtual_pipeline_degree
        # changes the partitioning), so the single-chip model/step above
        # must not be used after it.
        pipeline_probe_out = _pipeline_interleave_probe(
            deadline=probe_deadline
        )

    zero_probe_out = None
    if os.environ.get("SMP_BENCH_ZERO_PROBE", "0") == "1":
        # Re-inits the framework per block (the sharding mode changes the
        # compiled program); the headline model/step must not be reused
        # afterwards.
        zero_probe_out = _zero_probe(deadline=probe_deadline)

    tp_probe_out = None
    if os.environ.get("SMP_BENCH_TP_PROBE", "0") == "1":
        # Re-inits the framework per block (tp_overlap changes the
        # compiled program); the headline model/step must not be reused
        # afterwards.
        tp_probe_out = _tp_probe(deadline=probe_deadline)

    exec_cache_out = None
    if os.environ.get("SMP_BENCH_COMPILE_PROBE", "0") == "1":
        # Also re-inits the framework; anything after this point must not
        # touch the headline model/step objects.
        exec_cache_out = _compile_cache_probe(
            deadline=probe_deadline
        )

    serving_out = None
    if os.environ.get("SMP_BENCH_SERVE_PROBE", "0") == "1":
        # Also re-inits the framework (single-device serving config).
        serving_out = _serve_probe(deadline=probe_deadline)

    autoscale_out = None
    if os.environ.get("SMP_BENCH_AUTOSCALE_PROBE", "0") == "1":
        # Also re-inits the framework (single-device serving config).
        autoscale_out = _autoscale_probe(deadline=probe_deadline)

    quant_out = None
    if os.environ.get("SMP_BENCH_QUANT_PROBE", "0") == "1":
        # Also re-inits the framework (the precision knob changes the
        # compiled step program).
        quant_out = _quant_probe(deadline=probe_deadline)

    # Read from the executable that was timed, not from the dispatcher.
    attn_path = (
        "pallas_flash" if "smp_flash_fwd" in compiled_exec.as_text()
        else "xla_jnp"
    )

    result = {
        "metric": "tokens/sec/chip GPT-2-124M train step",
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": n_chips},
        "value": round(tok_per_sec_chip, 2),
        "unit": "tokens/sec/chip",
        # Pipeline schedule of the headline config (pp=1 runs none); the
        # perf ledger carries this so rounds that move the schedule knob
        # stay attributable.
        "schedule": headline_schedule,
        "vs_baseline": round(tok_per_sec_chip / base_tok_per_sec, 3),
        "baseline_def": "plain-JAX same-model train step, same run",
        "plain_jax_tokens_per_sec_chip": round(base_tok_per_sec, 2),
        "step_ms": round(dt * 1e3, 1),
        "mfu": round(mfu, 4),
        "model_tflops_per_step": round(flops / 1e12, 3),
        "chip_peak_bf16_tflops": peak,
        "attention_path": attn_path,
        "roofline": roofline_out,
        "hlo_audit": hlo_audit_out,
        "final_loss": round(final_loss, 4),
    }
    from smdistributed_modelparallel_tpu.utils.goodput import goodput

    gp_block = goodput.bench_block()
    if gp_block is not None:
        result["goodput"] = gp_block
    if exec_cache_out is not None:
        result["exec_cache"] = exec_cache_out
    if serving_out is not None:
        result["serving"] = serving_out
    if autoscale_out is not None:
        result["autoscale"] = autoscale_out
    if quant_out is not None:
        result["quant"] = quant_out
    if zero_probe_out is not None:
        result["zero_probe"] = zero_probe_out
    if tp_probe_out is not None:
        result["tp_overlap"] = tp_probe_out
    if pipeline_probe_out is not None:
        result["pipeline_probe"] = pipeline_probe_out
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
