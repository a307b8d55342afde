#!/usr/bin/env python
"""Perf-regression ledger: one tracked trajectory over every bench round.

Usage:
    python scripts/perf_ledger.py [--repo DIR]            # table + verdict
    python scripts/perf_ledger.py --json                  # verdict JSON only
    python scripts/perf_ledger.py --check [--threshold F] # CI gate (rc != 0
                                                          #  on any problem)

Aggregates the committed bench evidence into one machine-readable
trajectory, so chip windows land in a ledger instead of hand-read files:

- ``BENCH_r<NN>.json`` — the driver's per-round record (``n``, ``rc``,
  ``parsed`` = bench.py's stdout JSON line with value / vs_baseline /
  mfu / step_ms / roofline). ``rc != 0`` means the round produced no
  measurement.
- ``BENCH_NOTES.md`` — rounds whose JSON carries no measurement fall
  back to the notes: numbers measured DURING the round by its builder
  are recorded there in fenced code blocks under a
  ``## Round N`` heading; the ledger parses ``vs_baseline <x>`` /
  ``MFU <y>`` pairs from exactly those fenced blocks (prose mentions of
  other rounds' numbers are deliberately not parsed) and takes the best
  block per round.
- ``BASELINE.json`` — metric definition / north star, echoed in the
  verdict for context.

The verdict is one JSON object: per-round rows, the best and latest
on-chip evidence, and ``problems`` — and ``--check`` is the single entry
point the tier-1 regression gate (tests/test_profiling.py) and bench
rounds share. Checked invariants (CPU-safe, no wall-time comparisons so
CI stays unflaky):

- every ``BENCH_r*.json`` parses, with integer ``n``/``rc`` and, when
  ``rc == 0``, a parsed block with numeric ``value`` and ``vs_baseline``;
- round numbers are strictly increasing with the file order (no
  duplicates, no renumbering);
- no silent regression: a JSON-measured on-chip round whose
  ``vs_baseline`` drops more than ``--threshold`` (default 5%) below the
  previous on-chip evidence must have a ``## Round N`` entry in
  BENCH_NOTES.md explaining it (notes-sourced evidence is documented by
  construction);
- the ``exec_cache`` block (bench.py SMP_BENCH_COMPILE_PROBE: cold vs
  warm compile A/B through the persistent executable cache) is
  schema-checked when present (numeric ``cold_s``/``warm_s``/``speedup``,
  internally consistent) and rendered per round;
- the ``zero_probe`` / ``pipeline_probe`` / ``serving`` /
  ``autoscale`` / ``tp_overlap`` / ``quant`` blocks (the other bench
  probe A/Bs, SMP_BENCH_ZERO_PROBE / SMP_BENCH_PIPELINE_PROBE /
  SMP_BENCH_SERVE_PROBE / SMP_BENCH_AUTOSCALE_PROBE /
  SMP_BENCH_TP_PROBE / SMP_BENCH_QUANT_PROBE — for ``quant``, the
  bf16-vs-fp8 train-step A/B (delayed-scaling e4m3/e5m2, loss-drift
  parity) plus the bf16-vs-int8 paged-KV decode A/B (token parity and
  the measured per-block pool byte ratio); for ``tp_overlap``,
  GSPMD vs the ring decomposition vs ring + fused Pallas kernels at
  tp=2; for ``autoscale``, a bursty ragged-arrival trace served static
  vs SLO-autoscaled with a mid-run canaried weight update) are
  schema-checked when present (numeric timings, speedups
  internally consistent) and rendered per round;
- the ``goodput`` block (bench.py's wall-clock attribution ledger stamp)
  is schema-checked when present — fraction in [0, 1], per-state seconds
  that sum to the wall clock within 1% — and rendered per round;
- the ``hlo_audit`` block (bench.py >= round 9: the headline program's
  X-ray summary — fingerprint, collective ops/bytes by kind, remat
  fraction, replicated bytes) is schema-checked when present, and
  fingerprint drift between consecutive same-platform rounds without a
  ``## Round N`` notes entry is flagged: the compiled program changed
  (schedule, sharding, remat policy) and nobody documented why.

Stdlib only — runnable anywhere the repo can be copied to.
"""

import argparse
import glob
import json
import os
import re
import sys

_ROUND_FILE_RE = re.compile(r"BENCH_r(\d+)\.json$")
_NOTES_HEAD_RE = re.compile(r"^## Round (\d+)\b")
_FENCE_RE = re.compile(r"^```")
_VSB_RE = re.compile(r"vs_baseline:?\s+\*{0,2}(\d+(?:\.\d+)?)")
_MFU_RE = re.compile(r"MFU:?\s+\*{0,2}(\d+(?:\.\d+)?)")


def load_rounds(repo):
    """[(path, payload_or_error_str)] for BENCH_r*.json, filename order."""
    out = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        try:
            with open(path, encoding="utf-8") as f:
                out.append((path, json.load(f)))
        except (OSError, ValueError) as e:
            out.append((path, f"unreadable: {e}"))
    return out


def parse_notes(repo):
    """{round: [{"vs_baseline": x, "mfu": y|None}, ...]} from the fenced
    code blocks of BENCH_NOTES.md's ``## Round N`` sections.

    Only fenced blocks are measurement evidence — prose routinely quotes
    OTHER rounds' numbers ("the round-4 numbers below...") and must not
    be attributed to the section it appears in.
    """
    path = os.path.join(repo, "BENCH_NOTES.md")
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return {}
    evidence = {}
    current = None
    in_fence = False
    for line in lines:
        m = _NOTES_HEAD_RE.match(line)
        if m:
            current = int(m.group(1))
            in_fence = False
            continue
        if _FENCE_RE.match(line):
            in_fence = not in_fence
            continue
        if not (in_fence and current is not None):
            continue
        vm = _VSB_RE.search(line)
        if vm:
            mm = _MFU_RE.search(line)
            evidence.setdefault(current, []).append({
                "vs_baseline": float(vm.group(1)),
                "mfu": float(mm.group(1)) if mm else None,
            })
    return evidence


def notes_rounds(repo):
    """Round numbers that have ANY ``## Round N`` section (documented)."""
    path = os.path.join(repo, "BENCH_NOTES.md")
    try:
        with open(path, encoding="utf-8") as f:
            return {
                int(m.group(1))
                for m in (_NOTES_HEAD_RE.match(l) for l in f)
                if m
            }
    except OSError:
        return set()


def _is_on_chip(parsed):
    """bench.py labels the CPU fallback in the metric string."""
    metric = (parsed or {}).get("metric", "")
    return "CPU smoke" not in metric


def _audit_schema_problem(audit):
    """Why a round's ``hlo_audit`` block is malformed, or None. Absent
    (None) blocks are fine — rounds predating the X-ray, or a backend
    without an AOT executable."""
    if audit is None:
        return None
    if not isinstance(audit, dict):
        return f"'hlo_audit' must be an object, got {type(audit).__name__}"
    fp = audit.get("fingerprint")
    if not isinstance(fp, str) or not fp:
        return "'hlo_audit' lacks a string 'fingerprint'"
    if not isinstance(audit.get("remat_fraction"), (int, float)):
        return "'hlo_audit' lacks a numeric 'remat_fraction'"
    rb = audit.get("replicated_bytes")
    if rb is not None and not isinstance(rb, (int, float)):
        return "'hlo_audit.replicated_bytes' must be a number when present"
    for key in ("collective_ops", "collective_bytes"):
        val = audit.get(key)
        if val is not None and not (
            isinstance(val, dict)
            and all(isinstance(v, (int, float)) for v in val.values())
        ):
            return f"'hlo_audit.{key}' must map op kinds to numbers"
    return None


def _exec_cache_schema_problem(probe):
    """Why a round's ``exec_cache`` block (bench.py
    SMP_BENCH_COMPILE_PROBE cold/warm compile A/B) is malformed, or None.
    Absent blocks are fine — rounds predating the cache, or probe not
    requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'exec_cache' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "exec_cache":
        return "'exec_cache.component' must be the string 'exec_cache'"
    for key in ("cold_s", "warm_s", "speedup"):
        if not isinstance(probe.get(key), (int, float)):
            return f"'exec_cache' lacks a numeric '{key}'"
    if probe["warm_s"] > 0 and abs(
        probe["speedup"] - probe["cold_s"] / probe["warm_s"]
    ) > max(0.05 * probe["speedup"], 0.05):
        return "'exec_cache.speedup' inconsistent with cold_s/warm_s"
    return None


def _zero_probe_schema_problem(probe):
    """Why a round's ``zero_probe`` block (bench.py SMP_BENCH_ZERO_PROBE
    zero2d-vs-zero3 A/B) is malformed, or None. Absent blocks are fine —
    rounds predating ZeRO-3, or probe not requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'zero_probe' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "zero_probe":
        return "'zero_probe.component' must be the string 'zero_probe'"
    for key in ("zero2d_ms", "zero3_ms", "speedup"):
        if not isinstance(probe.get(key), (int, float)):
            return f"'zero_probe' lacks a numeric '{key}'"
    if probe["zero3_ms"] > 0 and abs(
        probe["speedup"] - probe["zero2d_ms"] / probe["zero3_ms"]
    ) > max(0.05 * probe["speedup"], 0.05):
        return "'zero_probe.speedup' inconsistent with zero2d_ms/zero3_ms"
    mem = probe.get("memory")
    if mem is not None and not isinstance(mem, dict):
        return "'zero_probe.memory' must be an object when present"
    return None


def _tp_probe_schema_problem(probe):
    """Why a round's ``tp_overlap`` block (bench.py SMP_BENCH_TP_PROBE
    GSPMD-vs-ring-vs-ring+fusions A/B at tp=2) is malformed, or None.
    Absent blocks are fine — rounds predating overlapped tp, or probe
    not requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'tp_overlap' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "tp_overlap":
        return "'tp_overlap.component' must be the string 'tp_overlap'"
    for key in ("off_ms", "ring_ms", "ring_fused_ms", "speedup_ring",
                "speedup_fused"):
        if not isinstance(probe.get(key), (int, float)):
            return f"'tp_overlap' lacks a numeric '{key}'"
    if probe["ring_ms"] > 0 and abs(
        probe["speedup_ring"] - probe["off_ms"] / probe["ring_ms"]
    ) > max(0.05 * probe["speedup_ring"], 0.05):
        return "'tp_overlap.speedup_ring' inconsistent with off_ms/ring_ms"
    if probe["ring_fused_ms"] > 0 and abs(
        probe["speedup_fused"] - probe["off_ms"] / probe["ring_fused_ms"]
    ) > max(0.05 * probe["speedup_fused"], 0.05):
        return ("'tp_overlap.speedup_fused' inconsistent with "
                "off_ms/ring_fused_ms")
    xray = probe.get("tp_overlap")
    if xray is not None and not isinstance(xray, dict):
        return "'tp_overlap.tp_overlap' (X-ray block) must be an object"
    return None


def _pipeline_probe_schema_problem(probe):
    """Why a round's ``pipeline_probe`` block (bench.py
    SMP_BENCH_PIPELINE_PROBE 3-way schedule A/B) is malformed, or None.
    Absent blocks are fine — rounds predating the stamped probe, or
    probe not requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return (
            f"'pipeline_probe' must be an object, got {type(probe).__name__}"
        )
    if probe.get("component") != "pipeline_schedule":
        return ("'pipeline_probe.component' must be the string "
                "'pipeline_schedule'")
    scheds = probe.get("schedules")
    if not (isinstance(scheds, dict) and scheds and all(
        isinstance(v, (int, float)) for v in scheds.values()
    )):
        return "'pipeline_probe.schedules' must map schedule names to ms"
    remat = probe.get("remat_fraction")
    if remat is not None:
        if not (isinstance(remat, dict) and all(
            isinstance(v, (int, float)) and 0.0 <= v <= 1.0
            for v in remat.values()
        )):
            return ("'pipeline_probe.remat_fraction' must map schedule "
                    "names to fractions in [0, 1]")
        unknown = sorted(set(remat) - set(scheds))
        if unknown:
            return ("'pipeline_probe.remat_fraction' names schedules the "
                    f"probe did not time: {unknown}")
    best = probe.get("schedule_best")
    if best is not None and best not in scheds:
        return f"'pipeline_probe.schedule_best' {best!r} not in schedules"
    return None


def _serve_probe_schema_problem(probe):
    """Why a round's ``serving`` block (bench.py SMP_BENCH_SERVE_PROBE
    static-vs-continuous-batching A/B) is malformed, or None. Absent
    blocks are fine — rounds predating the serving engine, or probe not
    requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'serving' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "serving":
        return "'serving.component' must be the string 'serving'"
    for key in ("ttft_ms", "itl_ms", "tokens_per_sec", "speedup"):
        if not isinstance(probe.get(key), (int, float)):
            return f"'serving' lacks a numeric '{key}'"
    static = probe.get("static_tokens_per_sec")
    if static is not None:
        if not isinstance(static, (int, float)):
            return "'serving.static_tokens_per_sec' must be numeric"
        if static > 0 and abs(
            probe["speedup"] - probe["tokens_per_sec"] / static
        ) > max(0.05 * probe["speedup"], 0.05):
            return ("'serving.speedup' inconsistent with "
                    "tokens_per_sec/static_tokens_per_sec")
    if probe.get("token_parity") is False:
        # A speedup at unequal outputs measures nothing.
        return "'serving.token_parity' is false — the A/B is invalid"
    # Streaming percentile columns: optional (older rounds predate the
    # histogram telemetry), but when present they must be numeric and
    # ordered — a p99 below p50 means the quantile math regressed.
    for kind in ("ttft", "itl"):
        pcts = {}
        for stat in ("p50", "p95", "p99"):
            v = probe.get(f"{kind}_{stat}_ms")
            if v is None:
                continue
            if not isinstance(v, (int, float)):
                return f"'serving.{kind}_{stat}_ms' must be numeric"
            pcts[stat] = v
        if ("p50" in pcts and "p99" in pcts
                and pcts["p99"] < pcts["p50"] - 1e-9):
            return (f"'serving.{kind}_p99_ms' < '{kind}_p50_ms' — "
                    "percentiles are not monotonic")
    # Fleet metrics-plane sub-block: optional (rounds predating the
    # fleet plane, or SMP_FLEET_INTERVAL off), but when present it must
    # show a live plane — at least one aggregated window, numeric
    # endpoint round-trip when the scrape server bound, and straggler
    # verdicts as a list of ranks.
    fb = probe.get("fleet")
    if fb is not None:
        if not isinstance(fb, dict):
            return "'serving.fleet' must be an object"
        if not isinstance(fb.get("windows"), (int, float)) \
                or fb["windows"] < 1:
            return "'serving.fleet.windows' must be a count >= 1"
        if not isinstance(fb.get("stragglers"), list):
            return "'serving.fleet.stragglers' must be a list of ranks"
        rt = fb.get("endpoint_roundtrip_ms")
        if rt is not None and not isinstance(rt, (int, float)):
            return "'serving.fleet.endpoint_roundtrip_ms' must be numeric"
    return None


def _autoscale_schema_problem(probe):
    """Why a round's ``autoscale`` block (bench.py
    SMP_BENCH_AUTOSCALE_PROBE bursty static-vs-autoscaled A/B) is
    malformed, or None. Absent blocks are fine — rounds predating the
    serving control plane, or probe not requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'autoscale' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "autoscale":
        return "'autoscale.component' must be the string 'autoscale'"
    se = probe.get("scale_events")
    if not isinstance(se, int) or se < 1:
        return ("'autoscale.scale_events' must be an integer >= 1 — a "
                "burst that never scaled measured nothing")
    for key in ("p99_ttft_ms_static", "p99_ttft_ms_auto",
                "weight_update_s"):
        if not isinstance(probe.get(key), (int, float)):
            return f"'autoscale' lacks a numeric '{key}'"
    if probe.get("weight_update_s") < 0:
        return "'autoscale.weight_update_s' must be non-negative"
    verdict = probe.get("canary_verdict")
    if verdict not in ("promoted", "rolled_back", "none"):
        return ("'autoscale.canary_verdict' must be 'promoted', "
                "'rolled_back' or 'none'")
    fresh = probe.get("fresh_compiles")
    if fresh is not None and (not isinstance(fresh, int) or fresh < 0):
        return "'autoscale.fresh_compiles' must be a count when present"
    if probe.get("token_parity") is False:
        # The scaled run must emit the same tokens as the static run —
        # a latency win at different output measures nothing.
        return "'autoscale.token_parity' is false — the A/B is invalid"
    return None


def _quant_probe_schema_problem(probe):
    """Why a round's ``quant`` block (bench.py SMP_BENCH_QUANT_PROBE
    bf16-vs-fp8 train A/B + bf16-vs-int8-KV decode A/B) is malformed,
    or None. Absent blocks are fine — rounds predating smp.quant, or
    probe not requested."""
    if probe is None:
        return None
    if not isinstance(probe, dict):
        return f"'quant' must be an object, got {type(probe).__name__}"
    if probe.get("component") != "quant":
        return "'quant.component' must be the string 'quant'"
    train = probe.get("train")
    if train is not None:
        if not isinstance(train, dict):
            return "'quant.train' must be an object when present"
        for key in ("bf16_ms", "fp8_ms", "speedup_fp8", "loss_rel_diff"):
            if not isinstance(train.get(key), (int, float)):
                return f"'quant.train' lacks a numeric '{key}'"
        if train["fp8_ms"] > 0 and abs(
            train["speedup_fp8"] - train["bf16_ms"] / train["fp8_ms"]
        ) > max(0.05 * train["speedup_fp8"], 0.05):
            return "'quant.train.speedup_fp8' inconsistent with bf16_ms/fp8_ms"
        if train["loss_rel_diff"] < 0:
            return "'quant.train.loss_rel_diff' must be non-negative"
        xray = train.get("quant_xray")
        if xray is not None and not isinstance(xray, dict):
            return "'quant.train.quant_xray' must be an object when present"
    decode = probe.get("decode")
    if decode is not None:
        if not isinstance(decode, dict):
            return "'quant.decode' must be an object when present"
        for key in ("bf16_tokens_per_sec", "int8_kv_tokens_per_sec",
                    "speedup_kv", "kv_block_bytes_bf16",
                    "kv_block_bytes_int8", "kv_bytes_ratio"):
            if not isinstance(decode.get(key), (int, float)):
                return f"'quant.decode' lacks a numeric '{key}'"
        bb = decode["kv_block_bytes_bf16"]
        if bb > 0 and abs(
            decode["kv_bytes_ratio"]
            - decode["kv_block_bytes_int8"] / bb
        ) > max(0.05 * decode["kv_bytes_ratio"], 0.005):
            return ("'quant.decode.kv_bytes_ratio' inconsistent with "
                    "kv_block_bytes_int8/kv_block_bytes_bf16")
        if decode.get("token_parity") is False:
            # A byte ratio at unequal outputs measures nothing.
            return "'quant.decode.token_parity' is false — the A/B is invalid"
    if train is None and decode is None:
        return "'quant' carries neither a 'train' nor a 'decode' leg"
    return None


def _goodput_schema_problem(block):
    """Why a round's ``goodput`` block (bench.py's wall-clock attribution
    ledger stamp) is malformed, or None. Absent blocks are fine — rounds
    predating the ledger."""
    if block is None:
        return None
    if not isinstance(block, dict):
        return f"'goodput' must be an object, got {type(block).__name__}"
    frac = block.get("fraction")
    if not isinstance(frac, (int, float)) or not 0.0 <= frac <= 1.0:
        return "'goodput.fraction' must be a number in [0, 1]"
    wall = block.get("wall_s")
    if not isinstance(wall, (int, float)) or wall < 0:
        return "'goodput.wall_s' must be a non-negative number"
    secs = block.get("seconds")
    if not isinstance(secs, dict) or not all(
        isinstance(k, str) and isinstance(v, (int, float)) and v >= -1e-9
        for k, v in secs.items()
    ):
        return ("'goodput.seconds' must map state names to non-negative "
                "seconds")
    # The ledger's core invariant travels with the stamp: attributed
    # seconds must account for the wall clock (1% + rounding slack).
    if wall > 1.0 and abs(sum(secs.values()) - wall) > max(0.01 * wall, 0.5):
        return ("'goodput.seconds' do not sum to 'wall_s' — the "
                "attribution ledger leaked time")
    for key in ("sentinel", "forensics"):
        val = block.get(key)
        if val is not None and not isinstance(val, list):
            return f"'goodput.{key}' must be a list when present"
    return None


def build_ledger(repo, threshold=0.05):
    """The full trajectory + verdict dict (see module docstring)."""
    rounds = []
    problems = []
    notes = parse_notes(repo)
    documented = notes_rounds(repo)
    last_n = None
    for path, payload in load_rounds(repo):
        name = os.path.basename(path)
        if not isinstance(payload, dict):
            problems.append(f"{name}: {payload}")
            continue
        n = payload.get("n")
        rc = payload.get("rc")
        if not isinstance(n, int) or not isinstance(rc, int):
            problems.append(f"{name}: missing integer 'n'/'rc'")
            continue
        fn = _ROUND_FILE_RE.search(name)
        if fn and int(fn.group(1)) != n:
            problems.append(f"{name}: filename round != payload n={n}")
        if last_n is not None and n <= last_n:
            problems.append(
                f"{name}: round numbering not strictly increasing "
                f"({last_n} -> {n})"
            )
        last_n = n
        parsed = payload.get("parsed")
        row = {
            "round": n,
            "rc": rc,
            "source": name,
            "status": "ok" if rc == 0 else "no_measurement",
            "on_chip": None,
            "vs_baseline": None,
            "mfu": None,
            "tokens_per_sec_chip": None,
            "step_ms": None,
            "roofline": None,
            "schedule": None,
            "hlo_audit": None,
            "exec_cache": None,
            "zero_probe": None,
            "tp_overlap": None,
            "pipeline_probe": None,
            "serving": None,
            "autoscale": None,
            "quant": None,
            "goodput": None,
            "documented": n in documented,
        }
        if rc == 0:
            if not isinstance(parsed, dict) or not isinstance(
                parsed.get("value"), (int, float)
            ) or not isinstance(parsed.get("vs_baseline"), (int, float)):
                problems.append(
                    f"{name}: rc=0 but parsed block lacks numeric "
                    "value/vs_baseline"
                )
                row["status"] = "schema_error"
            else:
                schedule = parsed.get("schedule")
                if schedule is not None and not isinstance(schedule, str):
                    problems.append(
                        f"{name}: 'schedule' must be a string when "
                        f"present, got {type(schedule).__name__}"
                    )
                    schedule = None
                audit = parsed.get("hlo_audit")
                audit_problem = _audit_schema_problem(audit)
                if audit_problem:
                    problems.append(f"{name}: {audit_problem}")
                    audit = None
                row["hlo_audit"] = audit
                probe = parsed.get("exec_cache")
                probe_problem = _exec_cache_schema_problem(probe)
                if probe_problem:
                    problems.append(f"{name}: {probe_problem}")
                    probe = None
                row["exec_cache"] = probe
                zprobe = parsed.get("zero_probe")
                zprobe_problem = _zero_probe_schema_problem(zprobe)
                if zprobe_problem:
                    problems.append(f"{name}: {zprobe_problem}")
                    zprobe = None
                row["zero_probe"] = zprobe
                tprobe = parsed.get("tp_overlap")
                tprobe_problem = _tp_probe_schema_problem(tprobe)
                if tprobe_problem:
                    problems.append(f"{name}: {tprobe_problem}")
                    tprobe = None
                row["tp_overlap"] = tprobe
                pprobe = parsed.get("pipeline_probe")
                pprobe_problem = _pipeline_probe_schema_problem(pprobe)
                if pprobe_problem:
                    problems.append(f"{name}: {pprobe_problem}")
                    pprobe = None
                row["pipeline_probe"] = pprobe
                sprobe = parsed.get("serving")
                sprobe_problem = _serve_probe_schema_problem(sprobe)
                if sprobe_problem:
                    problems.append(f"{name}: {sprobe_problem}")
                    sprobe = None
                row["serving"] = sprobe
                aprobe = parsed.get("autoscale")
                aprobe_problem = _autoscale_schema_problem(aprobe)
                if aprobe_problem:
                    problems.append(f"{name}: {aprobe_problem}")
                    aprobe = None
                row["autoscale"] = aprobe
                qprobe = parsed.get("quant")
                qprobe_problem = _quant_probe_schema_problem(qprobe)
                if qprobe_problem:
                    problems.append(f"{name}: {qprobe_problem}")
                    qprobe = None
                row["quant"] = qprobe
                gp = parsed.get("goodput")
                gp_problem = _goodput_schema_problem(gp)
                if gp_problem:
                    problems.append(f"{name}: {gp_problem}")
                    gp = None
                row["goodput"] = gp
                row.update(
                    on_chip=_is_on_chip(parsed),
                    vs_baseline=parsed["vs_baseline"],
                    mfu=parsed.get("mfu"),
                    tokens_per_sec_chip=parsed["value"],
                    step_ms=parsed.get("step_ms"),
                    roofline=parsed.get("roofline"),
                    # Pipeline schedule the round's headline number ran
                    # under (bench.py >= round 6 stamps it; older rounds
                    # predate the field and stay None): schedule-knob
                    # moves stay attributable across the trajectory.
                    schedule=schedule,
                )
        elif n in notes:
            # The driver's run produced no number, but the round DID
            # measure on chip earlier — the notes' fenced block is the
            # round's evidence (best block wins, like the round itself
            # kept its best path).
            best = max(notes[n], key=lambda e: e["vs_baseline"])
            row.update(
                status="notes",
                source=f"BENCH_NOTES.md §Round {n}",
                on_chip=True,
                vs_baseline=best["vs_baseline"],
                mfu=best["mfu"],
            )
        rounds.append(row)

    on_chip = [r for r in rounds if r["on_chip"] and r["vs_baseline"] is not None]
    # Silent-regression gate: JSON-measured on-chip drops beyond the
    # threshold need a BENCH_NOTES.md round entry.
    for prev, cur in zip(on_chip, on_chip[1:]):
        if cur["status"] != "ok":
            continue  # notes-sourced evidence is documented by construction
        drop = 1.0 - cur["vs_baseline"] / prev["vs_baseline"]
        if drop > threshold and not cur["documented"]:
            problems.append(
                f"round {cur['round']}: vs_baseline "
                f"{cur['vs_baseline']:.3f} regressed {drop * 100:.1f}% vs "
                f"round {prev['round']} ({prev['vs_baseline']:.3f}) with no "
                "BENCH_NOTES.md entry"
            )

    # Fingerprint-drift gate: a round whose compiled headline program
    # changed (different X-ray fingerprint) since the LAST round on the
    # same platform needs a BENCH_NOTES.md round entry — the program's
    # parallel structure moved and the trajectory reader deserves the
    # why. Tracked per platform (CPU smoke vs chip compile different
    # programs by design), so an interleaved off-platform round cannot
    # silence the comparison.
    last_by_platform = {}
    for cur in rounds:
        if not cur.get("hlo_audit") or cur["on_chip"] is None:
            continue
        prev = last_by_platform.get(cur["on_chip"])
        last_by_platform[cur["on_chip"]] = cur
        if prev is None:
            continue
        if (prev["hlo_audit"]["fingerprint"] != cur["hlo_audit"]["fingerprint"]
                and not cur["documented"]):
            problems.append(
                f"round {cur['round']}: compiled-program fingerprint "
                f"drifted ({prev['hlo_audit']['fingerprint']} -> "
                f"{cur['hlo_audit']['fingerprint']} since round "
                f"{prev['round']}) with no BENCH_NOTES.md entry"
            )

    best = max(on_chip, key=lambda r: r["vs_baseline"], default=None)
    latest = on_chip[-1] if on_chip else None
    baseline = {}
    try:
        with open(os.path.join(repo, "BASELINE.json"), encoding="utf-8") as f:
            b = json.load(f)
        baseline = {"metric": b.get("metric")}
    except (OSError, ValueError):
        problems.append("BASELINE.json unreadable")
    return {
        "ok": not problems,
        "baseline": baseline,
        "rounds": rounds,
        "best_on_chip": best,
        "latest_on_chip": latest,
        "threshold": threshold,
        "problems": problems,
    }


def render_table(ledger, out=sys.stdout):
    w = out.write
    w("=== perf ledger ===\n")
    if ledger["baseline"].get("metric"):
        w(f"metric: {ledger['baseline']['metric']}\n")
    w(f"\n{'round':>5}  {'status':<15}{'chip':<6}{'vs_base':>8}"
      f"{'MFU':>7}{'tok/s/chip':>12}{'step ms':>9}  source\n")
    for r in ledger["rounds"]:
        vb = f"{r['vs_baseline']:.3f}" if r["vs_baseline"] is not None else "-"
        mfu = f"{r['mfu']:.3f}" if r["mfu"] is not None else "-"
        tps = (f"{r['tokens_per_sec_chip']:,.0f}"
               if r["tokens_per_sec_chip"] is not None else "-")
        sms = f"{r['step_ms']:.1f}" if r["step_ms"] is not None else "-"
        chip = {True: "tpu", False: "cpu", None: "-"}[r["on_chip"]]
        sched = f"  [{r['schedule']}]" if r.get("schedule") else ""
        w(f"{r['round']:>5}  {r['status']:<15}{chip:<6}{vb:>8}"
          f"{mfu:>7}{tps:>12}{sms:>9}  {r['source']}{sched}\n")
        roof = r.get("roofline")
        if isinstance(roof, dict) and roof.get("mfu") is not None:
            parts = [f"mfu {roof['mfu']:.3f}"]
            for k, lbl in (("compute_s", "compute"), ("comm_s", "comm"),
                           ("bubble_s", "bubble")):
                if roof.get(k) is not None:
                    parts.append(f"{lbl} {roof[k] * 1e3:.1f}ms")
            if roof.get("bound"):
                parts.append(f"{roof['bound']}-bound")
            w(f"{'':>7}roofline: " + "  ".join(parts) + "\n")
        audit = r.get("hlo_audit")
        if isinstance(audit, dict):
            parts = [f"fp {audit.get('fingerprint', '?')}"]
            if audit.get("remat_fraction") is not None:
                parts.append(f"remat {100 * audit['remat_fraction']:.1f}%")
            cb = audit.get("collective_bytes") or {}
            for op in sorted(cb):
                parts.append(f"{op} {cb[op]:,.0f}B")
            if audit.get("replicated_bytes"):
                parts.append(f"!! replicated {audit['replicated_bytes']:,}B")
            w(f"{'':>7}xray: " + "  ".join(parts) + "\n")
        probe = r.get("exec_cache")
        if isinstance(probe, dict):
            w(f"{'':>7}exec_cache: cold {probe['cold_s']:.2f}s  warm "
              f"{probe['warm_s']:.2f}s  speedup {probe['speedup']:.1f}x\n")
        pprobe = r.get("pipeline_probe")
        if isinstance(pprobe, dict):
            remat = pprobe.get("remat_fraction") or {}
            parts = []
            for sched in sorted(pprobe.get("schedules", {})):
                ms = pprobe["schedules"][sched]
                part = f"{sched} {ms:.1f}ms"
                if sched in remat:
                    part += f" (remat {100 * remat[sched]:.0f}%)"
                parts.append(part)
            if pprobe.get("schedule_best"):
                parts.append(f"best {pprobe['schedule_best']}")
            w(f"{'':>7}pipeline_probe: " + "  ".join(parts) + "\n")
        sprobe = r.get("serving")
        if isinstance(sprobe, dict):
            parts = [
                f"ttft {sprobe['ttft_ms']:.1f}ms",
                f"itl {sprobe['itl_ms']:.1f}ms",
                f"{sprobe['tokens_per_sec']:,.0f} tok/s",
                f"speedup {sprobe['speedup']:.2f}x vs static",
            ]
            if sprobe.get("token_parity"):
                parts.append("parity ok")
            w(f"{'':>7}serving: " + "  ".join(parts) + "\n")
            for kind in ("ttft", "itl"):
                pcts = [sprobe.get(f"{kind}_{s}_ms")
                        for s in ("p50", "p95", "p99")]
                if all(isinstance(v, (int, float)) for v in pcts):
                    w(f"{'':>7}serving {kind} p50/p95/p99: "
                      f"{pcts[0]:.1f}/{pcts[1]:.1f}/{pcts[2]:.1f}ms\n")
            if sprobe.get("timeseries_windows"):
                parts = [f"{sprobe['timeseries_windows']} window(s)"]
                tw = sprobe.get("tokens_per_sec_last_window")
                if tw is not None:
                    parts.append(f"last-window {tw:,.0f} tok/s")
                tl = sprobe.get("tokens_per_sec_lifetime")
                if tl is not None:
                    parts.append(f"lifetime {tl:,.0f} tok/s")
                if sprobe.get("trace_slot_lanes") is not None:
                    parts.append(
                        f"trace lanes {sprobe['trace_slot_lanes']}"
                        f" (open spans {sprobe.get('trace_open_spans', 0)})"
                    )
                w(f"{'':>7}serving timeseries: " + "  ".join(parts) + "\n")
            fb = sprobe.get("fleet")
            if isinstance(fb, dict):
                parts = [f"{fb.get('windows', 0)} window(s)",
                         f"ranks {fb.get('ranks', 1)}"]
                if fb.get("endpoint_roundtrip_ms") is not None:
                    parts.append(
                        f"scrape rt {fb['endpoint_roundtrip_ms']:.1f}ms"
                    )
                stragglers = fb.get("stragglers") or []
                parts.append(
                    "stragglers " + (",".join(map(str, stragglers))
                                     if stragglers else "none")
                )
                if fb.get("goodput") is not None:
                    parts.append(f"goodput {100 * fb['goodput']:.0f}%")
                w(f"{'':>7}serving fleet: " + "  ".join(parts) + "\n")
        aprobe = r.get("autoscale")
        if isinstance(aprobe, dict):
            parts = [
                f"{aprobe['scale_events']} scale event(s)",
                f"p99 ttft {aprobe['p99_ttft_ms_static']:.1f}ms static "
                f"-> {aprobe['p99_ttft_ms_auto']:.1f}ms autoscaled",
                f"weight update {aprobe['weight_update_s']:.3f}s",
                f"canary {aprobe['canary_verdict']}",
            ]
            if aprobe.get("fresh_compiles") is not None:
                parts.append(
                    f"{aprobe['fresh_compiles']} fresh compile(s)"
                )
            if aprobe.get("token_parity"):
                parts.append("parity ok")
            w(f"{'':>7}autoscale: " + "  ".join(parts) + "\n")
        qprobe = r.get("quant")
        if isinstance(qprobe, dict):
            train = qprobe.get("train")
            if isinstance(train, dict):
                parts = [
                    f"bf16 {train['bf16_ms']:.1f}ms",
                    f"fp8 {train['fp8_ms']:.1f}ms",
                    f"speedup {train['speedup_fp8']:.2f}x",
                    f"loss drift {train['loss_rel_diff']:.2%}",
                ]
                xray = train.get("quant_xray") or {}
                casts = xray.get("f8_casts") or {}
                if casts:
                    parts.append(
                        f"f8 casts e4m3={casts.get('e4m3', 0)} "
                        f"e5m2={casts.get('e5m2', 0)}"
                    )
                w(f"{'':>7}quant train: " + "  ".join(parts) + "\n")
            decode = qprobe.get("decode")
            if isinstance(decode, dict):
                parts = [
                    f"bf16 {decode['bf16_tokens_per_sec']:,.0f} tok/s",
                    f"int8-kv {decode['int8_kv_tokens_per_sec']:,.0f} tok/s",
                    f"kv bytes/block {decode['kv_block_bytes_bf16']:,}B"
                    f" -> {decode['kv_block_bytes_int8']:,}B"
                    f" ({decode['kv_bytes_ratio']:.2f}x)",
                ]
                if decode.get("token_parity"):
                    parts.append("parity ok")
                w(f"{'':>7}quant decode: " + "  ".join(parts) + "\n")
        gp = r.get("goodput")
        if isinstance(gp, dict):
            parts = [
                f"{100 * gp['fraction']:.0f}% of {gp['wall_s']:.0f}s wall",
            ]
            bad = {k: v for k, v in (gp.get("seconds") or {}).items()
                   if k != "step" and v > 0}
            if bad:
                top = sorted(bad.items(), key=lambda kv: -kv[1])[:3]
                parts.append("badput " + " ".join(
                    f"{k}={v:.1f}s" for k, v in top))
            if gp.get("sentinel"):
                parts.append(f"!! {len(gp['sentinel'])} regression(s)")
            if gp.get("forensics"):
                parts.append(f"{len(gp['forensics'])} forensic bundle(s)")
            w(f"{'':>7}goodput: " + "  ".join(parts) + "\n")
        zprobe = r.get("zero_probe")
        if isinstance(zprobe, dict):
            parts = [
                f"zero2d {zprobe['zero2d_ms']:.1f}ms",
                f"zero3 {zprobe['zero3_ms']:.1f}ms",
                f"speedup {zprobe['speedup']:.2f}x",
            ]
            mem = zprobe.get("memory") or {}
            pb = {
                k: (v or {}).get("param_bytes_per_device")
                for k, v in mem.items() if isinstance(v, dict)
            }
            if pb.get("zero2d") and pb.get("zero3"):
                parts.append(
                    f"params/device {pb['zero2d']:,}B -> {pb['zero3']:,}B"
                )
            z = zprobe.get("zero") or {}
            if z.get("overlap_fraction") is not None:
                parts.append(f"overlap {100 * z['overlap_fraction']:.0f}%")
            w(f"{'':>7}zero_probe: " + "  ".join(parts) + "\n")
        tprobe = r.get("tp_overlap")
        if isinstance(tprobe, dict):
            parts = [
                f"off {tprobe['off_ms']:.1f}ms",
                f"ring {tprobe['ring_ms']:.1f}ms",
                f"ring+fused {tprobe['ring_fused_ms']:.1f}ms",
                f"speedup {tprobe['speedup_ring']:.2f}x"
                f"/{tprobe['speedup_fused']:.2f}x",
            ]
            xray = tprobe.get("tp_overlap") or {}
            if xray.get("overlap_evidence") is not None:
                parts.append(
                    "overlap proven" if xray["overlap_evidence"]
                    else "!! overlap NOT proven"
                )
            if xray.get("ring_permute_ops"):
                parts.append(f"{xray['ring_permute_ops']} ring hop(s)")
            w(f"{'':>7}tp_overlap: " + "  ".join(parts) + "\n")
    if ledger["best_on_chip"]:
        b = ledger["best_on_chip"]
        w(f"\nbest on-chip:   round {b['round']}  vs_baseline "
          f"{b['vs_baseline']:.3f}"
          + (f"  MFU {b['mfu']:.3f}" if b["mfu"] is not None else "") + "\n")
    if ledger["latest_on_chip"]:
        l = ledger["latest_on_chip"]
        w(f"latest on-chip: round {l['round']}  vs_baseline "
          f"{l['vs_baseline']:.3f}"
          + (f"  MFU {l['mfu']:.3f}" if l["mfu"] is not None else "") + "\n")
    if ledger["problems"]:
        w("\nproblems:\n")
        for p in ledger["problems"]:
            w(f"!! {p}\n")
    else:
        w("\nledger invariants hold.\n")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Aggregate BENCH_r*.json / BENCH_NOTES.md / "
        "BASELINE.json into one perf trajectory with a machine-readable "
        "verdict; --check gates on the ledger invariants."
    )
    ap.add_argument("--repo", default=None,
                    help="repo root (default: this script's parent)")
    ap.add_argument("--json", action="store_true",
                    help="print the verdict JSON instead of the table")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless every invariant holds")
    ap.add_argument("--threshold", type=float, default=0.05,
                    help="silent-regression threshold on vs_baseline "
                    "(default %(default)s)")
    args = ap.parse_args(argv)
    repo = args.repo or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    ledger = build_ledger(repo, threshold=args.threshold)
    if args.json or args.check:
        json.dump(ledger, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        render_table(ledger)
    if args.check:
        for p in ledger["problems"]:
            sys.stderr.write(f"perf_ledger: {p}\n")
        return 0 if ledger["ok"] else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
