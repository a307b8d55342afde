#!/usr/bin/env python
"""Pretty-print a step report from SMP telemetry JSON dump(s).

Usage:
    SMP_TELEMETRY_PATH=/tmp/telemetry.json python train.py ...
    python scripts/telemetry_report.py /tmp/telemetry.json
    python scripts/telemetry_report.py /tmp/telemetry.json --prometheus
    python scripts/telemetry_report.py /tmp/dumps/      # per-rank dir

Renders the run the way the reference's one-time Studio metrics upload was
read: throughput (tokens/sec), pipeline bubble fraction (measured vs the
(pp-1)/(mb+pp-1) bound), host comm volume by collective, compile-cache
behavior and compile wall time, XLA-counted FLOPs/bytes of the compiled
step, performance (the smp_mfu / smp_roofline_* gauges published by
utils/profiling.py: MFU, arithmetic intensity vs the ridge point, and
the compute/comm/bubble decomposition of the step time), the
compiled-program X-ray audit (smp_hlo_* gauges from utils/hlo_audit.py:
collective census by mesh axis, replicated-bytes warnings, remat
fraction), training health
(sentinel words, loss-scale events, grad/update norms, fault
attributions, OOM post-mortems — utils/health.py), and peak HBM per
device.

Given a DIRECTORY, every telemetry dump in it (the per-rank
``path.rank<i>`` files N processes write for one ``SMP_TELEMETRY_PATH``)
is loaded and the report is the cross-rank aggregate: counters and
histograms summed, gauges maxed (peak-HBM keeps the worst device), plus a
per-rank table with step counts, phases, and wall-clock skew measured at
the last shared barrier sync mark. The merge itself is
``utils/telemetry.merge_metric_reports`` when the package is importable
(the same function the live fleet aggregator runs, keeping this offline
view bit-equal to the ``/fleet`` scrape endpoint) with a pinned-equal
stdlib fallback, so the script stays runnable anywhere the JSON can be
copied to — no jax required.
"""

import argparse
import copy
import json
import os
import re
import sys


def _series(report, name):
    fam = report.get("metrics", {}).get(name)
    return fam["series"] if fam else []


def _value(report, name, default=None, **labels):
    for s in _series(report, name):
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value", default)
    return default


def _hist_totals(report, name):
    """(sum, count) aggregated over every label set of a histogram."""
    total, count = 0.0, 0
    for s in _series(report, name):
        total += s.get("sum", 0.0)
        count += s.get("count", 0)
    return total, count


def _quantile_from_counts(buckets, counts, q):
    """q-quantile of a bucketed distribution (stdlib copy of
    utils/telemetry.quantile_from_counts — same interpolation, so
    percentiles of cross-rank MERGED bucket counts match what a single
    rank would have published). Log-interpolates inside geometric
    buckets; the overflow bucket clamps to the last boundary; None when
    empty."""
    total = sum(counts)
    if total <= 0:
        return None
    target = min(max(float(q), 0.0), 1.0) * total
    acc = 0.0
    for i, c in enumerate(counts):
        if c > 0 and acc + c >= target:
            if i >= len(buckets):
                return float(buckets[-1])
            lo = buckets[i - 1] if i > 0 else 0.0
            hi = buckets[i]
            f = (target - acc) / c
            if lo > 0.0:
                return float(lo * (hi / lo) ** f)
            return float(lo + (hi - lo) * f)
        acc += c
    return float(buckets[-1])


def _hist_quantiles(report, name, qs, **labels):
    """(count, mean, [quantile...]) of one histogram series (bucket
    counts merged across matching label sets), or None when empty."""
    buckets, counts, total, n = None, None, 0.0, 0
    for s in _series(report, name):
        if not all(s["labels"].get(k) == v for k, v in labels.items()):
            continue
        b = s.get("buckets") or []
        c = s.get("counts") or []
        if buckets is None:
            buckets, counts = list(b), list(c)
        elif b == buckets and len(c) == len(counts):
            counts = [x + y for x, y in zip(counts, c)]
        total += s.get("sum", 0.0)
        n += s.get("count", 0)
    if not n or buckets is None:
        return None
    return (n, total / n,
            [_quantile_from_counts(buckets, counts, q) for q in qs])


def _fmt_bytes(n):
    if n is None:
        return "n/a"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n):,} B"
        n /= 1024
    return f"{n:,.1f} TiB"


def _fmt_num(n):
    if n is None:
        return "n/a"
    n = float(n)
    for unit, div in (("T", 1e12), ("G", 1e9), ("M", 1e6), ("k", 1e3)):
        if abs(n) >= div:
            return f"{n / div:,.2f}{unit}"
    return f"{n:,.0f}"


def render(report, out=sys.stdout):
    w = out.write
    meta = report.get("meta", {})
    w("=== SMP step report ===\n")
    if "ranks" in meta:
        w(f"aggregated over ranks {meta['ranks']}\n")
    else:
        w(f"pid {meta.get('pid')}  phase {meta.get('phase')!r} "
          f"(age {meta.get('phase_age_seconds', 0):.1f}s)\n")
    history = meta.get("phase_history", [])[-5:]
    if history:
        w("recent phases: " + " -> ".join(p["phase"] for p in history) + "\n")

    # -- throughput -----------------------------------------------------
    steps = _value(report, "smp_step_total", 0)
    tokens = _value(report, "smp_step_tokens_total")
    disp_sum, disp_count = _hist_totals(report, "smp_step_time_seconds")
    w("\n-- throughput --\n")
    w(f"steps: {int(steps or 0)}   tokens: {_fmt_num(tokens)}\n")
    if disp_count:
        w(f"dispatch wall: {disp_sum:.3f}s over {disp_count} steps "
          f"({disp_sum / disp_count:.3f}s/step)\n")
        if tokens and disp_sum > 0:
            w(f"tokens/sec (host dispatch bound): {_fmt_num(tokens / disp_sum)}\n")
    step_q = _hist_quantiles(
        report, "smp_step_time_seconds", (0.5, 0.9, 0.99)
    )
    if step_q:
        _, _, (p50, p90, p99) = step_q
        w(f"step time p50/p90/p99: {1e3 * p50:.1f}/{1e3 * p90:.1f}/"
          f"{1e3 * p99:.1f} ms\n")

    # -- pipeline bubble ------------------------------------------------
    bubbles = _series(report, "smp_pipeline_bubble_fraction")
    if bubbles:
        w("\n-- pipeline --\n")
        for s in bubbles:
            sched = s["labels"].get("schedule", "?")
            theo = _value(
                report, "smp_pipeline_bubble_fraction_theoretical",
                schedule=sched,
            )
            pp = _value(report, "smp_pipeline_stages", schedule=sched)
            mb = _value(report, "smp_pipeline_microbatches", schedule=sched)
            virt = _value(
                report, "smp_pipeline_virtual_stages", schedule=sched
            )
            shape = ""
            if pp and mb:
                shape = f"  (pp={int(pp)}, mb={int(mb)}"
                shape += f", v={int(virt)})" if virt and virt > 1 else ")"
            w(f"{sched}: bubble {100 * s['value']:.1f}% measured"
              + (f" vs {100 * theo:.1f}% schedule bound" if theo is not None else "")
              + shape + "\n")

    # -- comm volume ----------------------------------------------------
    ops = _series(report, "smp_comm_ops_total")
    if ops:
        w("\n-- host collectives --\n")
        w(f"{'op':<12}{'group':<12}{'calls':>8}{'bytes':>14}\n")
        for s in sorted(ops, key=lambda s: (s["labels"].get("op", ""),
                                            s["labels"].get("group", ""))):
            op = s["labels"].get("op", "?")
            grp = s["labels"].get("group", "?")
            nbytes = _value(report, "smp_comm_bytes_total", 0, op=op, group=grp)
            w(f"{op:<12}{grp:<12}{int(s['value']):>8}"
              f"{_fmt_bytes(nbytes):>14}\n")

    # -- compile --------------------------------------------------------
    hits = _value(report, "smp_step_compile_cache_total", 0, event="hit")
    misses = _value(report, "smp_step_compile_cache_total", 0, event="miss")
    comp_sum, comp_count = _hist_totals(report, "smp_step_compile_seconds")
    lower_sum, lower_count = _hist_totals(report, "smp_step_lower_seconds")
    if hits or misses or comp_count:
        w("\n-- compilation --\n")
        w(f"step cache: {int(hits or 0)} hits / {int(misses or 0)} misses\n")
        if comp_count:
            w(f"XLA compile wall: {comp_sum:.1f}s over {comp_count} compiles\n")
        if lower_count:
            w(f"trace+lower wall: {lower_sum:.1f}s over {lower_count} "
              "programs\n")
    for s in _series(report, "smp_compiled_step_flops"):
        name = s["labels"].get("step", "?")
        ba = _value(report, "smp_compiled_step_bytes_accessed", step=name)
        tmp = _value(report, "smp_compiled_step_temp_bytes", step=name)
        w(f"compiled {name}: {_fmt_num(s['value'])} FLOPs, "
          f"{_fmt_bytes(ba)} accessed, {_fmt_bytes(tmp)} temp\n")

    # -- executable cache (persistent AOT cache; utils/exec_cache.py) ----
    # Lookup outcomes + compile wall split by source: the availability
    # story (warm starts replacing recompiles) measured, not assumed.
    # Gated on actual cache lookups — every run carries source="fresh"
    # compile series, but without SMP_EXEC_CACHE there is no cache story
    # to tell.
    ec = _series(report, "smp_exec_cache_total")
    by_source = {
        s["labels"].get("source"): (s.get("count", 0), s.get("sum", 0.0))
        for s in _series(report, "smp_step_compile_seconds")
        if s["labels"].get("source")
    }
    if ec:
        w("\n-- executable cache --\n")
        outcomes = "  ".join(
            f"{s['labels'].get('result', '?')}={int(s['value'])}"
            for s in sorted(
                ec, key=lambda s: s["labels"].get("result", "")
            )
        )
        w(f"lookups: {outcomes}\n")
        for src in sorted(by_source):
            cnt, secs = by_source[src]
            if cnt:
                w(f"compile wall ({src}): {secs:.2f}s over {int(cnt)} "
                  f"compile(s) ({secs / cnt:.2f}s each)\n")
        entries = _value(report, "smp_exec_cache_entries")
        if entries is not None:
            w(f"entries at last warm-start consult: {int(entries)}\n")
        hit_s = _value(report, "smp_exec_cache_hit_seconds")
        if hit_s is not None:
            w(f"last hit deserialize+verify: {hit_s:.3f}s\n")

    # -- performance (roofline/MFU; utils/profiling.py) ------------------
    # Programs with a known peak carry smp_mfu; programs attributed on an
    # unknown backend (CPU smoke without the peak env overrides) still
    # show achieved FLOP/s and arithmetic intensity.
    perf_names = sorted({
        s["labels"].get("step", "?")
        for metric in ("smp_mfu", "smp_roofline_achieved_flops_per_s")
        for s in _series(report, metric)
    })
    if perf_names:
        w("\n-- performance --\n")
        for name in perf_names:
            mfu = _value(report, "smp_mfu", step=name)
            flops = _value(report, "smp_roofline_flops", step=name)
            step_s = _value(report, "smp_roofline_step_seconds", step=name)
            achieved = _value(
                report, "smp_roofline_achieved_flops_per_s", step=name
            )
            line = f"{name}: "
            line += f"MFU {mfu:.3f}" if mfu is not None else "MFU n/a"
            if achieved is not None:
                line += f"  ({_fmt_num(achieved)} FLOP/s achieved"
                if flops is not None and step_s:
                    line += f" = {_fmt_num(flops)} FLOP / {step_s * 1e3:.1f} ms"
                line += ")"
            w(line + "\n")
            ai = _value(
                report, "smp_roofline_arithmetic_intensity", step=name
            )
            ridge = _value(report, "smp_roofline_ridge_intensity", step=name)
            if ai is not None:
                line = f"  arithmetic intensity {ai:.1f} FLOP/B"
                if ridge is not None:
                    line += f" vs ridge {ridge:.1f}"
                    cb = _value(
                        report, "smp_roofline_compute_bound", step=name
                    )
                    if cb is not None:
                        line += (" -> " + ("compute" if cb else "memory")
                                 + "-bound")
                w(line + "\n")
            comp = _value(report, "smp_roofline_compute_seconds", step=name)
            comm = _value(report, "smp_roofline_comm_seconds", step=name)
            bub = _value(report, "smp_roofline_bubble_seconds", step=name)
            if step_s and comp is not None:
                parts = [f"compute {100 * comp / step_s:.1f}%"]
                if comm is not None:
                    parts.append(f"comm+other {100 * comm / step_s:.1f}%")
                if bub is not None:
                    parts.append(f"bubble {100 * bub / step_s:.1f}%")
                w("  decomposition: " + " / ".join(parts) + "\n")

    # -- hlo audit (compiled-program X-ray; utils/hlo_audit.py) ----------
    # smp_hlo_* gauges are stamped once per compiled program: the static
    # collective census (per op kind and attributed mesh axis), the
    # replication detector's wasted-byte estimate, and the remat census.
    audit_names = sorted({
        s["labels"].get("step", "?")
        for metric in ("smp_hlo_collective_ops", "smp_hlo_remat_fraction")
        for s in _series(report, metric)
    })
    if audit_names:
        w("\n-- hlo audit --\n")
        for name in audit_names:
            w(f"{name}:\n")
            ops = [
                s for s in _series(report, "smp_hlo_collective_ops")
                if s["labels"].get("step") == name
            ]
            if ops:
                w(f"  {'collective':<20}{'axis':<14}{'ops':>6}"
                  f"{'bytes/device':>16}\n")
                for s in sorted(ops, key=lambda s: (
                        s["labels"].get("op", ""),
                        s["labels"].get("axis", ""))):
                    op = s["labels"].get("op", "?")
                    axis = s["labels"].get("axis", "?")
                    nbytes = _value(
                        report, "smp_hlo_collective_bytes",
                        step=name, op=op, axis=axis,
                    )
                    w(f"  {op:<20}{axis:<14}{int(s['value']):>6}"
                      f"{_fmt_bytes(nbytes):>16}\n")
            else:
                w("  no collectives (single-device program)\n")
            remat = _value(report, "smp_hlo_remat_fraction", step=name)
            if remat is not None:
                w(f"  remat: {100 * remat:.1f}% recomputed FLOPs "
                  "(static census)\n")
            rep_bytes = _value(report, "smp_hlo_replicated_bytes", step=name)
            rep_n = _value(report, "smp_hlo_replicated_findings", step=name)
            if rep_n:
                w(f"  !! replication: {int(rep_n)} finding(s), "
                  f"{_fmt_bytes(rep_bytes)} wasted per device\n")

    # -- recompute (memory-budgeted recompute planner; parallel/
    # remat_plan.py) --------------------------------------------------
    # smp_recompute_* gauges: the active stash plan per schedule (mode,
    # per-chunk decisions, ring slots), its stash bytes vs the budget,
    # and the planner's executed-FLOP recompute fractions before (full)
    # and after (planned) — next to the measured census fraction the
    # hlo-audit section shows for the compiled program.
    rc_scheds = sorted({
        s["labels"].get("schedule", "?")
        for s in _series(report, "smp_recompute_mode_info")
    })
    if rc_scheds:
        w("\n-- recompute --\n")
        for sched in rc_scheds:
            mode = effective = None
            for s in _series(report, "smp_recompute_mode_info"):
                if s["labels"].get("schedule") == sched:
                    mode = s["labels"].get("mode")
                    effective = s["labels"].get("effective")
            line = f"{sched}: mode {mode}"
            if effective and effective != mode:
                line += f" -> {effective}"
            n_stash = _value(report, "smp_recompute_chunks",
                             schedule=sched, decision="stash")
            n_rec = _value(report, "smp_recompute_chunks",
                           schedule=sched, decision="recompute")
            if n_stash is not None:
                line += (f"   chunks: {int(n_stash)} stashed"
                         + (f", {int(n_rec)} degraded" if n_rec else ""))
            w(line + "\n")
            res_slots = _value(report, "smp_recompute_ring_slots",
                               schedule=sched, ring="residual")
            cot_slots = _value(report, "smp_recompute_ring_slots",
                               schedule=sched, ring="cotangent")
            stash_b = _value(report, "smp_recompute_stash_bytes",
                             schedule=sched)
            budget_b = _value(report, "smp_recompute_budget_bytes",
                              schedule=sched)
            if stash_b is not None:
                line = f"  stash: {_fmt_bytes(stash_b)}/device"
                if budget_b is not None:
                    line += f" vs budget {_fmt_bytes(budget_b)}"
                else:
                    line += " (unbudgeted)"
                if res_slots is not None:
                    line += (f"  [rings: residual x{int(res_slots)}"
                             + (f", cotangent x{int(cot_slots)}"
                                if cot_slots else "") + "]")
                w(line + "\n")
            before = _value(report, "smp_recompute_predicted_fraction",
                            schedule=sched, when="full")
            after = _value(report, "smp_recompute_predicted_fraction",
                           schedule=sched, when="planned")
            if before is not None and after is not None:
                w(f"  recompute census (planner model): "
                  f"{100 * before:.0f}% full -> {100 * after:.0f}% "
                  "planned (measured program census in -- hlo audit --)\n")

    # -- zero (ZeRO-3 fully-sharded params; parallel/zero.py + the X-ray's
    # zero_report) ------------------------------------------------------
    # smp_zero3_* gauges: rdp-axis parameter-gather / gradient-scatter
    # traffic of the compiled program, the bucketed-reduce layout, and the
    # overlap evidence (loop-interior fraction + double-buffered transfer
    # registers). Rendered identically for one dump and the cross-rank
    # aggregate (gauges maxed — the census is identical across ranks of
    # one SPMD program).
    zero_names = sorted({
        s["labels"].get("step", "?")
        for metric in ("smp_zero3_gather_ops", "smp_zero3_buckets")
        for s in _series(report, metric)
    })
    if zero_names:
        w("\n-- zero --\n")
        for name in zero_names:
            g_ops = _value(report, "smp_zero3_gather_ops", step=name)
            g_bytes = _value(report, "smp_zero3_gather_bytes", step=name)
            s_ops = _value(report, "smp_zero3_scatter_ops", step=name)
            s_bytes = _value(report, "smp_zero3_scatter_bytes", step=name)
            w(f"{name}:\n")
            if g_ops is not None or s_ops is not None:
                w(f"  param gathers: {int(g_ops or 0)} op(s), "
                  f"{_fmt_bytes(g_bytes)}/device   grad scatters: "
                  f"{int(s_ops or 0)} op(s), {_fmt_bytes(s_bytes)}/device\n")
            buckets = _value(report, "smp_zero3_buckets", step=name)
            b_bytes = _value(report, "smp_zero3_bucket_bytes", step=name)
            if buckets is not None:
                w(f"  reduce-scatter buckets: {int(buckets)} "
                  f"({_fmt_bytes(b_bytes)} grads/microbatch)\n")
            n_sharded = _value(report, "smp_zero3_sharded_params", step=name)
            n_persist = _value(
                report, "smp_zero3_persistent_params", step=name
            )
            if n_sharded is not None:
                w(f"  params: {int(n_sharded)} rdp-sharded, "
                  f"{int(n_persist or 0)} persistent (replicated)\n")
            overlap = _value(
                report, "smp_zero3_overlap_fraction", step=name
            )
            regs = _value(report, "smp_zero3_prefetch_registers", step=name)
            if overlap is not None:
                line = (f"  overlap: {100 * overlap:.1f}% of gather/scatter "
                        "bytes issued inside loop bodies")
                if regs:
                    line += (f"; {int(regs)} double-buffered register "
                             "gather(s)")
                w(line + "\n")

    # -- tp overlap (ring-decomposed collective matmuls;
    # ops/collective_matmul.py + the X-ray's tp_overlap_report) ----------
    # smp_tp_overlap_* gauges: the decomposed ring-hop census attributed
    # to the tp axis, the parked-hop double-buffering evidence, residual
    # synchronous tp collectives, plus the fused-kernel dispatch
    # counters (smp_fused_kernel_dispatch_total). Rendered identically
    # for one dump and the cross-rank aggregate.
    tp_names = sorted({
        s["labels"].get("step", "?")
        for s in _series(report, "smp_tp_overlap_ring_permute_ops")
    })
    fused_series = _series(report, "smp_fused_kernel_dispatch_total")
    if tp_names or fused_series:
        w("\n-- tp overlap --\n")
        for name in tp_names:
            hops = _value(report, "smp_tp_overlap_ring_permute_ops",
                          step=name)
            hop_bytes = _value(report, "smp_tp_overlap_ring_permute_bytes",
                               step=name)
            parked = _value(report, "smp_tp_overlap_parked_hops", step=name)
            w(f"{name}:\n")
            w(f"  ring hops: {int(hops or 0)} tp collective-permute(s), "
              f"{_fmt_bytes(hop_bytes)}/device overlapped"
              f"; {int(parked or 0)} parked in loop carries "
              "(double-buffered)\n")
            ag = _value(report, "smp_tp_overlap_tp_allgather_ops", step=name)
            rs = _value(report, "smp_tp_overlap_tp_reduce_scatter_ops",
                        step=name)
            ar = _value(report, "smp_tp_overlap_tp_allreduce_ops", step=name)
            w(f"  residual synchronous tp collectives: "
              f"{int(ag or 0)} all-gather(s), {int(rs or 0)} "
              f"reduce-scatter(s), {int(ar or 0)} all-reduce(s)\n")
            ev = _value(report, "smp_tp_overlap_evidence", step=name)
            if ev is not None:
                w("  overlap evidence: "
                  + ("PROVEN (hops feed only data movement into the next "
                     "partial matmul)" if ev else "NOT PROVEN")
                  + "\n")
        if fused_series:
            counts = {}
            for s in fused_series:
                key = (s["labels"].get("kernel", "?"),
                       s["labels"].get("path", "?"))
                counts[key] = counts.get(key, 0) + s["value"]
            parts = [
                f"{kernel}/{path} {int(v)}"
                for (kernel, path), v in sorted(counts.items())
            ]
            w("  fused-kernel dispatch decisions: " + "  ".join(parts)
              + "\n")

    # -- quant (low-precision dispatch + fp8 delayed-scaling state;
    # smp.quant) ---------------------------------------------------------
    # smp_quant_dispatch_total counts the trace-time routing decisions
    # (which seams engaged fp8 / which knobs fell back), smp_quant_amax /
    # smp_quant_scale carry the delayed-scaling statistics per site
    # (latest absorb), and smp_serve_kv_bytes makes the int8 paged-KV
    # pool halving a measured byte count. Rendered identically for one
    # dump and the cross-rank aggregate (counters summed; the gauges are
    # maxed, which is exact for the replicated SPMD quant state).
    q_disp = _series(report, "smp_quant_dispatch_total")
    q_amax = _series(report, "smp_quant_amax")
    kv_bytes_total = _value(report, "smp_serve_kv_bytes", state="total")
    if q_disp or q_amax or kv_bytes_total is not None:
        w("\n-- quant --\n")
        if q_disp:
            counts = {}
            for s in q_disp:
                key = (s["labels"].get("site", "?"),
                       s["labels"].get("path", "?"))
                counts[key] = counts.get(key, 0) + s["value"]
            parts = [
                f"{site}/{path} x{int(v)}"
                for (site, path), v in sorted(counts.items())
            ]
            w("  dispatch decisions: " + "  ".join(parts) + "\n")
        observed = [s for s in q_amax if s.get("value", 0) > 0]
        if q_amax:
            silent = len(q_amax) - len(observed)
            if observed:
                w(f"  {'site':<16}{'amax':>12}{'scale':>12}\n")
                for s in sorted(
                    observed, key=lambda s: s["labels"].get("site", "")
                ):
                    site = s["labels"].get("site", "?")
                    scale = _value(report, "smp_quant_scale", site=site)
                    w(f"  {site:<16}{s['value']:>12.4g}"
                      + (f"{scale:>12.4g}" if scale is not None
                         else f"{'n/a':>12}") + "\n")
            if silent:
                w(f"  ({silent} slot(s) never observed — scale held at "
                  "1.0)\n")
        if kv_bytes_total is not None:
            kv_bytes_used = _value(
                report, "smp_serve_kv_bytes", state="used"
            )
            w(f"  kv pool bytes: {_fmt_bytes(kv_bytes_used)} used / "
              f"{_fmt_bytes(kv_bytes_total)} total\n")

    # -- serving (smp.serving continuous-batching engine) ---------------
    # Latency distributions (percentiles from the merged log-bucketed
    # histograms — identical in single-dump and cross-rank dir modes,
    # because aggregate() sums bucket counts element-wise), windowed
    # throughput, SLO goodput, occupancy (queue depth, decode slots,
    # paged KV-pool blocks), and request lifecycle counters incl.
    # failover re-admissions.
    serve_events = {
        s["labels"].get("event", "?"): s["value"]
        for s in _series(report, "smp_serve_requests_total")
    }
    if serve_events or _series(report, "smp_serve_slots"):
        w("\n-- serving --\n")
        if serve_events:
            w("  requests: " + "  ".join(
                f"{k} {int(v)}" for k, v in sorted(serve_events.items())
            ) + "\n")
        tok = {
            s["labels"].get("kind", "?"): s["value"]
            for s in _series(report, "smp_serve_tokens_total")
        }
        if tok:
            w("  tokens: " + "  ".join(
                f"{k} {int(v)}" for k, v in sorted(tok.items())
            ) + "\n")
        ttft_last = _value(report, "smp_serve_ttft_seconds", stat="last")
        ttft_mean = _value(report, "smp_serve_ttft_seconds", stat="mean")
        itl_last = _value(report, "smp_serve_itl_seconds", stat="last")
        itl_mean = _value(report, "smp_serve_itl_seconds", stat="mean")
        if ttft_mean is not None or itl_mean is not None:
            parts = []
            if ttft_mean is not None:
                parts.append(f"ttft {1e3 * ttft_mean:.1f}ms mean"
                             + (f" ({1e3 * ttft_last:.1f}ms last)"
                                if ttft_last is not None else ""))
            if itl_mean is not None:
                parts.append(f"itl {1e3 * itl_mean:.1f}ms mean"
                             + (f" ({1e3 * itl_last:.1f}ms last)"
                                if itl_last is not None else ""))
            w("  latency: " + "  ".join(parts) + "\n")
        lat_rows = []
        for kind in ("ttft", "itl", "queue_wait", "prefill",
                     "decode_step"):
            hq = _hist_quantiles(
                report, "smp_serve_latency_seconds", (0.5, 0.9, 0.99),
                kind=kind,
            )
            if hq:
                lat_rows.append((kind, hq))
        if lat_rows:
            w(f"  {'latency (ms)':<14}{'n':>8}{'mean':>9}{'p50':>9}"
              f"{'p90':>9}{'p99':>9}\n")
            for kind, (n, mean, (p50, p90, p99)) in lat_rows:
                w(f"  {kind:<14}{n:>8}{1e3 * mean:>9.1f}"
                  f"{1e3 * p50:>9.1f}{1e3 * p90:>9.1f}"
                  f"{1e3 * p99:>9.1f}\n")
        rps = _value(report, "smp_serve_requests_per_sec")
        tps = _value(report, "smp_serve_tokens_per_sec", scope="engine")
        tps_chip = _value(report, "smp_serve_tokens_per_sec", scope="chip")
        if rps is not None or tps is not None:
            parts = []
            if rps is not None:
                parts.append(f"{rps:.2f} req/s")
            if tps is not None:
                parts.append(f"{tps:,.1f} tok/s")
            if tps_chip is not None:
                parts.append(f"{tps_chip:,.1f} tok/s/chip")
            w("  throughput (last window): " + "  ".join(parts) + "\n")
        windows = _value(report, "smp_timeseries_windows")
        goodput = _value(report, "smp_slo_goodput_fraction")
        violations = _series(report, "smp_slo_violations_total")
        if windows or goodput is not None or violations:
            parts = []
            if windows:
                parts.append(f"{int(windows)} window(s)")
            if goodput is not None:
                parts.append(f"goodput {100.0 * goodput:.1f}%")
            n_viol = int(sum(s["value"] for s in violations))
            if n_viol:
                detail = ", ".join(
                    f"{s['labels'].get('slo', '?')} x{int(s['value'])}"
                    for s in sorted(
                        violations,
                        key=lambda s: s["labels"].get("slo", ""),
                    ) if s["value"]
                )
                parts.append(f"{n_viol} violation(s): {detail}")
            elif goodput is not None:
                parts.append("0 violations")
            w("  slo: " + "  ".join(parts) + "\n")
        q = _value(report, "smp_serve_queue_depth")
        active = _value(report, "smp_serve_slots", state="active")
        total = _value(report, "smp_serve_slots", state="total")
        if total is not None:
            w(f"  occupancy: queue {int(q or 0)}  slots "
              f"{int(active or 0)}/{int(total)}\n")
        kv_used = _value(report, "smp_serve_kv_blocks", state="used")
        kv_total = _value(report, "smp_serve_kv_blocks", state="total")
        kv_res = _value(report, "smp_serve_kv_blocks", state="reserved")
        if kv_total:
            pct = 100.0 * (kv_used or 0) / kv_total
            w(f"  kv pool: {int(kv_used or 0)}/{int(kv_total)} blocks "
              f"used ({pct:.0f}%), {int(kv_res or 0)} reserved\n")
        progs = _value(report, "smp_serve_programs")
        if progs is not None:
            w(f"  compiled programs: {int(progs)}\n")

    # -- control plane (serving/controller.py, SMP_AUTOSCALE) -----------
    # Scale events with their phase breakdowns, the live replica count,
    # routed-request split by weights version, live weight-update
    # timing, and canary verdicts incl. the rollback latch.
    scale_dirs = {
        s["labels"].get("direction", "?"): s["value"]
        for s in _series(report, "smp_autoscale_events_total")
    }
    routed = _series(report, "smp_controller_routed_total")
    if scale_dirs or routed:
        w("\n-- control plane --\n")
        replicas = _value(report, "smp_controller_replicas")
        if scale_dirs:
            parts = [f"{k} x{int(v)}" for k, v in sorted(scale_dirs.items())]
            if replicas is not None:
                parts.append(f"now {int(replicas)} replica(s)")
            w("  scale events: " + "  ".join(parts) + "\n")
            last_s = _value(report, "smp_autoscale_last_scale_seconds")
            phases = {
                s["labels"].get("phase", "?"): s["value"]
                for s in _series(report, "smp_autoscale_phase_seconds")
            }
            if last_s is not None:
                detail = " ".join(
                    f"{k} {1e3 * v:.0f}ms" for k, v in sorted(phases.items())
                )
                w(f"  last event: {last_s:.3f}s"
                  + (f"  ({detail})" if detail else "") + "\n")
        elif replicas is not None:
            w(f"  replicas: {int(replicas)}\n")
        if routed:
            w("  routed: " + "  ".join(
                f"v{s['labels'].get('version', '?')} {int(s['value'])}"
                for s in sorted(
                    routed, key=lambda s: s["labels"].get("version", "")
                )
            ) + "\n")
        drained = _value(report, "smp_controller_drain_stragglers_total")
        if drained:
            w(f"  drain protocol: {int(drained)} straggler(s) "
              "re-dispatched\n")
        wu = {
            s["labels"].get("outcome", "?"): s["value"]
            for s in _series(report, "smp_weight_updates_total")
        }
        if wu:
            wv = _value(report, "smp_controller_weights_version")
            wu_s = _value(report, "smp_weight_update_seconds")
            parts = [f"{k} x{int(v)}" for k, v in sorted(wu.items())]
            if wv is not None:
                parts.append(f"live version {int(wv)}")
            if wu_s is not None:
                parts.append(f"last {wu_s:.3f}s")
            w("  weight updates: " + "  ".join(parts) + "\n")
        promos = _value(report, "smp_canary_promotions_total")
        rollbacks = _value(report, "smp_canary_rollback_total")
        active = _value(report, "smp_canary_active")
        if promos or rollbacks or active:
            parts = []
            if promos:
                parts.append(f"{int(promos)} promoted")
            if rollbacks:
                parts.append(f"{int(rollbacks)} ROLLED BACK")
            if active:
                parts.append("1 in flight")
            w("  canary: " + "  ".join(parts) + "\n")

    # -- health ---------------------------------------------------------
    # Fed by utils/health.py (SMP_HEALTH_CHECK sentinel), the fp16 loss
    # scaler, and the optimizer norm gauges; rendered identically for one
    # dump and for the cross-rank aggregate (counters summed, gauges
    # maxed, per-label fault series preserved).
    checks = _value(report, "smp_health_checks_total")
    trips = _series(report, "smp_health_trips_total")
    bads = _series(report, "smp_health_bad_count")
    faults = _series(report, "smp_health_fault_total")
    scale = _value(report, "smp_loss_scale")
    overflows = _value(report, "smp_loss_scale_events_total", event="overflow")
    growths = _value(report, "smp_loss_scale_events_total", event="growth")
    static_of = _value(
        report, "smp_loss_scale_events_total", event="static_overflow"
    )
    gn = _value(report, "smp_grad_norm")
    pn = _value(report, "smp_param_norm")
    ur = _value(report, "smp_update_ratio")
    ooms = _series(report, "smp_oom_total")
    if any((checks, trips, faults, ooms)) or scale is not None or gn is not None:
        w("\n-- health --\n")
        if checks:
            n_trips = int(sum(s["value"] for s in trips))
            last = _value(report, "smp_health_last_checked_step")
            w(f"sentinel: {int(checks)} health words checked"
              + (f" (through step {int(last)})" if last is not None else "")
              + f", {n_trips} trip(s)\n")
        if bads:
            w("last health word:\n")
            for s in sorted(bads, key=lambda s: s["labels"].get("tag", "")):
                tag = s["labels"].get("tag", "?")
                absmax = _value(report, "smp_health_absmax", tag=tag)
                first_mb = _value(
                    report, "smp_health_first_microbatch", tag=tag
                )
                line = f"  {tag:<28} bad={int(s['value'])}"
                if absmax is not None:
                    line += f"  absmax={absmax:.4g}"
                if s["value"] and first_mb is not None and first_mb >= 0:
                    line += f"  first_mb={int(first_mb)}"
                w(line + "\n")
        if gn is not None or pn is not None:
            w("grad norm: " + (f"{gn:.5g}" if gn is not None else "n/a")
              + (f"   param norm: {pn:.5g}" if pn is not None else "")
              + (f"   update ratio: {ur:.3g}" if ur is not None else "")
              + "\n")
        if scale is not None or overflows or growths or static_of:
            w(f"loss scale: {scale:g}" if scale is not None else "loss scale:")
            w(f"  ({int(overflows or 0)} overflow(s), "
              f"{int(growths or 0)} growth(s)"
              + (f", {int(static_of)} static overflow(s)" if static_of else "")
              + ")\n")
        for s in faults:
            lab = s["labels"]
            w(f"!! fault: layer={lab.get('layer')} "
              f"microbatch={lab.get('microbatch')} tag={lab.get('tag')} "
              f"x{int(s['value'])}\n")
        for s in ooms:
            w(f"!! OOM post-mortem dumped for {s['labels'].get('step', '?')} "
              f"x{int(s['value'])}\n")

    # -- memory ---------------------------------------------------------
    peaks = _series(report, "smp_device_peak_hbm_bytes")
    w("\n-- memory --\n")
    if peaks:
        for s in sorted(peaks, key=lambda s: s["labels"].get("device", "")):
            limit = _value(
                report, "smp_device_hbm_bytes_limit",
                device=s["labels"].get("device"),
            )
            w(f"peak HBM {s['labels'].get('device', '?')}: "
              f"{_fmt_bytes(s['value'])}"
              + (f" / {_fmt_bytes(limit)}" if limit else "") + "\n")
    else:
        w("peak HBM: n/a (backend reports no allocator stats)\n")
    return 0


# ----------------------------------------------------------------------
# Cross-rank aggregation (directory of per-rank dumps)
# ----------------------------------------------------------------------

_RANK_RE = re.compile(r"\.rank(\d+)$")


def load_rank_dumps(dirpath):
    """{rank: report} for every telemetry dump in the directory. Rank
    comes from the dump's own meta, falling back to the ``.rank<i>``
    filename suffix, then to load order."""
    reports = {}
    unranked = []
    for name in sorted(os.listdir(dirpath)):
        path = os.path.join(dirpath, name)
        if not os.path.isfile(path):
            continue
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(payload, dict) or "metrics" not in payload:
            continue
        rank = payload.get("meta", {}).get("rank")
        if rank is None:
            m = _RANK_RE.search(name)
            rank = int(m.group(1)) if m else None
        if rank is None or rank in reports:
            unranked.append((name, payload))
        else:
            reports[rank] = payload
    nxt = (max(reports) + 1) if reports else 0
    for name, payload in unranked:
        # Aggregating a dump of unknown provenance (no rank, or a rank
        # already claimed — e.g. a stale un-suffixed file from an earlier
        # run left in the directory) inflates every summed counter; make
        # the synthetic assignment loud so the reader can exclude it.
        sys.stderr.write(
            f"warning: {name} has no unclaimed rank; aggregating it as "
            f"synthetic rank {nxt} (stale leftover dump?)\n"
        )
        reports[nxt] = payload
        nxt += 1
    return reports


def _package_merge():
    """The canonical cross-rank merge lives in
    ``utils/telemetry.merge_metric_reports`` (shared with the live fleet
    aggregator, so offline aggregation stays bit-equal to the on-fleet
    scrape view). This script prefers it when the package is importable
    next to the dumps and keeps ``_merge_fallback`` below — pinned equal
    by tests/test_fleet.py — for the copied-off-box, no-jax case the
    module docstring promises."""
    try:
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            merge_metric_reports,
        )

        return merge_metric_reports
    except Exception:
        return None


def aggregate(reports):
    """One merged report: counters/histogram series summed element-wise
    across ranks, gauges maxed (peak HBM keeps the worst device). Series
    are matched by (metric, label-set)."""
    merge = _package_merge()
    if merge is not None:
        return merge(reports)
    return _merge_fallback(reports)


def _merge_fallback(reports):
    out = {"meta": {"ranks": sorted(reports)}, "metrics": {}}
    for rank in sorted(reports):
        for name, fam in reports[rank].get("metrics", {}).items():
            ofam = out["metrics"].setdefault(
                name, {"kind": fam["kind"], "help": fam.get("help", ""),
                       "series": []},
            )
            for series in fam.get("series", []):
                key = tuple(sorted(series.get("labels", {}).items()))
                dst = None
                for s in ofam["series"]:
                    if tuple(sorted(s.get("labels", {}).items())) == key:
                        dst = s
                        break
                if dst is None:
                    ofam["series"].append(copy.deepcopy(series))
                    continue
                if fam["kind"] == "histogram":
                    dst["sum"] = dst.get("sum", 0.0) + series.get("sum", 0.0)
                    dst["count"] = dst.get("count", 0) + series.get("count", 0)
                    if dst.get("buckets") == series.get("buckets"):
                        dst["counts"] = [
                            a + b for a, b in zip(dst["counts"],
                                                  series["counts"])
                        ]
                    else:
                        # Mixed-build dumps: sum/count merge fine, the
                        # per-bucket distribution cannot — say so rather
                        # than render a distribution that doesn't add up.
                        sys.stderr.write(
                            f"warning: histogram {name} has differing "
                            "buckets across ranks; aggregate bucket "
                            "counts reflect only the first rank\n"
                        )
                elif fam["kind"] == "counter":
                    dst["value"] = dst.get("value", 0) + series.get("value", 0)
                else:  # gauge: keep the worst rank
                    dst["value"] = max(dst.get("value", 0),
                                       series.get("value", 0))
    return out


def render_cross_rank(reports, out=sys.stdout):
    w = out.write
    ranks = sorted(reports)
    w(f"=== SMP cross-rank report ({len(ranks)} rank(s)) ===\n")

    # Per-rank table with the wall-clock skew columns: the
    # smp_sync_last_unix_seconds gauge is stamped at barrier exit, which
    # every member leaves near-simultaneously — differences across ranks
    # are clock skew (+ exit jitter), no extra collective needed. Skew is
    # only meaningful between ranks stamped at the SAME barrier ordinal
    # (smp_sync_seq): a rank that died earlier was stamped at a different
    # physical barrier, and comparing those wall clocks would report
    # inter-barrier elapsed time as skew.
    syncs = {
        r: _value(reports[r], "smp_sync_last_unix_seconds", group="WORLD")
        for r in ranks
    }
    desync = {
        r: _value(reports[r], "smp_sync_seq", group="WORLD") for r in ranks
    }
    seq_counts = {}
    for r in ranks:
        if desync[r] is not None and syncs[r] is not None:
            seq_counts[desync[r]] = seq_counts.get(desync[r], 0) + 1
    ref_seq = max(seq_counts, key=lambda s: seq_counts[s], default=None)
    base = min((syncs[r] for r in ranks
                if desync[r] == ref_seq and syncs[r] is not None),
               default=None)
    w(f"\n{'rank':>4}  {'steps':>6}  {'sync seq':>8}  {'skew ms':>9}  "
      f"phase\n")
    for r in ranks:
        rep = reports[r]
        steps = _value(rep, "smp_step_total", 0)
        seq = desync[r]
        comparable = (seq is not None and seq == ref_seq
                      and syncs[r] is not None and base is not None)
        skew = f"{(syncs[r] - base) * 1e3:+.3f}" if comparable else "n/a"
        phase = rep.get("meta", {}).get("phase", "?")
        w(f"{r:>4}  {int(steps or 0):>6}  "
          f"{'n/a' if seq is None else int(seq):>8}  {skew:>9}  "
          f"{phase}\n")
    seqs = {v for v in desync.values() if v is not None}
    if len(seqs) > 1:
        w("!! sync sequence numbers differ across ranks "
          f"({desync}): ranks stopped at different barriers (crash or "
          "desync); skew is only shown for ranks at barrier "
          f"{ref_seq}\n")

    w("\n--- aggregate (counters summed, gauges maxed across ranks) ---\n")
    return render(aggregate(reports), out=out)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Pretty-print an SMP telemetry JSON dump "
        "(SMP_TELEMETRY_PATH) as a step report; a directory of per-rank "
        "dumps renders the cross-rank aggregate."
    )
    ap.add_argument("path", help="telemetry JSON file, or a directory of "
                    "per-rank dumps")
    ap.add_argument(
        "--prometheus", action="store_true",
        help="re-render the dump's metrics in Prometheus text format",
    )
    args = ap.parse_args(argv)
    if os.path.isdir(args.path):
        reports = load_rank_dumps(args.path)
        if not reports:
            sys.stderr.write(
                f"no telemetry dumps found in directory {args.path}\n"
            )
            return 2
        if args.prometheus:
            sys.stderr.write(
                "--prometheus applies to a single dump, not a directory\n"
            )
            return 2
        return render_cross_rank(reports)
    try:
        with open(args.path) as f:
            report = json.load(f)
    except (OSError, ValueError) as e:
        sys.stderr.write(f"cannot read telemetry dump {args.path}: {e}\n")
        return 2
    if args.prometheus:
        for name, fam in sorted(report.get("metrics", {}).items()):
            sys.stdout.write(f"# TYPE {name} {fam['kind']}\n")
            for s in fam["series"]:
                lab = ",".join(
                    f'{k}="{v}"' for k, v in sorted(s["labels"].items())
                )
                sfx = f"{{{lab}}}" if lab else ""
                if fam["kind"] == "histogram":
                    acc = 0
                    for b, c in zip(
                        list(s.get("buckets", [])) + ["+Inf"], s["counts"]
                    ):
                        acc += c
                        ble = (lab + "," if lab else "") + f'le="{b}"'
                        sys.stdout.write(f"{name}_bucket{{{ble}}} {acc}\n")
                    sys.stdout.write(f"{name}_sum{sfx} {s['sum']}\n")
                    sys.stdout.write(f"{name}_count{sfx} {s['count']}\n")
                else:
                    sys.stdout.write(f"{name}{sfx} {s['value']}\n")
        return 0
    return render(report)


if __name__ == "__main__":
    sys.exit(main())
