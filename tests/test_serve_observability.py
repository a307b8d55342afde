"""PR-16 serving SLO observability: streaming percentile histograms,
per-request span tracing, the metrics time-series ring (the autoscaler
feed), and the SLO gate.

Tiers: pure-host units under a fake clock (bucket boundaries, quantile
interpolation, cross-rank histogram merge, window rotation + delta
rates, SLO verdict flips, slo_report exit codes, span pairing) plus one
compiled engine E2E that drives greedy / stochastic / EOS-early-stop /
resumed requests through the full trace pipeline with
``jax.block_until_ready`` rigged to raise — the no-per-token-device-sync
claim is an assertion, not a comment.
"""

import json
import os
import sys
import threading
import time

import pytest

from smdistributed_modelparallel_tpu.utils import telemetry as tel
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.telemetry import (
    LATENCY_BUCKETS,
    TelemetryRegistry,
    _geometric_buckets,
    quantile_from_counts,
    record_serve_latency,
    record_serve_occupancy,
    record_serve_request,
    record_serve_tokens,
    record_step_time,
    serve_latency_summary,
    telemetry,
)
from smdistributed_modelparallel_tpu.utils.timeseries import (
    MetricsTimeSeries,
    evaluate_slo,
    parse_slo,
)

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

import slo_report  # noqa: E402
import telemetry_report  # noqa: E402
import trace_fuse  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    yield
    telemetry.reset()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _gauge(report, name, **labels):
    fam = report["metrics"].get(name)
    if not fam:
        return None
    for s in fam["series"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value")
    return None


# ---------------------------------------------------------------------------
# streaming percentile histograms
# ---------------------------------------------------------------------------


class TestLogHistogram:
    def test_buckets_geometric_fixed_and_deterministic(self):
        assert LATENCY_BUCKETS[0] == pytest.approx(5e-4)
        for lo, hi in zip(LATENCY_BUCKETS, LATENCY_BUCKETS[1:]):
            assert hi / lo == pytest.approx(1.3, rel=1e-6)
        assert LATENCY_BUCKETS[-1] >= 240.0
        # Deterministic: the mergeability contract is every process
        # computing the identical tuple.
        assert LATENCY_BUCKETS == _geometric_buckets(5e-4, 240.0, 1.3)
        # Fixed memory: a histogram is ~50 counts regardless of samples.
        assert len(LATENCY_BUCKETS) < 60

    def test_observe_le_boundary_semantics(self):
        reg = TelemetryRegistry()
        h = reg.histogram("h", "t", buckets=(1.0, 2.0, 4.0))
        for v in (1.0, 1.0001, 5.0, 0.0):
            h.labels().observe(v)
        (s,) = reg.report()["metrics"]["h"]["series"]
        # le semantics: 1.0 and 0.0 land in bucket0 (<=1.0), 1.0001 in
        # bucket1, 5.0 in the overflow bucket.
        assert s["counts"] == [2, 1, 0, 1]
        assert s["count"] == 4 and s["sum"] == pytest.approx(7.0001)

    def test_quantile_edges_and_monotonicity(self):
        b = list(LATENCY_BUCKETS)
        assert quantile_from_counts(b, [0] * (len(b) + 1), 0.5) is None
        # Everything in the overflow bucket clamps to the last boundary.
        over = [0] * len(b) + [7]
        assert quantile_from_counts(b, over, 0.99) == b[-1]
        counts = [0] * (len(b) + 1)
        counts[3], counts[10], counts[20] = 5, 3, 2
        qs = [quantile_from_counts(b, counts, q)
              for q in (0.1, 0.5, 0.9, 0.99)]
        assert all(a <= z for a, z in zip(qs, qs[1:]))
        # Interpolated values stay inside their bucket's bounds.
        assert b[2] <= qs[0] <= b[3]

    def test_cross_rank_merge(self):
        r0, r1 = TelemetryRegistry(), TelemetryRegistry()
        for reg, vals in ((r0, (0.01, 0.02)), (r1, (0.2, 0.4, 0.8))):
            h = reg.histogram("smp_serve_latency_seconds", "t",
                              buckets=LATENCY_BUCKETS)
            for v in vals:
                h.labels(kind="ttft").observe(v)
        merged = telemetry_report.aggregate(
            {0: r0.report(), 1: r1.report()}
        )
        (s,) = merged["metrics"]["smp_serve_latency_seconds"]["series"]
        assert s["count"] == 5
        assert sum(s["counts"]) == 5
        q50 = quantile_from_counts(s["buckets"], s["counts"], 0.5)
        assert 0.01 < q50 < 0.8  # between the per-rank extremes

    def test_record_serve_latency_gauges_and_summary(self):
        for ms in (5, 10, 20, 40, 400):
            record_serve_latency("ttft", ms / 1e3)
        rep = telemetry.report()
        last = _gauge(rep, "smp_serve_ttft_seconds", stat="last")
        mean = _gauge(rep, "smp_serve_ttft_seconds", stat="mean")
        p50 = _gauge(rep, "smp_serve_ttft_seconds", stat="p50")
        p99 = _gauge(rep, "smp_serve_ttft_seconds", stat="p99")
        assert last == pytest.approx(0.4)
        assert mean == pytest.approx(0.095)
        assert p99 >= p50 > 0
        summ = serve_latency_summary("ttft", qs=(0.5, 0.99))
        assert summ["count"] == 5
        assert summ["mean_s"] == pytest.approx(0.095)
        assert summ["quantiles_s"][0.99] >= summ["quantiles_s"][0.5]
        assert serve_latency_summary("itl") is None

    def test_record_step_time_histogram(self):
        for v in (0.1, 0.1, 0.1, 2.0):
            record_step_time(v)
        rep = telemetry.report()
        (s,) = rep["metrics"]["smp_step_time_seconds"]["series"]
        assert s["count"] == 4
        p50 = _gauge(rep, "smp_step_time_quantile_seconds", stat="p50")
        p99 = _gauge(rep, "smp_step_time_quantile_seconds", stat="p99")
        assert p99 >= p50 > 0


# ---------------------------------------------------------------------------
# metrics time-series
# ---------------------------------------------------------------------------


def _ts(clk, **kw):
    kw.setdefault("registry", telemetry)
    kw.setdefault("interval", 1.0)
    kw.setdefault("clock", clk)
    kw.setdefault("wall", lambda: 1700000000.0 + clk.t)
    kw.setdefault("path", "")
    return MetricsTimeSeries(**kw)


class TestTimeSeries:
    def test_window_rotation_and_interval_gate(self):
        clk = FakeClock()
        ts = _ts(clk)
        clk.advance(0.5)
        assert ts.maybe_sample() is None  # interval not elapsed
        clk.advance(0.5)
        w1 = ts.maybe_sample()
        assert w1["seq"] == 1 and w1["window_s"] == pytest.approx(1.0)
        assert ts.maybe_sample() is None  # gate re-arms
        clk.advance(2.5)
        w2 = ts.maybe_sample()
        assert w2["seq"] == 2 and w2["window_s"] == pytest.approx(2.5)

    def test_windowed_rates_differ_from_lifetime(self):
        clk = FakeClock()
        ts = _ts(clk, chips=2)
        # Burst window: 100 generated tokens, 4 completions in 1s.
        record_serve_tokens("generated", 100)
        record_serve_request("finished", 4)
        record_serve_request("admitted", 4)
        clk.advance(1.0)
        w1 = ts.maybe_sample()
        assert w1["tokens_per_s"] == pytest.approx(100.0)
        assert w1["tokens_per_s_chip"] == pytest.approx(50.0)
        assert w1["requests_per_s"] == pytest.approx(4.0)
        assert w1["requests_finished"] == 4
        # Idle window: windowed rate collapses to 0 while the lifetime
        # rate averages the burst into history — the satellite-1 fix is
        # exactly this divergence being visible.
        clk.advance(1.0)
        w2 = ts.maybe_sample()
        assert w2["tokens_per_s"] == 0.0
        assert w2["lifetime_tokens_per_s"] == pytest.approx(50.0)
        assert w2["tokens_per_s"] != w2["lifetime_tokens_per_s"]
        rep = telemetry.report()
        assert _gauge(rep, "smp_serve_tokens_per_sec",
                      scope="engine") == 0.0
        assert _gauge(rep, "smp_serve_requests_per_sec") == 0.0
        assert _gauge(rep, "smp_timeseries_windows") == 2

    def test_window_percentiles_use_bucket_deltas(self):
        clk = FakeClock()
        ts = _ts(clk)
        for _ in range(20):
            record_serve_latency("ttft", 0.010)
        clk.advance(1.0)
        w1 = ts.maybe_sample()
        assert w1["ttft_mean_ms"] == pytest.approx(10.0)
        assert w1["ttft_p50_ms"] == pytest.approx(10.0, rel=0.35)
        # Second window: only slow samples. Cumulative percentiles would
        # be dragged toward the 20 fast samples of window 1; the delta
        # distribution must not be.
        for _ in range(5):
            record_serve_latency("ttft", 0.200)
        clk.advance(1.0)
        w2 = ts.maybe_sample()
        assert w2["ttft_mean_ms"] == pytest.approx(200.0)
        assert w2["ttft_p50_ms"] == pytest.approx(200.0, rel=0.35)
        assert w2["ttft_p50_ms"] > 10 * w1["ttft_p50_ms"]
        # An idle window records no percentile keys at all.
        clk.advance(1.0)
        w3 = ts.maybe_sample()
        assert "ttft_p50_ms" not in w3 and "ttft_mean_ms" not in w3

    def test_ring_bound_and_jsonl_feed(self, tmp_path):
        clk = FakeClock()
        path = str(tmp_path / "ts.jsonl")
        ts = _ts(clk, size=2, path=path)
        for _ in range(3):
            clk.advance(1.0)
            ts.maybe_sample()
        snaps = ts.snapshots()
        assert [w["seq"] for w in snaps] == [2, 3]  # ring bounded
        lines = [json.loads(ln) for ln in
                 open(path).read().splitlines() if ln]
        assert len(lines) == 3  # the JSONL keeps everything
        assert all(ln["kind"] == "serve_window" for ln in lines)

    def test_slo_verdict_flip_goodput_and_counters(self):
        clk = FakeClock()
        ts = _ts(clk, slo="ttft_p99_ms=50,queue_depth=8")
        record_serve_latency("ttft", 0.005)
        clk.advance(1.0)
        w1 = ts.maybe_sample()
        assert w1["slo"]["ok"] and w1["slo"]["goodput"] == 1.0
        for _ in range(3):
            record_serve_latency("ttft", 0.200)
        clk.advance(1.0)
        w2 = ts.maybe_sample()
        assert not w2["slo"]["ok"]
        assert "ttft_p99_ms" in w2["slo"]["violations"]
        assert w2["slo"]["goodput"] == pytest.approx(0.5)
        # Occupancy-driven violation on a third window.
        record_serve_occupancy(20, 4, 4, 10, 2, 0, 12)
        clk.advance(1.0)
        w3 = ts.maybe_sample()
        assert "queue_depth" in w3["slo"]["violations"]
        rep = telemetry.report()
        assert _gauge(rep, "smp_slo_goodput_fraction") == pytest.approx(
            1.0 / 3.0
        )
        assert _gauge(rep, "smp_slo_ok") == 0.0
        assert _gauge(rep, "smp_slo_violations_total",
                      slo="ttft_p99_ms") == 1
        assert _gauge(rep, "smp_slo_violations_total",
                      slo="queue_depth") == 1

    def test_parse_slo(self):
        slo = parse_slo("ttft_p99_ms=500, itl_p99_ms=50,queue_depth=8")
        assert slo == {"ttft_p99_ms": 500.0, "itl_p99_ms": 50.0,
                       "queue_depth": 8.0}
        assert parse_slo("") == {}
        with pytest.raises(SMPValidationError, match="unknown SLO key"):
            parse_slo("ttfff_p99_ms=500")
        with pytest.raises(SMPValidationError, match="lacks"):
            parse_slo("ttft_p99_ms")
        with pytest.raises(SMPValidationError, match="not a number"):
            parse_slo("ttft_p99_ms=fast")

    def test_evaluate_slo_bounds_and_missing_values(self):
        v = evaluate_slo({"tokens_per_s_min": 20.0},
                         {"tokens_per_s": 10.0})
        assert not v["ok"] and "tokens_per_s_min" in v["violations"]
        v = evaluate_slo({"tokens_per_s_min": 20.0},
                         {"tokens_per_s": 30.0})
        assert v["ok"]
        # A key the window has no value for is not a violation.
        v = evaluate_slo({"ttft_p99_ms": 1.0}, {"queue_depth": 0.0})
        assert v["ok"]

    def test_disabled_constructs_nothing(self, monkeypatch):
        monkeypatch.delenv("SMP_TIMESERIES_INTERVAL", raising=False)
        assert MetricsTimeSeries.from_env() is None
        monkeypatch.setenv("SMP_TIMESERIES_INTERVAL", "0")
        assert MetricsTimeSeries.from_env() is None
        monkeypatch.setenv("SMP_TIMESERIES_INTERVAL", "banana")
        assert MetricsTimeSeries.from_env() is None
        ts = MetricsTimeSeries(interval=0.0, registry=telemetry)
        assert not ts.enabled and ts._prev is None
        assert ts.start() is None and ts.maybe_sample() is None
        assert not any(
            t.name == MetricsTimeSeries.THREAD_NAME
            for t in threading.enumerate()
        )

    def test_snapshotter_thread_lifecycle(self):
        ts = MetricsTimeSeries(interval=0.03, registry=telemetry, path="")
        ts.start()
        assert any(t.name == MetricsTimeSeries.THREAD_NAME
                   for t in threading.enumerate())
        deadline = time.time() + 5.0
        while not ts.snapshots() and time.time() < deadline:
            time.sleep(0.01)
        ts.stop()
        ts.stop()  # idempotent
        assert len(ts.snapshots()) >= 1
        assert not any(t.name == MetricsTimeSeries.THREAD_NAME
                       for t in threading.enumerate())


# ---------------------------------------------------------------------------
# slo_report.py gate
# ---------------------------------------------------------------------------


def _window(seq, **kw):
    w = {"kind": "serve_window", "seq": seq, "t_wall": 1000.0 + seq,
         "window_s": 1.0, "tokens_per_s": 50.0, "queue_depth": 0.0}
    w.update(kw)
    return w


def _write_jsonl(path, windows):
    with open(path, "w") as f:
        for w in windows:
            f.write(json.dumps(w) + "\n")
    return str(path)


class TestSLOReportScript:
    def test_check_exit_codes(self, tmp_path, capsys):
        p = _write_jsonl(tmp_path / "ts.jsonl", [
            _window(1, ttft_p99_ms=10.0),
            _window(2, ttft_p99_ms=100.0),
        ])
        assert slo_report.main(
            [p, "--slo", "ttft_p99_ms=500", "--check"]) == 0
        assert slo_report.main(
            [p, "--slo", "ttft_p99_ms=50", "--check"]) == 1
        assert slo_report.main(
            [p, "--slo", "ttft_p99_ms=50", "--check",
             "--min-goodput", "0.5"]) == 0
        out = capsys.readouterr().out
        assert "goodput" in out and "PASS" in out and "FAIL" in out
        assert "ttft_p99_ms" in out

    def test_nothing_to_evaluate_is_rc2(self, tmp_path):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert slo_report.main([str(empty), "--check"]) == 2
        p = _write_jsonl(tmp_path / "ts.jsonl", [_window(1)])
        # Windows but no embedded verdicts and no --slo.
        assert slo_report.main([p, "--check"]) == 2
        # Bad / empty spec.
        assert slo_report.main([p, "--slo", "bogus_key=1"]) == 2
        assert slo_report.main([p, "--slo", " , "]) == 2

    def test_embedded_verdicts_and_dir_mode(self, tmp_path):
        d = tmp_path / "dumps"
        d.mkdir()
        _write_jsonl(d / "ts.jsonl.rank0", [
            _window(1, slo={"ok": True, "violations": {}}),
        ])
        _write_jsonl(d / "ts.jsonl.rank1", [
            _window(1, slo={"ok": False, "violations": {
                "itl_p99_ms": {"limit": 5.0, "value": 9.0}}}),
        ])
        assert slo_report.main([str(d), "--check"]) == 1
        assert slo_report.main(
            [str(d), "--check", "--min-goodput", "0.5"]) == 0


# ---------------------------------------------------------------------------
# span pairing + trace fusion (pure host)
# ---------------------------------------------------------------------------


def _ev(ts_us, event, rid, trace=None, slot=-1, pos=-1):
    return {"kind": "serve", "ts_us": ts_us, "event": event, "rid": rid,
            "trace": trace or rid, "slot": slot, "pos": pos}


class TestServeSpans:
    def test_lifecycle_pairs_into_closed_spans(self):
        events = [
            _ev(0, "queued", "r0"),
            _ev(10, "admitted", "r0", slot=1),
            _ev(12, "prefill_chunk", "r0", slot=1, pos=4),
            _ev(20, "first_token", "r0", slot=1),
            _ev(90, "finished", "r0", slot=1, pos=8),
        ]
        spans, chunks, findings = trace_fuse.serve_request_spans(events)
        assert findings == []
        assert {s["name"] for s in spans} == {
            "queued:r0", "prefill:r0", "decode:r0"}
        by = {s["name"]: s for s in spans}
        assert by["queued:r0"]["tid"] == "serve queue"
        assert by["prefill:r0"]["tid"] == "slot 1"
        assert by["decode:r0"]["dur"] == pytest.approx(70.0)
        assert len(chunks) == 1

    def test_failover_readmission_continues_one_trace(self):
        # rid changes ring, trace id does not: the survivor's readmitted
        # events join the dead replica's queued/admitted under one trace.
        events = [
            _ev(0, "queued", "r7"),
            _ev(5, "admitted", "r7", slot=0),
            _ev(8, "first_token", "r7", slot=0),
            _ev(40, "readmitted", "r7", trace="r7", slot=2),
        ]
        spans, _, findings = trace_fuse.serve_request_spans(events)
        # readmitted after first_token is out of lifecycle order AND the
        # decode edge never closed in this ring.
        assert any("out of lifecycle order" in f for f in findings)
        assert any("left open" in f for f in findings)
        # A clean cross-ring trace: queued -> readmitted -> finished.
        events = [
            _ev(0, "queued", "r8"),
            _ev(5, "readmitted", "r8", slot=2, pos=3),
            _ev(7, "first_token", "r8", slot=2),
            _ev(30, "finished", "r8", slot=2),
        ]
        spans, _, findings = trace_fuse.serve_request_spans(events)
        assert findings == []
        assert {s["name"] for s in spans} == {
            "queued:r8", "prefill:r8", "decode:r8"}

    def test_fully_resumed_and_open_spans(self):
        events = [
            _ev(0, "queued", "ra"),
            _ev(2, "finished", "ra"),       # fully-resumed fast path
            _ev(0, "queued", "rb"),
            _ev(4, "admitted", "rb", slot=0),
        ]
        spans, _, findings = trace_fuse.serve_request_spans(events)
        names = {s["name"] for s in spans}
        assert "resumed:ra" in names
        assert any("rb" in f and "left open" in f for f in findings)

    def test_fuse_emits_slot_span_lanes(self, tmp_path):
        ring = tmp_path / "flight.jsonl.rank0"
        with open(ring, "w") as f:
            f.write(json.dumps({"kind": "meta", "rank": 0, "size": 64,
                                "anchor_unix_us": 0}) + "\n")
            for ev in [
                _ev(0, "queued", "r0"),
                _ev(10, "admitted", "r0", slot=0),
                _ev(20, "first_token", "r0", slot=0),
                _ev(50, "finished", "r0", slot=0),
            ]:
                f.write(json.dumps(dict(ev, id=1)) + "\n")
        out = tmp_path / "fused.json"
        rc = trace_fuse.main(
            ["-o", str(out), "--no-report", str(ring)])
        assert rc in (0, None)
        trace = json.load(open(out))
        slot_spans = [e for e in trace["traceEvents"]
                      if e.get("ph") == "X"
                      and str(e.get("tid", "")).startswith("slot ")]
        assert {e["name"] for e in slot_spans} == {
            "prefill:r0", "decode:r0"}
        # Serve events must not ALSO appear as flight_recorder instants.
        assert not any(
            e.get("tid") == "flight_recorder"
            and "serve" in str(e.get("name", ""))
            for e in trace["traceEvents"]
        )
        import io

        streams = [trace_fuse.load_stream(str(ring))]
        table = trace_fuse.align(streams)
        buf = io.StringIO()
        trace_fuse.render_report(streams, table, out=buf)
        assert "serving request traces" in buf.getvalue()


# ---------------------------------------------------------------------------
# report rendering
# ---------------------------------------------------------------------------


class TestReportRendering:
    def test_serving_section_percentiles_and_slo(self, capsys):
        import io

        for ms in (5, 10, 400):
            record_serve_latency("ttft", ms / 1e3)
            record_serve_latency("itl", ms / 1e3)
        record_serve_request("admitted", 3)
        record_serve_request("finished", 3)
        telemetry.gauge("smp_timeseries_windows", "t").set(4)
        telemetry.gauge("smp_slo_goodput_fraction", "t").set(0.75)
        telemetry.counter("smp_slo_violations_total", "t").labels(
            slo="ttft_p99_ms").inc(1)
        buf = io.StringIO()
        telemetry_report.render(telemetry.report(), out=buf)
        text = buf.getvalue()
        assert "latency (ms)" in text and "p99" in text
        assert "ttft" in text and "itl" in text
        assert "slo: 4 window(s)" in text
        assert "goodput 75.0%" in text
        assert "ttft_p99_ms x1" in text

    def test_step_time_percentiles_render(self):
        import io

        record_step_time(0.1)
        record_step_time(0.3)
        buf = io.StringIO()
        telemetry_report.render(telemetry.report(), out=buf)
        assert "step time p50/p90/p99" in buf.getvalue()

    def test_cross_rank_percentile_aggregate(self):
        import io

        r0, r1 = TelemetryRegistry(), TelemetryRegistry()
        for reg, ms in ((r0, 10), (r1, 100)):
            h = reg.histogram("smp_serve_latency_seconds", "t",
                              buckets=LATENCY_BUCKETS)
            for _ in range(4):
                h.labels(kind="ttft").observe(ms / 1e3)
            reg.counter("smp_serve_requests_total", "t").labels(
                event="admitted").inc(4)
        merged = telemetry_report.aggregate(
            {0: r0.report(), 1: r1.report()})
        buf = io.StringIO()
        telemetry_report.render(merged, out=buf)
        text = buf.getvalue()
        assert "latency (ms)" in text
        # 8 merged samples across both ranks on one row.
        assert "ttft" in text


# ---------------------------------------------------------------------------
# engine E2E: traces closed, windows written, no per-token device sync
# ---------------------------------------------------------------------------


class TestEngineTraceE2E:
    def test_trace_timeseries_and_slo_end_to_end(
            self, tmp_path, monkeypatch):
        import jax

        import smdistributed_modelparallel_tpu as smp
        from smdistributed_modelparallel_tpu.models.transformer_lm import (
            TransformerLM,
        )
        from smdistributed_modelparallel_tpu.serving import (
            ServeRequest,
            ServingEngine,
        )
        from smdistributed_modelparallel_tpu.utils.flight_recorder import (
            flight_recorder,
        )

        ts_path = str(tmp_path / "ts.jsonl")
        monkeypatch.setenv("SMP_TIMESERIES_INTERVAL", "0.05")
        monkeypatch.setenv("SMP_TIMESERIES_PATH", ts_path)
        monkeypatch.setenv(
            "SMP_SLO", "ttft_p99_ms=60000,itl_p99_ms=60000,queue_depth=64"
        )
        smp.init({})
        flight_recorder.clear()
        mod = TransformerLM(vocab_size=97, max_len=64, d_model=32,
                            n_layers=2, n_heads=4)
        import jax.numpy as jnp

        params = mod.init(jax.random.key(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
        engine = ServingEngine(
            mod, params=params, max_slots=2, block_tokens_override=4,
            prefill_chunk=4,
        )
        assert engine.timeseries is not None
        assert not hasattr(engine, "_ttft_sum")  # satellite 2
        engine._program("prefill")
        engine._program("decode")

        prompt = list(range(1, 9))

        def _req(rid, **kw):
            kw.setdefault("temperature", 0.0)
            kw.setdefault("seed", 3)
            return ServeRequest(rid, prompt, kw.pop("max_new", 6), **kw)

        # Phase 1 (greedy + stochastic) runs with jax.block_until_ready
        # rigged to raise: the tracing/latency path must never add a
        # per-token device sync (host timestamps only).
        def _no_sync(*a, **k):
            raise AssertionError(
                "serving tick called jax.block_until_ready"
            )

        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "block_until_ready", _no_sync)
            results = engine.run([
                _req("r0"),
                _req("r1", temperature=0.8),
            ], timeout_s=240.0)
        eos = int(results["r0"][1])
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "block_until_ready", _no_sync)
            results2 = engine.run([
                # Same prompt/greedy as r0 but stops at token 2 via EOS.
                _req("r2", eos_token_id=eos),
                # Resumed re-admission: continues r0's trace id.
                _req("r3", resume_tokens=tuple(results["r0"][:2]),
                     trace_id="r0"),
            ], timeout_s=240.0)
        assert int(results2["r2"][-1]) == eos
        assert len(results2["r2"]) <= 2  # EOS early stop
        assert ([int(x) for x in results2["r3"]]
                == [int(x) for x in results["r0"]])

        # Trace continuity is mirrored for failover peers.
        assert engine.mirror_log["r3"]["trace_id"] == "r0"
        assert engine.mirror_log["r0"]["trace_id"] == "r0"

        # >= 3 time-series windows (idle samples extend the feed).
        for _ in range(3):
            time.sleep(engine.timeseries.interval + 0.01)
            engine.timeseries.maybe_sample()
        snaps = engine.timeseries.snapshots()
        assert len(snaps) >= 3
        assert any(w.get("tokens_generated", 0) > 0 for w in snaps)
        assert all("slo" in w for w in snaps)
        lines = [json.loads(ln) for ln in
                 open(ts_path).read().splitlines() if ln]
        assert len(lines) == len(snaps)
        assert lines[-1]["seq"] == snaps[-1]["seq"]

        # Histogram-derived latency stats: nonzero, ordered.
        summ = serve_latency_summary("ttft", qs=(0.5, 0.9, 0.99))
        assert summ["count"] >= 3
        assert (summ["quantiles_s"][0.99] >= summ["quantiles_s"][0.5]
                > 0.0)

        # Every admitted request's spans close; r3 re-admits into r0's
        # trace (the readmitted edge) and slot lanes stay within range.
        ring = str(tmp_path / "flight.jsonl")
        flight_recorder.dump(ring)
        stream = trace_fuse.load_stream(ring)
        serve_events = [e for e in stream.events
                        if e.get("kind") == "serve"]
        assert any(e["event"] == "readmitted" and e["rid"] == "r3"
                   for e in serve_events)
        spans, _, findings = trace_fuse.serve_request_spans(serve_events)
        assert not any("left open" in f for f in findings)
        lanes = {s["tid"] for s in spans if s["tid"].startswith("slot ")}
        assert lanes and lanes <= {"slot 0", "slot 1"}
        fused = str(tmp_path / "fused.json")
        rc = trace_fuse.main(["-o", fused, "--no-report", ring])
        assert rc in (0, None)

        # The SLO gate passes on the generous run-time spec and fails a
        # tightened offline what-if.
        assert slo_report.main([ts_path, "--check"]) == 0
        assert slo_report.main(
            [ts_path, "--slo", "tokens_per_s_min=1e12", "--check"]) == 1

        engine.close()
        assert not any(
            t.name == MetricsTimeSeries.THREAD_NAME
            for t in threading.enumerate()
        )
