"""PR-17 fleet metrics plane: the canonical cross-rank merge (shared by
the live aggregator and the offline scripts), aggregator election +
fleet windows, the three fleet detectors under fake clocks, the scrape
endpoint, the disabled-constructs-nothing contract, and
slo_report --fleet.

Everything here is tier-1 host-only: planes are built with ``bus=None``
and injected ``alive_fn``/clock; peer snapshots are ingested directly.
The 2-process gloo E2E (aggregator kill -> re-election -> continuous
fleet JSONL) lives in tests/test_multiprocess.py (slow tier).
"""

import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.request

import pytest

from smdistributed_modelparallel_tpu.utils.fleet import (
    FLEET_TX,
    FleetController,
    FleetMetricsPlane,
    fleet_interval,
)
from smdistributed_modelparallel_tpu.utils.flight_recorder import (
    flight_recorder,
)
from smdistributed_modelparallel_tpu.utils.telemetry import (
    LATENCY_BUCKETS,
    TelemetryRegistry,
    merge_metric_reports,
    quantile_from_counts,
    render_prometheus_report,
)

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

import slo_report  # noqa: E402
import telemetry_report  # noqa: E402
import trace_fuse  # noqa: E402


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t


def _serve_registry(ttft=(), itl=(), step=(), kv_used=None,
                    queue_depth=None, finished=0, generated=0):
    reg = TelemetryRegistry()
    lat = reg.histogram("smp_serve_latency_seconds",
                        buckets=LATENCY_BUCKETS)
    for v in ttft:
        lat.labels(kind="ttft").observe(v)
    for v in itl:
        lat.labels(kind="itl").observe(v)
    st = reg.histogram("smp_step_time_seconds", buckets=LATENCY_BUCKETS)
    for v in step:
        st.labels().observe(v)
    if kv_used is not None:
        reg.gauge("smp_serve_kv_blocks").labels(state="used").set(kv_used)
    if queue_depth is not None:
        reg.gauge("smp_serve_queue_depth").labels().set(queue_depth)
    if finished:
        reg.counter("smp_serve_requests_total").labels(
            event="finished").inc(finished)
    if generated:
        reg.counter("smp_serve_tokens_total").labels(
            kind="generated").inc(generated)
    return reg


def _snap(reg, rank, seq=1, t_wall=0.0):
    rep = reg.report()
    return {
        "v": 1, "rank": rank, "seq": seq, "t_wall": t_wall,
        "phase": rep["meta"]["phase"],
        "metrics": {
            n: {"kind": f["kind"], "series": f["series"]}
            for n, f in rep["metrics"].items()
        },
    }


def _plane(world=2, rank=0, alive=None, clock=None, registry=None, **kw):
    clk = clock if clock is not None else FakeClock()
    return FleetMetricsPlane(
        registry=registry if registry is not None else TelemetryRegistry(),
        bus=None, rank=rank, world=world,
        interval=kw.pop("interval", 1.0),
        path=kw.pop("path", ""), port=kw.pop("port", None),
        alive_fn=alive if alive is not None else (lambda p: True),
        clock=clk, wall=clk, slo=kw.pop("slo", None), **kw,
    )


def _gauge(reg, name, **labels):
    fam = reg.report()["metrics"].get(name)
    if fam is None:
        return None
    for s in fam["series"]:
        if s["labels"] == labels:
            return s["value"]
    return None


# ----------------------------------------------------------------------
# The canonical merge: properties + script parity
# ----------------------------------------------------------------------


class TestMergeMetricReports:
    def _reports(self):
        a = _serve_registry(ttft=[0.01, 0.02, 0.4], itl=[0.005],
                            kv_used=10, finished=3, generated=40).report()
        b = _serve_registry(ttft=[0.05], itl=[0.006, 0.2],
                            kv_used=30, finished=5, generated=90).report()
        c = _serve_registry(ttft=[1.5] * 4, kv_used=2, finished=1).report()
        return a, b, c

    def test_commutative(self):
        a, b, _ = self._reports()
        m1 = merge_metric_reports([a, b])
        m2 = merge_metric_reports([b, a])
        assert m1["metrics"] == m2["metrics"]

    def test_associative(self):
        """Counts (the quantile inputs) merge bit-associatively; the
        float ``sum`` field is only approximately associative, as any
        float addition is."""
        a, b, c = self._reports()
        left = merge_metric_reports([merge_metric_reports([a, b]), c])
        right = merge_metric_reports([a, merge_metric_reports([b, c])])
        assert set(left["metrics"]) == set(right["metrics"])
        for name, fam in left["metrics"].items():
            for ls, rs in zip(fam["series"],
                              right["metrics"][name]["series"]):
                for key in ls:
                    if key == "sum":
                        assert ls[key] == pytest.approx(rs[key])
                    else:
                        assert ls[key] == rs[key], (name, key)

    def test_inputs_not_mutated(self):
        a, b, _ = self._reports()
        before = json.dumps([a, b], sort_keys=True)
        merge_metric_reports([a, b])
        assert json.dumps([a, b], sort_keys=True) == before

    def test_counts_sum_and_gauges_max(self):
        a, b, _ = self._reports()
        m = merge_metric_reports({0: a, 1: b})
        assert m["meta"]["ranks"] == [0, 1]
        fam = m["metrics"]["smp_serve_requests_total"]
        assert fam["series"][0]["value"] == 8  # 3 + 5
        kv = m["metrics"]["smp_serve_kv_blocks"]["series"][0]
        assert kv["value"] == 30  # max, not sum
        lat = [s for s in m["metrics"]["smp_serve_latency_seconds"]["series"]
               if s["labels"] == {"kind": "ttft"}][0]
        assert lat["count"] == 4
        assert sum(lat["counts"]) == 4

    def test_merged_quantiles_bounded_by_parts(self):
        """A merged quantile can never leave the envelope of the per-rank
        quantiles (monotonicity under merge)."""
        a, b, _ = self._reports()
        m = merge_metric_reports([a, b])

        def q(report, qq):
            s = [x for x in report["metrics"]["smp_serve_latency_seconds"]
                 ["series"] if x["labels"] == {"kind": "ttft"}][0]
            return quantile_from_counts(s["buckets"], s["counts"], qq)

        for qq in (0.1, 0.5, 0.9, 0.99):
            lo = min(q(a, qq), q(b, qq))
            hi = max(q(a, qq), q(b, qq))
            assert lo - 1e-12 <= q(m, qq) <= hi + 1e-12

    def test_script_aggregate_parity(self):
        """telemetry_report.aggregate (package path) == the pinned stdlib
        fallback == merge_metric_reports: the satellite's before/after
        parity pin."""
        a, b, c = self._reports()
        reports = {0: a, 1: b, 2: c}
        via_script = telemetry_report.aggregate(reports)
        via_fallback = telemetry_report._merge_fallback(reports)
        via_package = merge_metric_reports(reports)
        assert via_script == via_package
        assert via_fallback["metrics"] == via_package["metrics"]
        assert via_fallback["meta"]["ranks"] == [0, 1, 2]

    def test_script_fallback_pinned_semantics(self):
        """Exact-value pin of the merge semantics (counter sum, gauge
        max, bucket-count addition) so a regression in EITHER copy
        fails loudly."""
        buckets = [0.1, 1.0]
        mk = lambda cnt, val, counts: {  # noqa: E731 - local table
            "meta": {"rank": 0},
            "metrics": {
                "smp_c": {"kind": "counter", "help": "",
                          "series": [{"labels": {}, "value": cnt}]},
                "smp_g": {"kind": "gauge", "help": "",
                          "series": [{"labels": {}, "value": val}]},
                "smp_h": {"kind": "histogram", "help": "",
                          "series": [{"labels": {}, "buckets": buckets,
                                      "counts": counts,
                                      "sum": float(sum(counts)),
                                      "count": sum(counts)}]},
            },
        }
        merged = telemetry_report._merge_fallback(
            {0: mk(2, 5.0, [1, 2, 0]), 1: mk(3, 4.0, [0, 1, 4])})
        expected = {
            "smp_c": {"kind": "counter", "help": "",
                      "series": [{"labels": {}, "value": 5}]},
            "smp_g": {"kind": "gauge", "help": "",
                      "series": [{"labels": {}, "value": 5.0}]},
            "smp_h": {"kind": "histogram", "help": "",
                      "series": [{"labels": {}, "buckets": buckets,
                                  "counts": [1, 3, 4], "sum": 8.0,
                                  "count": 8}]},
        }
        assert merged["metrics"] == expected
        assert merge_metric_reports(
            {0: mk(2, 5.0, [1, 2, 0]), 1: mk(3, 4.0, [0, 1, 4])}
        )["metrics"] == expected

    def test_render_prometheus_report_matches_registry(self):
        reg = _serve_registry(ttft=[0.01], finished=2)
        assert (render_prometheus_report(reg.report())
                == reg.render_prometheus())


# ----------------------------------------------------------------------
# Plane: election, windows, bit-equal fleet percentiles
# ----------------------------------------------------------------------


class TestFleetAggregation:
    def test_disabled_constructs_nothing(self, monkeypatch):
        monkeypatch.delenv("SMP_FLEET_INTERVAL", raising=False)
        assert fleet_interval() == 0.0
        assert FleetMetricsPlane.from_env() is None
        monkeypatch.setenv("SMP_FLEET_INTERVAL", "0")
        assert FleetMetricsPlane.from_env() is None
        monkeypatch.setenv("SMP_FLEET_INTERVAL", "bogus")
        assert FleetMetricsPlane.from_env() is None
        # Even with a port configured: no interval, no server.
        monkeypatch.setenv("SMP_METRICS_PORT", "0")
        monkeypatch.setenv("SMP_FLEET_INTERVAL", "0")
        assert FleetMetricsPlane.from_env() is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("smp-fleet")]

    def test_controller_noops_when_disabled(self, monkeypatch):
        monkeypatch.delenv("SMP_FLEET_INTERVAL", raising=False)
        ctl = FleetController()
        assert ctl.start(bus=None) is None
        ctl.tick()   # must not raise
        ctl.stop()
        ctl.reset()

    def test_single_process_window(self):
        clk = FakeClock()
        reg = _serve_registry(ttft=[0.01, 0.02], finished=2, generated=10)
        p = _plane(world=1, rank=0, registry=reg, clock=clk)
        w = p.tick()
        assert w is not None and w["kind"] == "fleet_window"
        assert w["ranks"] == [0] and w["aggregator"] == 0
        assert w["resync"] is True and "tokens_per_s" not in w
        # Second tick before the interval elapses: gated.
        assert p.tick() is None
        clk.t += 1.5
        reg.counter("smp_serve_requests_total").labels(
            event="finished").inc(3)
        reg.counter("smp_serve_tokens_total").labels(
            kind="generated").inc(30)
        w2 = p.tick()
        assert w2["resync"] is False
        assert w2["requests_finished"] == 3
        assert w2["tokens_per_s"] == pytest.approx(30 / 1.5, rel=0.01)

    def test_interval_gate_counts_ticks(self):
        clk = FakeClock()
        p = _plane(world=1, registry=_serve_registry(ttft=[0.01]),
                   clock=clk)
        assert p.tick() is not None
        for _ in range(5):
            clk.t += 0.1
            assert p.tick() is None
        clk.t += 1.0
        assert p.tick() is not None

    def test_election_picks_lowest_alive_and_reelects(self):
        alive = {1: True, 2: True}
        clk = FakeClock()
        p = _plane(world=3, rank=1, alive=lambda r: alive[r] if r in alive
                   else True, clock=clk,
                   registry=_serve_registry(ttft=[0.01]))
        # Rank 0 alive: rank 1 is a publisher, not the aggregator.
        alive[0] = True
        assert p.tick() is None
        assert p.aggregator == 0 and not p.is_aggregator
        # Rank 0 dies: rank 1 takes over and cuts a resync window.
        alive[0] = False
        clk.t += 1.0
        flight_recorder.clear()
        w = p.tick()
        assert p.is_aggregator and w is not None
        assert w["aggregator"] == 1 and w["resync"] is True
        assert 0 in w["dead"]
        events = [e for e in flight_recorder.snapshot()
                  if e.get("kind") == "fleet" and e.get("event") == "elect"]
        assert events and events[-1]["rank"] == 1

    def test_fleet_percentiles_bit_equal_to_offline_merge(self, tmp_path):
        """Acceptance criterion: the scrape endpoint's fleet percentiles
        == telemetry_report.py --dir offline merge of the same ranks'
        dumps, bit for bit."""
        reg0 = _serve_registry(ttft=[0.01, 0.03, 0.2], itl=[0.004, 0.009],
                               step=[0.05])
        reg1 = _serve_registry(ttft=[0.02] * 5 + [1.2], itl=[0.006],
                               step=[0.07, 0.3])
        clk = FakeClock()
        p = _plane(world=2, rank=0, registry=reg0, clock=clk)
        p._ingest(1, _snap(reg1, 1), clk.t)
        p.tick()
        doc = p.fleet_report()
        assert doc["ranks"] == [0, 1]

        # Offline: dump both ranks, aggregate via the script.
        json.dump(reg0.report(),
                  open(tmp_path / "telemetry.json.rank0", "w"))
        json.dump(reg1.report(),
                  open(tmp_path / "telemetry.json.rank1", "w"))
        reports = telemetry_report.load_rank_dumps(str(tmp_path))
        merged = telemetry_report.aggregate(reports)
        for kind in ("ttft", "itl"):
            s = [x for x in merged["metrics"]["smp_serve_latency_seconds"]
                 ["series"] if x["labels"] == {"kind": kind}][0]
            for stat, q in (("p50", 0.5), ("p90", 0.9), ("p99", 0.99)):
                offline = telemetry_report._quantile_from_counts(
                    s["buckets"], s["counts"], q)
                assert doc["percentiles"][kind][f"{stat}_s"] == offline
        st = [x for x in merged["metrics"]["smp_step_time_seconds"]
              ["series"]][0]
        assert doc["percentiles"]["step_time"]["p99_s"] == \
            telemetry_report._quantile_from_counts(
                st["buckets"], st["counts"], 0.99)

    def test_fleet_slo_goodput_and_jsonl_feed(self, tmp_path):
        path = str(tmp_path / "fleet.jsonl")
        clk = FakeClock()
        reg = _serve_registry(ttft=[0.9], finished=1)
        p = _plane(world=1, registry=reg, clock=clk, path=path,
                   slo="ttft_p99_ms=100")
        w = p.tick()
        assert w["slo"]["ok"] is False  # 900ms ttft vs 100ms SLO
        assert "ttft_p99_ms" in w["slo"]["violations"]
        assert w["slo"]["goodput"] == 0.0
        assert _gauge(reg, "smp_fleet_goodput_fraction") == 0.0
        clk.t += 1.0
        w2 = p.tick()  # idle window: no new samples, SLO met vacuously
        assert w2["slo"]["ok"] is True
        assert w2["slo"]["goodput"] == 0.5
        lines = [json.loads(ln) for ln in open(path)]
        assert [ln["seq"] for ln in lines] == [1, 2]
        assert all(ln["kind"] == "fleet_window" for ln in lines)

    def test_gauge_skew_stats(self):
        reg0 = _serve_registry(ttft=[0.01], kv_used=10, queue_depth=2)
        reg1 = _serve_registry(ttft=[0.01], kv_used=30, queue_depth=6)
        clk = FakeClock()
        p = _plane(world=2, rank=0, registry=reg0, clock=clk)
        p._ingest(1, _snap(reg1, 1), clk.t)
        w = p.tick()
        assert w["queue_depth"] == 6  # SLO sees the worst rank
        assert w["queue_depth_by_rank"]["min"] == 2
        assert w["kv_used_by_rank"]["max"] == 30
        assert w["kv_used_by_rank"]["sum"] == 40


# ----------------------------------------------------------------------
# Detectors (fake clocks throughout)
# ----------------------------------------------------------------------


class TestFleetDetectors:
    def test_straggler_fires_on_rigged_slow_rank(self):
        reg0 = _serve_registry(itl=[0.01] * 20)
        reg1 = _serve_registry(itl=[0.25] * 20)  # 25x slower decode
        clk = FakeClock()
        p = _plane(world=2, rank=0, registry=reg0, clock=clk,
                   straggler_ratio_=2.0)
        p._ingest(1, _snap(reg1, 1), clk.t)
        flight_recorder.clear()
        w = p.tick()
        assert w["straggler"]["ranks"] == [1]
        assert w["straggler"]["source"] == "itl"
        assert w["straggler"]["ratios"]["1"] > 2.0
        assert _gauge(p.registry, "smp_fleet_straggler", rank="1") == 1
        assert _gauge(p.registry, "smp_fleet_straggler", rank="0") == 0
        events = [e for e in flight_recorder.snapshot()
                  if e.get("kind") == "fleet"
                  and e.get("event") == "straggler"]
        assert events and events[0]["rank"] == 1
        assert p.straggling == {1}

    def test_straggler_clears_and_uses_step_time_fallback(self):
        reg0 = _serve_registry(step=[0.05] * 10)
        reg1 = _serve_registry(step=[0.05] * 10)
        clk = FakeClock()
        p = _plane(world=2, rank=0, registry=reg0, clock=clk,
                   straggler_ratio_=2.0)
        p._ingest(1, _snap(reg1, 1), clk.t)
        w = p.tick()
        assert "straggler" not in w  # symmetric fleet: nobody fires
        assert _gauge(p.registry, "smp_fleet_straggler_ratio",
                      rank="0") == 1.0

    def test_stale_feed_distinct_from_dead(self):
        """Rank 1 heartbeats but stopped publishing -> stale (stays in
        the merge); rank 2 is dead -> excluded entirely."""
        alive = {1: True, 2: False}
        clk = FakeClock()
        reg0 = _serve_registry(ttft=[0.01], finished=1)
        reg1 = _serve_registry(ttft=[0.02], finished=1)
        p = _plane(world=3, rank=0, registry=reg0, clock=clk,
                   alive=lambda r: alive.get(r, True), stale_windows_=3)
        p._ingest(1, _snap(reg1, 1), clk.t)
        p._ingest(2, _snap(_serve_registry(ttft=[0.03]), 1), clk.t)
        w = p.tick()
        assert w["stale"] == [] and w["dead"] == [2]
        assert w["ranks"] == [0, 1]  # dead rank 2 left the merge
        # Rank 1 goes quiet for > stale_windows * interval but still
        # heartbeats.
        flight_recorder.clear()
        for _ in range(4):
            clk.t += 1.0
            w = p.tick()
        assert w["stale"] == [1]
        assert 1 in w["ranks"]  # stale stays merged, flagged not dropped
        assert _gauge(p.registry, "smp_fleet_stale_feed", rank="1") == 1
        events = [e for e in flight_recorder.snapshot()
                  if e.get("kind") == "fleet"]
        assert any(e["event"] == "stale_feed" and e["rank"] == 1
                   for e in events)
        # It resumes publishing: the flag clears with an edge event.
        p._ingest(1, _snap(reg1, 2), clk.t)
        clk.t += 1.0
        w = p.tick()
        assert w["stale"] == []
        assert _gauge(p.registry, "smp_fleet_stale_feed", rank="1") == 0
        assert any(e.get("event") == "stale_feed_clear"
                   for e in flight_recorder.snapshot()
                   if e.get("kind") == "fleet")

    def test_kv_imbalance_fires(self):
        reg0 = _serve_registry(ttft=[0.01], kv_used=100)
        reg1 = _serve_registry(ttft=[0.01], kv_used=2)
        clk = FakeClock()
        p = _plane(world=2, rank=0, registry=reg0, clock=clk,
                   kv_imbalance_ratio_=1.5)
        p._ingest(1, _snap(reg1, 1), clk.t)
        flight_recorder.clear()
        w = p.tick()
        # max/mean = 100/51 ~ 1.96 > 1.5
        assert w["kv_imbalance"]["ratio"] == pytest.approx(100 / 51,
                                                           abs=1e-3)
        assert w["kv_imbalance"]["worst_rank"] == 0
        assert _gauge(p.registry,
                      "smp_fleet_kv_imbalance_ratio") == pytest.approx(
                          100 / 51, abs=1e-3)
        assert any(e.get("event") == "kv_imbalance"
                   for e in flight_recorder.snapshot()
                   if e.get("kind") == "fleet")


# ----------------------------------------------------------------------
# Scrape endpoint
# ----------------------------------------------------------------------


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


class TestScrapeEndpoint:
    def test_routes_content_types_and_shapes(self):
        reg = _serve_registry(ttft=[0.01, 0.05], finished=2)
        p = _plane(world=1, registry=reg, port=0)
        p.start()
        try:
            assert p.bound_port
            st, ct, body = _get(p.bound_port, "/metrics")
            assert st == 200 and ct.startswith("text/plain")
            assert b"smp_serve_requests_total" in body
            st, ct, body = _get(p.bound_port, "/metrics.json")
            assert st == 200 and ct == "application/json"
            doc = json.loads(body)
            assert "metrics" in doc and "meta" in doc
            p.tick()
            st, ct, body = _get(p.bound_port, "/fleet")
            assert st == 200 and ct == "application/json"
            doc = json.loads(body)
            assert doc["kind"] == "fleet_report"
            assert doc["aggregator"] == 0 and doc["ranks"] == [0]
            assert "ttft" in doc["percentiles"]
            assert doc["freshness"]["0"]["stale"] is False
            st, ct, body = _get(p.bound_port, "/fleet/metrics")
            assert st == 200 and ct.startswith("text/plain")
            assert b"smp_serve_latency_seconds_bucket" in body
        finally:
            p.stop()
        # The port is released on stop.
        with pytest.raises(urllib.error.URLError):
            _get(p.bound_port or 1, "/metrics")

    def test_fleet_view_404_off_aggregator(self):
        # Rank 1 in a world where rank 0 is alive: publisher only.
        p = _plane(world=2, rank=1, registry=_serve_registry(ttft=[0.01]),
                   port=0)
        p.start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(p.bound_port, "/fleet")
            assert ei.value.code == 404
            doc = json.loads(ei.value.read())
            assert doc["aggregator"] == 0 and doc["rank"] == 1
            # Per-rank routes still answer everywhere.
            st, _, _ = _get(p.bound_port, "/metrics")
            assert st == 200
        finally:
            p.stop()

    def test_no_port_no_server(self):
        p = _plane(world=1, registry=_serve_registry(ttft=[0.01]),
                   port=None)
        p.start()
        try:
            assert p.bound_port is None
            assert not [t for t in threading.enumerate()
                        if t.name == "smp-fleet-http"]
        finally:
            p.stop()

    def test_stop_is_idempotent_and_final_flushes(self, tmp_path):
        path = str(tmp_path / "fleet.jsonl")
        p = _plane(world=1, registry=_serve_registry(ttft=[0.01]),
                   path=path)
        p.start()
        p.stop()
        p.stop()
        lines = [json.loads(ln) for ln in open(path)]
        assert lines and lines[-1]["kind"] == "fleet_window"
        # Stopped plane ticks are no-ops.
        assert p.tick() is None


# ----------------------------------------------------------------------
# slo_report --fleet
# ----------------------------------------------------------------------


class TestSloReportFleet:
    def _write_feed(self, path, verdicts):
        with open(path, "w") as fh:
            for i, ok in enumerate(verdicts):
                fh.write(json.dumps({
                    "kind": "fleet_window", "seq": i + 1,
                    "t_wall": 100.0 + i, "window_s": 1.0,
                    "ttft_p99_ms": 40.0 if ok else 900.0,
                    "slo": {"ok": ok,
                            "violations": {} if ok else
                            {"ttft_p99_ms": {"limit": 100.0,
                                             "value": 900.0}}},
                }) + "\n")

    def test_embedded_verdicts_and_check_exit_codes(self, tmp_path, capsys):
        feed = str(tmp_path / "fleet.jsonl")
        self._write_feed(feed, [True, True, False, True])
        assert slo_report.main([feed, "--fleet"]) == 0
        out = capsys.readouterr().out
        assert "fleet SLO report" in out
        assert "75.0%" in out
        assert slo_report.main([feed, "--fleet", "--check"]) == 1
        assert slo_report.main(
            [feed, "--fleet", "--check", "--min-goodput", "0.7"]) == 0

    def test_reevaluate_with_slo_flag(self, tmp_path):
        feed = str(tmp_path / "fleet.jsonl")
        self._write_feed(feed, [True, True])
        # Tighten the SLO offline: both windows' 40ms p99 now violate.
        assert slo_report.main(
            [feed, "--fleet", "--slo", "ttft_p99_ms=10", "--check"]) == 1

    def test_nothing_to_evaluate_is_2(self, tmp_path):
        empty = str(tmp_path / "empty.jsonl")
        open(empty, "w").close()
        assert slo_report.main([empty, "--fleet", "--check"]) == 2
        # serve_window records are NOT fleet windows.
        sw = str(tmp_path / "serve.jsonl")
        with open(sw, "w") as fh:
            fh.write(json.dumps({"kind": "serve_window", "seq": 1}) + "\n")
        assert slo_report.main([sw, "--fleet", "--check"]) == 2

    def test_synthesizes_fleet_window_from_rank_dumps(self, tmp_path,
                                                      capsys):
        """Dir mode over per-rank telemetry dumps: the shared merge
        builds one cumulative fleet window and the verdict matches the
        merged-bucket percentile."""
        reg0 = _serve_registry(ttft=[0.01] * 9)
        reg1 = _serve_registry(ttft=[0.8])  # one slow rank drags p99 up
        json.dump(reg0.report(),
                  open(tmp_path / "telemetry.json.rank0", "w"))
        json.dump(reg1.report(),
                  open(tmp_path / "telemetry.json.rank1", "w"))
        assert slo_report.main(
            [str(tmp_path), "--fleet", "--slo", "ttft_p99_ms=500",
             "--check"]) == 1
        # Loose SLO over the same dumps passes.
        assert slo_report.main(
            [str(tmp_path), "--fleet", "--slo", "ttft_p99_ms=2000",
             "--check"]) == 0
        # And the synthesized percentile is the bit-equal offline merge.
        merged = merge_metric_reports([reg0.report(), reg1.report()])
        s = [x for x in merged["metrics"]["smp_serve_latency_seconds"]
             ["series"] if x["labels"] == {"kind": "ttft"}][0]
        expect = round(1e3 * quantile_from_counts(
            s["buckets"], s["counts"], 0.99), 3)
        win = slo_report.synthesize_fleet_window([str(tmp_path)])
        assert win["ttft_p99_ms"] == expect
        assert win["synthesized"] is True


# ----------------------------------------------------------------------
# trace_fuse naming
# ----------------------------------------------------------------------


class TestFleetTooling:
    def test_trace_fuse_names_fleet_events(self):
        stream = trace_fuse.Stream(path="flight.json", kind="recorder",
                                   rank=0)
        stream.offset_us = 0.0
        stream.events = [{"kind": "fleet", "event": "straggler", "rank": 1,
                          "detail": "itl p99 ratio 3.1 > 2.0",
                          "ts_us": 10.0, "id": 1}]
        doc = trace_fuse.fuse([stream])
        names = [e["name"] for e in doc["traceEvents"]
                 if e.get("ph") == "i"]
        assert "fleet:straggler@r1" in names


# ----------------------------------------------------------------------
# Snapshot wire format (what rides control tx -7)
# ----------------------------------------------------------------------


class TestSnapshotWire:
    def test_tx_is_reserved_and_negative(self):
        from smdistributed_modelparallel_tpu.resilience.supervisor import (
            HEARTBEAT_TX,
            RECOVERY_TX,
        )
        from smdistributed_modelparallel_tpu.serving.replica import (
            SERVE_MIRROR_TX,
        )

        assert FLEET_TX == -7
        assert len({FLEET_TX, SERVE_MIRROR_TX, HEARTBEAT_TX,
                    RECOVERY_TX}) == 4

    def test_snapshot_strips_help_and_round_trips(self):
        reg = _serve_registry(ttft=[0.01], finished=1)
        p = _plane(world=1, registry=reg)
        snap = p._local_snapshot()
        wire = json.loads(json.dumps(snap))  # survives the bus encoding
        assert wire["rank"] == 0 and wire["v"] == 1
        for fam in wire["metrics"].values():
            assert "help" not in fam
        # Ingesting the wire form merges identically to the local form.
        merged = merge_metric_reports(
            [{"meta": {"rank": 0}, "metrics": wire["metrics"]}])
        assert merged["metrics"]["smp_serve_requests_total"]["series"][0][
            "value"] == 1

    def test_out_of_order_frames_keep_freshest(self):
        clk = FakeClock()
        reg = _serve_registry(finished=1)
        p = _plane(world=2, rank=0, registry=_serve_registry(ttft=[0.01]),
                   clock=clk)
        p._ingest(1, _snap(reg, 1, seq=5), clk.t)
        p._ingest(1, _snap(_serve_registry(finished=99), 1, seq=4), clk.t)
        assert p._snapshots[1]["snap"]["seq"] == 5
