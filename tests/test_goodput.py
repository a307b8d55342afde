"""PR-18 goodput ledger: exclusive-and-exhaustive wall-clock attribution
(the fake-clock sum-to-wall-clock invariant), the perf-regression
sentinel's change-point latch, the auto-forensics engine's cooldown /
cap rate limiting, the zero-cost-off contract, and the folds into the
watchdog dump, the time-series windows, the fleet windows, and the
slo_report script gate.

Everything here is tier-1 host-only: ledgers are built with injected
fake clocks and fresh ``TelemetryRegistry`` instances, never the
process singletons.
"""

import json
import os
import random
import sys

import pytest

from smdistributed_modelparallel_tpu.utils.goodput import (
    DEFAULT_FORENSICS_MAX,
    FORENSICS_PATH_ENV,
    GOODPUT_ENV,
    GOODPUT_MIN_ENV,
    PRODUCTIVE,
    REGRESSION_RATIO_ENV,
    STATES,
    ForensicsEngine,
    GoodputController,
    GoodputLedger,
    RegressionSentinel,
    classify_phase,
    goodput,
    goodput_enabled,
)
from smdistributed_modelparallel_tpu.utils.telemetry import (
    LATENCY_BUCKETS,
    TelemetryRegistry,
)

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

import slo_report  # noqa: E402


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t


_GOODPUT_ENVS = (GOODPUT_ENV, GOODPUT_MIN_ENV, REGRESSION_RATIO_ENV,
                 FORENSICS_PATH_ENV)


@pytest.fixture
def clean_env(monkeypatch):
    for v in _GOODPUT_ENVS:
        monkeypatch.delenv(v, raising=False)
    return monkeypatch


def _ledger(clk=None, **kw):
    clk = clk if clk is not None else FakeClock()
    kw.setdefault("registry", TelemetryRegistry())
    kw.setdefault("min_goodput", 0)     # 0/None-able; 0 disables the gate
    kw.setdefault("regression_ratio", 0)
    led = GoodputLedger(clock=clk, wall=clk, **kw)
    return led, clk


def _counter(reg, name, **labels):
    fam = reg.report()["metrics"].get(name)
    for s in (fam or {}).get("series", []):
        if s["labels"] == labels:
            return s["value"]
    return None


def _short_walk(led, clk):
    clk.t += 3.0                              # startup
    led.observe_phase("step_0/trace")
    clk.t += 2.0
    led.observe_phase("step_0")
    clk.t += 4.0
    with led.scope("data_wait"):
        clk.t += 1.5


def _random_walk(led, clk):
    rng = random.Random(18)
    phases = ["step_1/trace", "step_1", "compile/x", "barrier/y",
              "init/mesh", "initialized", "unclassified/noise"]
    for _ in range(200):
        clk.t += rng.uniform(0.0, 3.0)
        op = rng.random()
        if op < 0.6:
            led.observe_phase(rng.choice(phases))
        elif op < 0.8:
            with led.scope(rng.choice(("ckpt_save", "data_wait",
                                       "preempt_drain"))):
                clk.t += rng.uniform(0.0, 2.0)
        else:
            led.enter(rng.choice(("wedged", "recovery_first_step")))


# ----------------------------------------------------------------------
# The attribution state machine
# ----------------------------------------------------------------------


class TestLedgerInvariant:
    def test_sum_to_wall_clock_exact(self):
        """THE invariant: every second lands in exactly one state."""
        led, clk = _ledger()
        clk.t += 3.0                          # startup
        led.observe_phase("step_0/trace")
        clk.t += 2.0                          # trace
        led.observe_phase("compile/step_0")
        clk.t += 5.0                          # compile_fresh
        led.observe_phase("step_0")
        clk.t += 4.0                          # step
        with led.scope("ckpt_save"):
            clk.t += 7.0                      # ckpt_save
        clk.t += 1.0                          # back to step
        secs = led.seconds()
        assert sum(secs.values()) == pytest.approx(led.wall_seconds())
        assert secs["startup"] == pytest.approx(3.0)
        assert secs["trace"] == pytest.approx(2.0)
        assert secs["compile_fresh"] == pytest.approx(5.0)
        assert secs["step"] == pytest.approx(5.0)
        assert secs["ckpt_save"] == pytest.approx(7.0)
        assert led.goodput_fraction() == pytest.approx(5.0 / 22.0)
        assert set(secs) <= set(STATES)

    def test_invariant_holds_under_random_walk(self):
        led, clk = _ledger()
        _random_walk(led, clk)
        assert sum(led.seconds().values()) == pytest.approx(
            led.wall_seconds(), abs=1e-9
        )

    # What a reader of the published blocks may rely on, whatever the
    # rounding: a fraction in [0, 1] and seconds that add up to the wall.

    @pytest.mark.parametrize("walk", [_short_walk, _random_walk])
    def test_snapshot_seconds_sum_to_its_wall(self, walk):
        led, clk = _ledger()
        walk(led, clk)
        snap = led.snapshot()
        assert 0.0 <= snap["goodput_fraction"] <= 1.0
        assert set(snap["seconds"]) <= set(STATES)
        assert sum(snap["seconds"].values()) == pytest.approx(
            snap["wall_s"], rel=0.01
        )

    @pytest.mark.parametrize("walk", [_short_walk, _random_walk])
    def test_window_block_badput_is_the_rest_of_the_wall(self, walk):
        led, clk = _ledger()
        walk(led, clk)
        block = led.window_block()
        assert 0.0 <= block["fraction"] <= 1.0
        assert not set(block["badput"]) & set(PRODUCTIVE)
        assert sum(block["badput"].values()) == pytest.approx(
            (1.0 - block["fraction"]) * led.wall_seconds(), rel=0.01
        )

    def test_scope_restores_enclosing_state(self):
        led, clk = _ledger()
        led.observe_phase("step_3")
        clk.t += 1.0
        with led.scope("ckpt_save"):
            clk.t += 2.0
        assert led.state == "step"

    def test_ambient_phase_under_scope_lands_at_base(self):
        """A phase observed while an explicit scope is open must not
        steal attribution from the scope — it retargets the BASE state
        the ledger returns to."""
        led, clk = _ledger()
        led.observe_phase("step_3")
        clk.t += 1.0
        with led.scope("preempt_drain"):
            clk.t += 4.0
            led.observe_phase("barrier/emergency")  # ambient, nested
            clk.t += 2.0
        secs = led.seconds()
        assert secs["preempt_drain"] == pytest.approx(6.0)
        assert led.state == "sync_wait"   # the retargeted base
        assert sum(secs.values()) == pytest.approx(led.wall_seconds())

    def test_nested_scopes(self):
        led, clk = _ledger()
        with led.scope("preempt_drain"):
            clk.t += 1.0
            with led.scope("ckpt_save"):
                clk.t += 2.0
            clk.t += 1.0
        secs = led.seconds()
        assert secs["preempt_drain"] == pytest.approx(2.0)
        assert secs["ckpt_save"] == pytest.approx(2.0)

    def test_mark_stalled_attributes_wedged(self):
        led, clk = _ledger()
        led.observe_phase("step_9")
        clk.t += 1.0
        led.mark_stalled("watchdog")
        clk.t += 30.0
        assert led.seconds()["wedged"] == pytest.approx(30.0)
        led.observe_phase("step_10")   # stall over: ambient phase resumes
        clk.t += 1.0
        assert led.state == "step"
        assert sum(led.seconds().values()) == pytest.approx(
            led.wall_seconds()
        )

    def test_note_compile_moves_disk_cache_seconds(self):
        led, clk = _ledger()
        led.observe_phase("compile/step_0")
        clk.t += 8.0
        led.observe_phase("step_0")
        led.note_compile("disk_cache", 6.0)
        secs = led.seconds()
        assert secs["compile_cache"] == pytest.approx(6.0)
        assert secs["compile_fresh"] == pytest.approx(2.0)
        assert sum(secs.values()) == pytest.approx(led.wall_seconds())

    def test_note_compile_clamps_to_accrued(self):
        led, clk = _ledger()
        led.observe_phase("compile/step_0")
        clk.t += 2.0
        led.note_compile("disk_cache", 100.0)
        secs = led.seconds()
        assert secs.get("compile_fresh", 0.0) == pytest.approx(0.0)
        assert secs["compile_cache"] == pytest.approx(2.0)
        assert sum(secs.values()) == pytest.approx(led.wall_seconds())

    def test_note_compile_fresh_is_noop(self):
        led, clk = _ledger()
        led.observe_phase("compile/step_0")
        clk.t += 2.0
        led.note_compile("fresh", 2.0)
        assert "compile_cache" not in led.seconds()

    def test_transitions_recorded(self):
        led, clk = _ledger()
        led.observe_phase("step_0/trace")
        clk.t += 1.0
        led.observe_phase("step_0")
        trans = led.transitions()
        assert [t["to"] for t in trans] == ["trace", "step"]
        snap = led.snapshot()
        assert snap["state"] == "step"
        assert snap["transitions"][-1]["to"] == "step"


class TestClassifyPhase:
    @pytest.mark.parametrize("phase,state", [
        ("step_12/trace", "trace"),
        ("step_12", "step"),
        ("run/loop", "step"),
        ("compile/step_12", "compile_fresh"),
        ("init/mesh", "startup"),
        ("startup", "startup"),
        ("initialized", "idle"),
        ("shutdown", "idle"),
        ("barrier/sync", "sync_wait"),
        ("recv_from/3", "sync_wait"),
        ("weird/other", None),
        ("", None),
        (None, None),
    ])
    def test_mapping(self, phase, state):
        assert classify_phase(phase) == state


# ----------------------------------------------------------------------
# Publishing: the counters the fleet merge sums
# ----------------------------------------------------------------------


class TestPublish:
    def test_counters_and_gauge(self):
        reg = TelemetryRegistry()
        led, clk = _ledger(registry=reg)
        led.observe_phase("step_0")
        clk.t += 9.0
        with led.scope("data_wait"):
            clk.t += 1.0
        frac = led.publish()
        assert frac == pytest.approx(0.9)
        assert _counter(reg, "smp_goodput_seconds_total") == pytest.approx(
            9.0
        )
        assert _counter(
            reg, "smp_badput_seconds_total", state="data_wait"
        ) == pytest.approx(1.0)
        # Second publish after more time: counters move by the DELTA
        # (stay monotonic), never re-add history.
        clk.t += 1.0
        led.publish()
        assert _counter(reg, "smp_goodput_seconds_total") == pytest.approx(
            10.0
        )

    def test_fleet_window_fold(self):
        """Two ranks' published counters merge into a rank-weighted
        fleet train_goodput + per-state badput breakdown."""
        from test_fleet import FakeClock as FleetClock, _plane, _snap

        regs = []
        for good, wait in [(9.0, 1.0), (4.0, 6.0)]:
            reg = TelemetryRegistry()
            led, clk = _ledger(registry=reg)
            led.observe_phase("step_0")
            clk.t += good
            with led.scope("data_wait"):
                clk.t += wait
            led.publish()
            regs.append(reg)

        fclk = FleetClock()
        plane = _plane(world=2, rank=0, registry=regs[0], clock=fclk)
        plane._ingest(1, _snap(regs[1], 1), fclk.t)
        fclk.t += 1.0
        window = plane.tick()
        assert window["train_goodput"] == pytest.approx(13.0 / 20.0)
        assert window["badput_by_state"]["data_wait"] == pytest.approx(7.0)
        assert set(window["goodput_by_rank"]["by_rank"]) == {"0", "1"}
        # The merged fraction also lands on the aggregator's gauge.
        assert _counter(
            regs[0], "smp_fleet_train_goodput"
        ) == pytest.approx(13.0 / 20.0)


# ----------------------------------------------------------------------
# The perf-regression sentinel
# ----------------------------------------------------------------------


def _observe_steps(reg, values):
    h = reg.histogram("smp_step_time_seconds", buckets=LATENCY_BUCKETS)
    for v in values:
        h.labels().observe(v)


class TestRegressionSentinel:
    def _sentinel(self, reg, ratio=1.5):
        return RegressionSentinel(registry=reg, ratio=ratio, min_count=8,
                                  baseline_windows=3)

    def test_fires_once_per_episode_and_clears(self):
        reg = TelemetryRegistry()
        s = self._sentinel(reg)
        _observe_steps(reg, [0.1] * 8)
        s.check(wall=0.0)                     # primes _prev, no window yet
        for i in range(3):                    # 3 baseline windows
            _observe_steps(reg, [0.1] * 8)
            assert s.check(wall=float(i)) == []
        # Regression: windowed p50 jumps ~20x past the 1.5x ratio.
        _observe_steps(reg, [2.0] * 8)
        fired = s.check(wall=10.0)
        assert len(fired) == 1
        assert fired[0]["source"] == "step_time"
        assert fired[0]["ratio"] > 1.5
        assert _counter(
            reg, "smp_perf_regression_total", source="step_time"
        ) == 1
        assert _counter(
            reg, "smp_perf_regression", source="step_time"
        ) == 1
        # Still slow: LATCHED, no second fire.
        _observe_steps(reg, [2.0] * 8)
        assert s.check(wall=11.0) == []
        assert _counter(
            reg, "smp_perf_regression_total", source="step_time"
        ) == 1
        # Recovery clears the latch (and the gauge)...
        _observe_steps(reg, [0.1] * 8)
        assert s.check(wall=12.0) == []
        assert _counter(
            reg, "smp_perf_regression", source="step_time"
        ) == 0
        # ...so a NEW episode fires again.
        for i in range(2):
            _observe_steps(reg, [0.1] * 8)
            s.check(wall=13.0 + i)
        _observe_steps(reg, [2.0] * 8)
        assert len(s.check(wall=20.0)) == 1

    def test_regressed_windows_do_not_poison_baseline(self):
        """A persistent regression must not normalize itself away: the
        degraded windows never extend the baseline."""
        reg = TelemetryRegistry()
        s = self._sentinel(reg)
        _observe_steps(reg, [0.1] * 8)
        s.check(wall=0.0)
        for i in range(3):
            _observe_steps(reg, [0.1] * 8)
            s.check(wall=float(i))
        baseline_before = list(s._baseline["step_time"])
        for i in range(5):
            _observe_steps(reg, [2.0] * 8)
            s.check(wall=10.0 + i)
        assert list(s._baseline["step_time"]) == baseline_before
        assert "step_time" in s.regressed

    def test_small_windows_skipped(self):
        reg = TelemetryRegistry()
        s = self._sentinel(reg)
        _observe_steps(reg, [0.1] * 8)
        s.check(wall=0.0)
        _observe_steps(reg, [0.1] * 3)     # < min_count: no window cut
        s.check(wall=1.0)
        assert list(s.windows["step_time"]) == []

    def test_disabled_without_ratio(self, clean_env):
        reg = TelemetryRegistry()
        s = RegressionSentinel(registry=reg)   # no env, no explicit ratio
        assert not s.enabled
        _observe_steps(reg, [0.1] * 8)
        assert s.check() == []


# ----------------------------------------------------------------------
# Auto-forensics: bounded, cooldown-rate-limited
# ----------------------------------------------------------------------


class TestForensics:
    def _engine(self, tmp_path, **kw):
        clk = kw.pop("clock", FakeClock())
        return ForensicsEngine(
            path=str(tmp_path / "forensics"), registry=TelemetryRegistry(),
            clock=clk, wall=clk, **kw
        ), clk

    def test_capture_writes_bundle(self, tmp_path):
        eng, clk = self._engine(tmp_path)
        bundle = eng.trigger("perf_regression", detail="p50 2x",
                             context={"goodput": {"state": "step"}})
        assert bundle is not None and os.path.isdir(bundle)
        assert "perf_regression" in os.path.basename(bundle)
        doc = json.load(open(os.path.join(bundle, "forensics.json")))
        assert doc["reason"] == "perf_regression"
        assert doc["goodput"] == {"state": "step"}
        assert doc["threads"]            # thread stacks captured
        assert os.path.exists(os.path.join(bundle, "flight_recorder.jsonl"))

    def test_cooldown_suppresses_then_allows(self, tmp_path):
        eng, clk = self._engine(tmp_path, cooldown=600.0)
        assert eng.trigger("a") is not None
        assert eng.trigger("b") is None            # inside cooldown
        clk.t += 599.0
        assert eng.trigger("c") is None            # still inside
        clk.t += 2.0
        assert eng.trigger("d") is not None        # cooldown elapsed
        reg = eng.registry
        assert _counter(reg, "smp_forensics_total",
                        outcome="captured") == 2
        assert _counter(reg, "smp_forensics_total",
                        outcome="suppressed") == 2

    def test_bundle_cap(self, tmp_path):
        eng, clk = self._engine(tmp_path, cooldown=0.0, max_bundles=3)
        captured = 0
        for i in range(10):
            clk.t += 1.0
            if eng.trigger(f"r{i}") is not None:
                captured += 1
        assert captured == 3 == DEFAULT_FORENSICS_MAX - 5
        assert len(eng.bundles) == 3

    def test_disabled_without_path(self, clean_env):
        eng = ForensicsEngine(path=None, registry=TelemetryRegistry())
        assert not eng.enabled
        assert eng.trigger("anything") is None

    def test_never_raises(self, tmp_path, monkeypatch):
        eng, clk = self._engine(tmp_path)
        monkeypatch.setattr(
            eng, "_capture",
            lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")),
        )
        assert eng.trigger("a") is None


class TestLedgerClosedLoops:
    def test_min_goodput_triggers_forensics_once(self, tmp_path):
        clk = FakeClock()
        reg = TelemetryRegistry()
        eng = ForensicsEngine(path=str(tmp_path / "f"), registry=reg,
                              clock=clk, wall=clk, cooldown=0.0)
        led = GoodputLedger(registry=reg, clock=clk, wall=clk,
                            min_goodput=0.5, min_elapsed=60.0,
                            regression_ratio=0, forensics=eng)
        with led.scope("data_wait"):
            clk.t += 30.0
        led.tick()                 # below min, but < min_elapsed: holds
        assert not eng.bundles
        with led.scope("data_wait"):
            clk.t += 40.0
        led.tick()
        assert len(eng.bundles) == 1
        assert "goodput_min" in eng.bundles[0]
        led.tick()                 # fired once, stays fired
        assert len(eng.bundles) == 1

    def test_sentinel_fire_triggers_forensics_with_context(self, tmp_path):
        clk = FakeClock()
        reg = TelemetryRegistry()
        eng = ForensicsEngine(path=str(tmp_path / "f"), registry=reg,
                              clock=clk, wall=clk, cooldown=0.0)
        led = GoodputLedger(registry=reg, clock=clk, wall=clk,
                            min_goodput=0, regression_ratio=1.5,
                            forensics=eng)
        _observe_steps(reg, [0.1] * 8)
        led.tick()
        for _ in range(3):
            clk.t += 1.0
            _observe_steps(reg, [0.1] * 8)
            led.tick()
        clk.t += 1.0
        _observe_steps(reg, [2.0] * 8)
        led.tick()
        assert len(eng.bundles) == 1
        doc = json.load(
            open(os.path.join(eng.bundles[0], "forensics.json"))
        )
        assert doc["reason"] == "perf_regression"
        assert doc["goodput"]["state"]        # snapshot attached
        assert doc["sentinel"]["verdicts"]

    def test_maybe_tick_rate_limited(self):
        led, clk = _ledger(tick_seconds=5.0)
        led.observe_phase("step_0")
        clk.t += 1.0
        assert led.maybe_tick() is None       # < tick_seconds since t0
        clk.t += 5.0
        assert led.maybe_tick() is not None
        assert led.maybe_tick() is None       # immediately after: limited


# ----------------------------------------------------------------------
# Zero-cost-off + the controller lifecycle
# ----------------------------------------------------------------------


class TestController:
    def test_from_env_constructs_nothing_when_off(self, clean_env):
        assert not goodput_enabled()
        assert GoodputLedger.from_env() is None

    def test_dependent_knobs_arm_the_ledger(self, clean_env):
        for var, val in ((GOODPUT_MIN_ENV, "0.9"),
                         (REGRESSION_RATIO_ENV, "1.5"),
                         (FORENSICS_PATH_ENV, "/tmp/x")):
            clean_env.setenv(var, val)
            assert goodput_enabled()
            clean_env.delenv(var)
        clean_env.setenv(GOODPUT_ENV, "1")
        assert goodput_enabled()
        clean_env.setenv(GOODPUT_ENV, "off")
        assert not goodput_enabled()

    def test_disarmed_seams_are_noops(self, clean_env):
        ctl = GoodputController()
        assert ctl.ledger is None
        with ctl.scope("ckpt_save"):
            pass
        ctl.enter("wedged")
        ctl.on_step_edge(3)
        ctl.note_compile("disk_cache", 1.0)
        ctl.mark_stalled("x")
        assert ctl.trigger_forensics("r") is None
        assert ctl.snapshot() is None
        assert ctl.window_block() is None

    def test_start_chains_phase_listener_and_stop_restores(self, clean_env):
        clean_env.setenv(GOODPUT_ENV, "1")
        reg = TelemetryRegistry()
        seen = []
        reg._phase_listener = seen.append     # the flight-recorder's slot
        ctl = GoodputController()
        led = ctl.start(registry=reg)
        assert led is not None
        assert ctl.start(registry=reg) is led    # idempotent
        reg.set_phase("step_4")
        assert seen == ["step_4"]                # prior listener still fed
        assert led.state == "step"
        ctl.stop()
        assert reg._phase_listener == seen.append   # prior listener back
        ctl.reset()
        assert ctl.ledger is None

    def test_watchdog_snapshot_helper(self, clean_env):
        from smdistributed_modelparallel_tpu.utils.telemetry import (
            _goodput_snapshot,
        )

        assert _goodput_snapshot("stall") is None   # disarmed: absent
        clean_env.setenv(GOODPUT_ENV, "1")
        reg = TelemetryRegistry()
        ctl_prev = goodput.ledger
        try:
            goodput.ledger = GoodputLedger(
                registry=reg, min_goodput=0, regression_ratio=0,
                clock=FakeClock(), wall=FakeClock(),
            )
            snap = _goodput_snapshot("collective stuck")
            assert snap["state"] == "wedged"       # stall marked first
            assert "seconds" in snap and "transitions" in snap
        finally:
            goodput.ledger = ctl_prev


# ----------------------------------------------------------------------
# Script gates
# ----------------------------------------------------------------------


class TestScriptGates:
    def _fleet_feed(self, tmp_path, train_goodput):
        rec = {"kind": "fleet_window", "seq": 1, "t_wall": 1.0,
               "window_s": 1.0, "ranks": [0, 1],
               "slo": {"ok": True, "violations": {}}}
        if train_goodput is not None:
            rec["train_goodput"] = train_goodput
        p = tmp_path / "fleet.jsonl"
        p.write_text(json.dumps(rec) + "\n")
        return str(p)

    def test_min_train_goodput_pass_fail_absent(self, tmp_path, capsys):
        feed = self._fleet_feed(tmp_path, 0.95)
        assert slo_report.main(
            [feed, "--fleet", "--min-train-goodput", "0.9"]
        ) == 0
        assert slo_report.main(
            [feed, "--fleet", "--min-train-goodput", "0.99"]
        ) == 1
        bare = self._fleet_feed(tmp_path, None)
        assert slo_report.main(
            [bare, "--fleet", "--min-train-goodput", "0.9"]
        ) == 2
        # The gate is --fleet-scoped.
        assert slo_report.main(
            [feed, "--min-train-goodput", "0.9"]
        ) == 2
        capsys.readouterr()

    def test_min_train_goodput_combines_with_check(self, tmp_path, capsys):
        feed = self._fleet_feed(tmp_path, 0.5)
        assert slo_report.main(
            [feed, "--fleet", "--check", "--min-train-goodput", "0.9"]
        ) == 1
        capsys.readouterr()


# ----------------------------------------------------------------------
# The time-series fold
# ----------------------------------------------------------------------


class TestTimeseriesFold:
    def test_window_carries_train_goodput(self, clean_env):
        from smdistributed_modelparallel_tpu.utils.timeseries import (
            MetricsTimeSeries,
        )

        reg = TelemetryRegistry()
        clk = FakeClock()
        led = GoodputLedger(registry=reg, clock=clk, wall=clk,
                            min_goodput=0, regression_ratio=0)
        prev = goodput.ledger
        goodput.ledger = led
        try:
            led.observe_phase("step_0")
            clk.t += 9.0
            with led.scope("data_wait"):
                clk.t += 1.0
            ts = MetricsTimeSeries(registry=reg, interval=1.0, path="",
                                   clock=FakeClock(), wall=FakeClock())
            ts._clock.t += 2.0
            ts.sample()
            window = ts.snapshots()[-1]
            assert window["train_goodput"] == pytest.approx(0.9)
            assert window["badput_seconds"]["data_wait"] == pytest.approx(
                1.0
            )
        finally:
            goodput.ledger = prev
