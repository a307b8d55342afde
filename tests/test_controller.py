"""PR-19 serving control plane: SLO-driven autoscaling, request
routing, the drain protocol, and canaried live weight updates.

Tiers: pure-host policy units under a fake clock (hysteresis both
directions, the cooldown latch, min/max clamps, flap suppression),
router units over fake handles (least-loaded dispatch, deterministic
version splits, availability fallback), controller scale events over
fake handles (phase accounting, the JSONL feed, the drain/reroute
path), the chaos seams, and the slo_report tool gate —
none of which compile anything. One compiled-engine composite carries
every behavioral claim that needs real programs (drain token parity,
zero-recompile adoption, canary promote + chaos-corrupted rollback
with exactly one forensics bundle). The full burst E2E (scale 1->2->1
with token parity vs a never-scaled run) pays extra compiles and is
slow-tiered in conftest; the 2-process remote-replica E2E lives in
tests/test_multiprocess.py.
"""

import dataclasses
import importlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.models.transformer_lm import (
    TransformerLM,
)
from smdistributed_modelparallel_tpu.serving import (
    AutoscalePolicy,
    LocalReplicaHandle,
    RequestRouter,
    ServeRequest,
    ServingController,
    ServingEngine,
    serve_request_from_record,
    serve_request_to_record,
)
from smdistributed_modelparallel_tpu.serving import controller as ctl_mod
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts"
)
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)

import slo_report  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_registry():
    telemetry.reset()
    ctl_mod.reset_all()
    yield
    telemetry.reset()
    ctl_mod.reset_all()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def _counter(name, **labels):
    fam = telemetry.report()["metrics"].get(name)
    if not fam:
        return None
    for s in fam["series"]:
        if all(s["labels"].get(k) == v for k, v in labels.items()):
            return s.get("value")
    return None


class FakeHandle:
    """Router-surface stand-in: load = static base + accepted work."""

    def __init__(self, name, version=0, load=0):
        self.name = str(name)
        self.version = int(version)
        self.live = True
        self._load = int(load)
        self.submitted = []
        self._results = {}
        self.stragglers = []
        self.drained = False

    def load(self):
        return self._load + len(self.submitted)

    def submit(self, req):
        self.submitted.append(req)
        return True

    def step(self):
        return False

    def poll(self):
        pass

    def drain(self, timeout_s=120.0):
        self.drained = True
        return list(self.stragglers)

    def results(self):
        return dict(self._results)

    @property
    def busy(self):
        return False


def _record(rid, prompt=(1, 2), max_new=3, tokens=()):
    """A restartable mirror record (the drain-straggler wire format)."""
    return {
        "rid": rid, "prompt": list(prompt), "max_new_tokens": max_new,
        "temperature": 0.0, "top_k": None, "top_p": None,
        "eos_token_id": None, "seed": 0, "deadline_s": None,
        "tokens": list(tokens), "done": False, "trace_id": None,
    }


# ---------------------------------------------------------------------------
# autoscale policy (pure, fake clock)
# ---------------------------------------------------------------------------


class TestAutoscalePolicy:
    def test_hysteresis_up_and_streak_reset(self):
        clk = FakeClock()
        p = AutoscalePolicy({"queue_depth": 2.0}, hysteresis=2,
                            cooldown_s=0.0, clock=clk)
        assert p.observe({"queue_depth": 5}, live=1) is None
        clk.advance(1.0)
        assert p.observe({"queue_depth": 5}, live=1) == "up"
        # Firing resets the streak: one more bad window is not enough.
        assert p.observe({"queue_depth": 5}, live=2) is None

    def test_down_needs_empty_queue_and_real_headroom(self):
        p = AutoscalePolicy({"ttft_p99_ms": 100.0}, hysteresis=2,
                            cooldown_s=0.0, clock=FakeClock())
        # Meets the SLO but sits above half the threshold: not surplus.
        for _ in range(4):
            assert p.observe(
                {"ttft_p99_ms": 60.0, "queue_depth": 0}, live=2
            ) is None
        assert p.observe(
            {"ttft_p99_ms": 40.0, "queue_depth": 0}, live=2) is None
        # A queued request resets the comfort streak.
        assert p.observe(
            {"ttft_p99_ms": 40.0, "queue_depth": 1}, live=2) is None
        assert p.observe(
            {"ttft_p99_ms": 40.0, "queue_depth": 0}, live=2) is None
        assert p.observe(
            {"ttft_p99_ms": 40.0, "queue_depth": 0}, live=2) == "down"

    def test_cooldown_latches_but_streak_accumulates(self):
        clk = FakeClock()
        p = AutoscalePolicy({"queue_depth": 2.0}, hysteresis=1,
                            cooldown_s=10.0, clock=clk)
        assert p.observe({"queue_depth": 5}, live=1) == "up"
        clk.advance(5.0)
        assert p.observe({"queue_depth": 5}, live=2) is None  # held
        clk.advance(5.1)
        # The breach never cleared: first post-cooldown tick fires.
        assert p.observe({"queue_depth": 5}, live=2) == "up"

    def test_min_max_clamps(self):
        p = AutoscalePolicy({"queue_depth": 2.0}, hysteresis=1,
                            cooldown_s=0.0, min_replicas=1,
                            max_replicas=2, clock=FakeClock())
        # Clamped at max: no event, but the streak is kept alive.
        assert p.observe({"queue_depth": 9}, live=2) is None
        assert p.observe({"queue_depth": 9}, live=1) == "up"
        # Comfort at the floor never shrinks below min.
        assert p.observe({"queue_depth": 0}, live=1) is None
        assert p.observe({"queue_depth": 0}, live=1) is None

    def test_flapping_windows_never_fire(self):
        p = AutoscalePolicy({"queue_depth": 2.0}, hysteresis=2,
                            cooldown_s=0.0, clock=FakeClock())
        for _ in range(6):
            assert p.observe({"queue_depth": 5}, live=2) is None
            assert p.observe({"queue_depth": 0}, live=2) is None

    def test_validation(self):
        with pytest.raises(SMPValidationError):
            AutoscalePolicy(min_replicas=0)
        with pytest.raises(SMPValidationError):
            AutoscalePolicy(min_replicas=3, max_replicas=2)
        with pytest.raises(SMPValidationError):
            AutoscalePolicy(hysteresis=0)


# ---------------------------------------------------------------------------
# request router (pure, fake handles)
# ---------------------------------------------------------------------------


class TestRequestRouter:
    def test_least_loaded_with_name_tiebreak(self):
        r = RequestRouter()
        r.attach(FakeHandle("a", load=3))
        r.attach(FakeHandle("b", load=1))
        assert r.dispatch(ServeRequest("r1", [1, 2], 4)) == "b"
        assert r.dispatch(ServeRequest("r2", [1, 2], 4)) == "b"
        # Tie at load 3: lexicographic name breaks it deterministically.
        assert r.dispatch(ServeRequest("r3", [1, 2], 4)) == "a"
        assert r.routed == {"a": 1, "b": 2}
        assert _counter("smp_controller_routed_total", version="0") == 3

    def test_dead_handles_skipped(self):
        r = RequestRouter()
        h = r.attach(FakeHandle("a"))
        h.live = False
        assert r.dispatch(ServeRequest("x", [1], 2)) is None
        assert r.live_handles() == []

    def test_attach_duplicate_raises(self):
        r = RequestRouter()
        r.attach(FakeHandle("a"))
        with pytest.raises(SMPValidationError):
            r.attach(FakeHandle("a"))

    def test_split_validation(self):
        r = RequestRouter()
        with pytest.raises(SMPValidationError):
            r.set_split({0: 0.5, 1: 0.6})
        with pytest.raises(SMPValidationError):
            r.set_split({})
        r.set_split({0: 0.75, 1: 0.25})
        assert r.split == {0: 0.75, 1: 1.0}   # cumulative table
        r.set_split(None)
        assert r.split == {}

    def test_version_split_sticky_and_deterministic(self):
        def routed(n):
            r = RequestRouter()
            r.attach(FakeHandle("v0", version=0))
            r.attach(FakeHandle("v1", version=1))
            r.set_split({0: 0.75, 1: 0.25})
            return {
                f"r{i}": r.dispatch(ServeRequest(f"r{i}", [1], 2))
                for i in range(n)
            }

        first = routed(40)
        assert set(first.values()) == {"v0", "v1"}  # both take traffic
        minority = sum(1 for v in first.values() if v == "v1")
        assert 1 <= minority <= 20   # ~25% of 40, loosely
        # Same rids, fresh router: identical placement — a retried
        # request cannot flap between weight versions mid-canary.
        assert routed(40) == first

    def test_split_degrades_to_availability(self):
        r = RequestRouter()
        r.attach(FakeHandle("v0", version=0))
        r.set_split({0: 0.0, 1: 1.0})   # every rid maps to version 1
        assert r.dispatch(ServeRequest("x", [1], 2)) == "v0"


# ---------------------------------------------------------------------------
# controller arming + scale events (fake handles, fake clock)
# ---------------------------------------------------------------------------


class TestArming:
    def test_disarmed_constructs_nothing(self, monkeypatch):
        monkeypatch.delenv("SMP_AUTOSCALE", raising=False)
        assert ServingController.from_env() is None
        monkeypatch.setenv("SMP_AUTOSCALE", "0")
        assert ServingController.from_env() is None
        assert ctl_mod._ACTIVE == []

    def test_from_env_reads_every_knob(self, monkeypatch, tmp_path):
        monkeypatch.setenv("SMP_AUTOSCALE", "on")
        monkeypatch.setenv("SMP_SLO", "queue_depth=3,ttft_p99_ms=250")
        monkeypatch.setenv("SMP_AUTOSCALE_COOLDOWN", "1.5")
        monkeypatch.setenv("SMP_AUTOSCALE_MIN", "2")
        monkeypatch.setenv("SMP_AUTOSCALE_MAX", "5")
        monkeypatch.setenv("SMP_AUTOSCALE_HYSTERESIS", "3")
        monkeypatch.setenv("SMP_CANARY_FRACTION", "0.1")
        monkeypatch.setenv("SMP_CANARY_WINDOWS", "4")
        monkeypatch.setenv("SMP_CONTROLLER_PATH", str(tmp_path / "c.jsonl"))
        ctl = ServingController.from_env()
        try:
            assert ctl.policy.slo == {"queue_depth": 3.0,
                                      "ttft_p99_ms": 250.0}
            assert ctl.policy.cooldown_s == 1.5
            assert ctl.policy.min_replicas == 2
            assert ctl.policy.max_replicas == 5
            assert ctl.policy.hysteresis == 3
            assert ctl.canary_fraction == 0.1
            assert ctl.canary_windows == 4
            assert ctl.path == str(tmp_path / "c.jsonl")
            assert ctl in ctl_mod._ACTIVE
        finally:
            ctl.stop()
        assert ctl not in ctl_mod._ACTIVE

    def test_bad_numeric_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("SMP_AUTOSCALE", "1")
        monkeypatch.setenv("SMP_AUTOSCALE_COOLDOWN", "banana")
        ctl = ServingController.from_env()
        try:
            assert ctl.policy.cooldown_s == 30.0
        finally:
            ctl.stop()


class TestControllerScaleEvents:
    def _controller(self, tmp_path, clk, slo=None, **policy_kw):
        wins = []
        policy_kw.setdefault("cooldown_s", 0.0)
        policy_kw.setdefault("hysteresis", 2)
        ctl = ServingController(
            router=RequestRouter(),
            policy=AutoscalePolicy(slo or {"queue_depth": 2.0},
                                   clock=clk, **policy_kw),
            window_source=lambda: wins.pop(0) if wins else None,
            path=str(tmp_path / "ctl.jsonl"),
            clock=clk,
        )
        return ctl, wins

    def test_scale_up_phases_feed_and_lazy_first_token(self, tmp_path):
        clk = FakeClock()
        ctl, wins = self._controller(tmp_path, clk)
        ctl.register_live(FakeHandle("r0"))
        newh = FakeHandle("r1")

        def activate():
            clk.advance(0.5)   # the warm start, on the fake clock
            return newh

        ctl.add_standby("r1", activate)
        wins.append({"seq": 1, "queue_depth": 9})
        assert ctl.tick() is None          # hysteresis: one breach
        clk.advance(1.0)
        wins.append({"seq": 2, "queue_depth": 9})
        assert ctl.tick() == "up"
        assert ctl.replicas == 2
        ev = ctl.scale_events[0]
        assert ev["direction"] == "up" and ev["replica"] == "r1"
        assert ev["reason"] == "slo:queue_depth"
        assert ev["window_seq"] == 2
        assert ev["phases"]["trigger"] == pytest.approx(1.0)
        assert ev["phases"]["warm_start"] == pytest.approx(0.5)
        # The event stays OPEN until the new replica serves something.
        assert "seconds" not in ev
        assert not os.path.exists(ctl.path) or \
            not open(ctl.path).read().strip()
        clk.advance(0.25)
        newh._results["x"] = [1, 2]
        ctl.tick()                         # closes the pending phase
        assert ev["phases"]["first_token"] == pytest.approx(0.25)
        assert ev["seconds"] == pytest.approx(1.75)
        recs = [json.loads(l) for l in open(ctl.path)]
        assert [r["kind"] for r in recs] == ["scale_event"]
        assert recs[0]["seconds"] == pytest.approx(1.75)
        assert _counter("smp_autoscale_events_total", direction="up") == 1
        assert _counter("smp_controller_replicas") == 2

    def test_scale_up_without_standby_stays_put(self, tmp_path):
        clk = FakeClock()
        ctl, wins = self._controller(tmp_path, clk, hysteresis=1)
        ctl.register_live(FakeHandle("r0"))
        wins.append({"seq": 1, "queue_depth": 9})
        assert ctl.tick() is None
        assert ctl.replicas == 1 and ctl.scale_events == []

    def test_scale_down_drains_reroutes_and_guards_min(self, tmp_path):
        clk = FakeClock()
        ctl, wins = self._controller(tmp_path, clk)
        a = ctl.register_live(FakeHandle("a"))
        b = ctl.register_live(FakeHandle("b"))
        b.stragglers = [_record("q1")]
        b._results = {"f1": [7, 8]}
        wins.append({"seq": 1, "queue_depth": 0})
        assert ctl.tick() is None
        wins.append({"seq": 2, "queue_depth": 0})
        assert ctl.tick() == "down"
        # Last-activated replica is the victim; survivors absorb its
        # queued straggler, its finished results are retained.
        assert b.drained and ctl.replicas == 1
        assert "b" not in ctl.router.handles
        assert [r.request_id for r in a.submitted] == ["q1"]
        assert ctl.results()["f1"] == [7, 8]
        ev = ctl.scale_events[0]
        assert ev["direction"] == "down" and ev["stragglers"] == 1
        assert set(ev["phases"]) == {"drain", "reroute"}
        assert _counter("smp_controller_drain_stragglers_total") == 1
        # At the min clamp a direct shrink refuses outright.
        assert ctl.scale_down() is None
        assert ctl.replicas == 1


# ---------------------------------------------------------------------------
# chaos seams
# ---------------------------------------------------------------------------


class TestChaosSeams:
    def _chaos(self):
        # (attribute access would hit the ChaosInjector instance the
        # resilience package re-exports under the same name)
        return importlib.import_module(
            "smdistributed_modelparallel_tpu.resilience.chaos"
        )

    def test_corrupt_weights_hits_only_target_version(self, monkeypatch):
        chaos_mod = self._chaos()
        monkeypatch.setenv("SMP_CHAOS", "corrupt_weights@version=2")
        chaos_mod.chaos.reset()
        params = {"w": np.ones(3, np.float32), "i": np.arange(3)}
        assert chaos_mod.chaos.on_weight_update(1, params) is params
        out = chaos_mod.chaos.on_weight_update(2, params)
        assert np.allclose(out["w"], 1.01 * np.ones(3) + 0.01)
        assert np.array_equal(out["i"], np.arange(3))  # ints untouched
        # One-shot: version 2 adopted again is clean.
        assert chaos_mod.chaos.on_weight_update(2, params) is params
        chaos_mod.chaos.reset()

    def test_kill_replica_at_scale_event(self, monkeypatch):
        chaos_mod = self._chaos()
        killed = []
        monkeypatch.setattr(
            chaos_mod.os, "kill", lambda pid, sig: killed.append(sig)
        )
        monkeypatch.setenv("SMP_CHAOS", "kill_replica@scale=2")
        chaos_mod.chaos.reset()
        chaos_mod.chaos.on_scale_event(1)
        assert killed == []
        chaos_mod.chaos.on_scale_event(2)
        assert killed, "kill_replica@scale must fire on the K-th event"
        killed.clear()
        chaos_mod.chaos.on_scale_event(2)   # one-shot
        assert killed == []
        chaos_mod.chaos.reset()


# ---------------------------------------------------------------------------
# tool gate: slo_report --controller
# ---------------------------------------------------------------------------


def _feed(path, records):
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return str(path)


def _scale_event(seq, direction="up", seconds=1.0, **kw):
    ev = {"kind": "scale_event", "seq": seq, "direction": direction,
          "t_wall": 1000.0 + seq, "reason": "slo:queue_depth",
          "replicas": 2, "replica": "r1", "seconds": seconds,
          "phases": {"trigger": 0.1, "rendezvous": 0.0,
                     "warm_start": seconds - 0.1, "first_token": 0.0}}
    ev.update(kw)
    return ev


class TestControllerReportScript:
    def test_rc2_when_nothing_to_evaluate(self, tmp_path):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert slo_report.main(
            [str(empty), "--controller", "--check"]) == 2
        # --max-scale-seconds without --controller is a usage error.
        assert slo_report.main(
            [str(empty), "--max-scale-seconds", "5"]) == 2

    def test_timeline_and_gates(self, tmp_path, capsys):
        p = _feed(tmp_path / "ctl.jsonl", [
            _scale_event(1, seconds=2.5),
            _scale_event(2, direction="down", seconds=0.4,
                         stragglers=3,
                         phases={"drain": 0.3, "reroute": 0.1}),
            {"kind": "weight_update", "version": 1, "seconds": 0.002,
             "t_wall": 1004.0},
            {"kind": "canary", "verdict": "started", "version": 1,
             "t_wall": 1005.0, "detail": "fraction=0.25"},
            {"kind": "canary", "verdict": "promoted", "version": 1,
             "t_wall": 1006.0, "detail": ""},
        ])
        assert slo_report.main([p, "--controller", "--check"]) == 0
        out = capsys.readouterr().out
        assert "2 scale event(s)" in out
        assert "trigger 0.100s" in out and "warm_start" in out
        assert "3 straggler(s) re-dispatched" in out
        assert "promoted" in out and "PASS" in out
        # A slow scale event fails the latency gate.
        assert slo_report.main(
            [p, "--controller", "--check",
             "--max-scale-seconds", "1.0"]) == 1
        # Directory mode finds the feed; a rolled-back canary gates red.
        with open(p, "a") as f:
            f.write(json.dumps(
                {"kind": "canary", "verdict": "rolled_back", "version": 2,
                 "t_wall": 1007.0, "detail": "token_parity:1/2"}) + "\n")
        assert slo_report.main(
            [str(tmp_path), "--controller", "--check"]) == 1
        out = capsys.readouterr().out
        assert "never promoted" in out


# ---------------------------------------------------------------------------
# compiled composite: drain parity, zero-recompile adoption, canary
# ---------------------------------------------------------------------------


def _zoo(**kw):
    kw.setdefault("vocab_size", 97)
    kw.setdefault("max_len", 64)
    kw.setdefault("d_model", 32)
    kw.setdefault("n_layers", 2)
    kw.setdefault("n_heads", 4)
    return TransformerLM(**kw)


def _prompt(seed, length, vocab=97):
    return list(map(int, np.asarray(
        jax.random.randint(jax.random.key(seed), (length,), 0, vocab)
    )))


def _tree_copy(params):
    return jax.tree_util.tree_map(lambda x: x, params)


class TestControlPlaneEndToEnd:
    """One engine, one pair of compiled programs, every claim that
    needs them (the test_serving composite convention)."""

    def test_drain_adopt_and_canary_composite(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("SMP_GOODPUT", "on")
        monkeypatch.setenv("SMP_FORENSICS_PATH",
                           str(tmp_path / "forensics"))
        monkeypatch.setenv("SMP_FORENSICS_COOLDOWN", "0")
        smp.init({})
        from smdistributed_modelparallel_tpu.resilience.chaos import (
            chaos,
        )
        from smdistributed_modelparallel_tpu.utils import exec_cache
        from smdistributed_modelparallel_tpu.utils.goodput import goodput

        goodput.reset()
        goodput.start()
        try:
            mod = _zoo()
            params = mod.init(jax.random.key(0),
                              jnp.zeros((1, 4), jnp.int32))["params"]
            engine = ServingEngine(
                mod, params=params, max_slots=2,
                block_tokens_override=4, prefill_chunk=4,
            )
            prompts = [_prompt(80 + i, 5) for i in range(4)]
            reference = engine.run(
                [ServeRequest(f"ref{i}", prompts[i], 6)
                 for i in range(4)],
                timeout_s=300,
            )

            # -- drain protocol: zero dropped, zero duplicated --------
            for i in range(4):
                assert engine.submit(
                    ServeRequest(f"d{i}", prompts[i], 6))
            engine.step()            # admit up to both slots
            queued = len(engine._queue)
            stragglers = engine.drain()
            assert engine.in_flight == 0
            assert [r["rid"] for r in stragglers] == \
                [f"d{i}" for i in range(4 - queued, 4)]
            # Quiesced: the router's "stop admitting" contract holds.
            assert not engine.submit(ServeRequest("late", prompts[0], 6))
            engine.resume_admission()
            for rec in stragglers:
                assert engine.submit(serve_request_from_record(rec))
            results = engine.run(timeout_s=300)
            for i in range(4):
                assert list(results[f"d{i}"]) == \
                    list(reference[f"ref{i}"]), i

            # -- live weight adoption: ZERO recompiles ----------------
            with pytest.raises(SMPValidationError):
                engine.submit(ServeRequest("mid", prompts[0], 6))
                engine.step()
                while not engine.in_flight:
                    engine.step()
                engine.adopt_params(_tree_copy(params))
            engine.drain()
            engine.resume_admission()
            mark = exec_cache.compile_event_mark()
            seconds = engine.adopt_params(_tree_copy(params), version=1)
            assert seconds >= 0.0 and engine.weights_version == 1
            assert not [
                e for e in exec_cache.compile_events_since(mark)
                if e.get("source") == "fresh"
            ]
            assert _counter("smp_weight_updates_total",
                            outcome="adopted") >= 1
            assert _counter("smp_controller_weights_version") == 1
            # Shape-mismatched checkpoints are refused, not recompiled.
            with pytest.raises(SMPValidationError):
                engine.adopt_params({"bogus": np.zeros(3, np.float32)})

            # -- canary: promote on parity, roll back on corruption ---
            router = RequestRouter()
            handle = LocalReplicaHandle("primary", engine, version=1)
            wins = []
            ctl = ServingController(
                router=router,
                policy=AutoscalePolicy({"queue_depth": 50.0}),
                window_source=lambda: wins.pop(0) if wins else None,
                path=str(tmp_path / "ctl.jsonl"),
                canary_fraction=0.25, canary_windows=1,
            )
            ctl.register_live(handle)
            pinned = [ServeRequest(f"pin{i}", prompts[i], 6)
                      for i in (0, 1)]
            assert ctl.start_canary(
                _tree_copy(params), version=2, pinned=pinned) is True
            assert ctl.canary is not None
            assert engine.weights_version == 2
            wins.append({"seq": 10, "queue_depth": 0.0})
            ctl.tick()               # one clean SLO window -> promote
            assert ctl.canary is None and ctl.promotions == 1
            assert _counter("smp_canary_promotions_total") == 1

            monkeypatch.setenv("SMP_CHAOS", "corrupt_weights@version=3")
            chaos.reset()
            assert ctl.start_canary(
                _tree_copy(params), version=3, pinned=pinned) is False
            assert ctl.rollbacks == 1 and ctl.canary is None
            assert engine.weights_version == 2   # old weights restored
            # Exactly one rollback counter, exactly one forensics bundle.
            assert _counter("smp_canary_rollback_total") == 1
            bundles = [
                d for d in os.listdir(tmp_path / "forensics")
                if d.startswith("bundle_")
            ]
            assert len(bundles) == 1, bundles
            # The restored weights still serve reference tokens.
            out = engine.run(
                [ServeRequest("post", prompts[0], 6)], timeout_s=300)
            assert list(out["post"]) == list(reference["ref0"])
            # The decision feed gates red on the rolled-back version.
            feed = str(tmp_path / "ctl.jsonl")
            recs = [json.loads(l) for l in open(feed)]
            kinds = [r["kind"] for r in recs]
            assert kinds.count("weight_update") == 2
            assert {(r.get("verdict"), r.get("version"))
                    for r in recs if r["kind"] == "canary"} == {
                ("started", 2), ("promoted", 2), ("rolled_back", 3)}
            assert slo_report.main(
                [feed, "--controller", "--check"]) == 1
            ctl.stop()
        finally:
            chaos.reset()
            goodput.reset()


class TestAutoscaleEndToEnd:
    """Burst E2E (slow tier): one oversubscribed replica scales 1->2 on
    the queue-depth breach with an exec-cache warm start, drains back
    2->1 after the burst, and every stream is token-identical to a
    never-scaled run."""

    def test_burst_scales_up_then_drains_down(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("SMP_EXEC_CACHE", "on")
        monkeypatch.setenv("SMP_EXEC_CACHE_DIR", str(tmp_path / "cache"))
        monkeypatch.setenv("SMP_AUTOSCALE", "on")
        monkeypatch.setenv("SMP_SLO", "queue_depth=2")
        monkeypatch.setenv("SMP_AUTOSCALE_COOLDOWN", "0.3")
        monkeypatch.setenv("SMP_AUTOSCALE_MIN", "1")
        monkeypatch.setenv("SMP_AUTOSCALE_MAX", "2")
        monkeypatch.setenv("SMP_AUTOSCALE_HYSTERESIS", "2")
        monkeypatch.setenv("SMP_CONTROLLER_PATH",
                           str(tmp_path / "ctl.jsonl"))
        smp.init({})
        import time as _time

        mod = _zoo()
        params = mod.init(jax.random.key(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
        engines = []

        def _mk():
            eng = ServingEngine(
                mod, params=params, max_slots=2,
                block_tokens_override=4, prefill_chunk=4,
            )
            # Build both programs eagerly: activation IS the warm start
            # (the scale-up event must carry the compile-source counts).
            eng._program("prefill")
            eng._program("decode")
            engines.append(eng)
            return eng

        try:
            prompts = [_prompt(300 + i, 5) for i in range(8)]
            static = _mk()
            reference = static.run(
                [ServeRequest(f"s{i}", prompts[i % 8], 6)
                 for i in range(16)],
                timeout_s=300,
            )

            router = RequestRouter()
            wstate = {"seq": 0, "last": 0.0}

            def _win():
                now = _time.perf_counter()
                if now - wstate["last"] < 0.02:
                    return None
                wstate["last"] = now
                wstate["seq"] += 1
                depth = max(
                    (len(h.engine._queue)
                     for h in router.live_handles()),
                    default=0,
                )
                return {"seq": wstate["seq"], "t_wall": _time.time(),
                        "queue_depth": depth}

            ctl = ServingController.from_env(
                router=router, window_source=_win)
            assert ctl is not None
            ctl.register_live(
                LocalReplicaHandle("replica0", _mk(), version=0))
            ctl.add_standby(
                "replica1",
                lambda: LocalReplicaHandle("replica1", _mk(), version=0),
            )
            # The whole burst lands at once: queue depth breaches
            # immediately and stays breached until the second replica
            # bites.
            for i in range(16):
                assert router.dispatch(
                    ServeRequest(f"a{i}", prompts[i % 8], 6))
            deadline = _time.time() + 120
            while _time.time() < deadline:
                busy = router.step_all()
                ctl.tick()
                if not busy and len(ctl.results()) >= 16:
                    break
            assert len(ctl.results()) >= 16
            # Idle-tick through the cooldown until the drain fires.
            down_deadline = _time.time() + 20
            while ctl.replicas > 1 and _time.time() < down_deadline:
                router.step_all()
                ctl.tick()
                _time.sleep(0.005)
            directions = [e["direction"] for e in ctl.scale_events]
            assert directions[0] == "up" and "down" in directions, \
                directions
            up = ctl.scale_events[0]
            # Warm start: the standby engine compiled nothing fresh —
            # both programs deserialized from the shared cache dir.
            assert up["warm"].get("fresh", 0) == 0, up["warm"]
            assert up["warm"].get("disk_cache", 0) >= 2, up["warm"]
            assert set(up["phases"]) >= {"trigger", "rendezvous",
                                         "warm_start", "first_token"}
            # Token parity with the never-scaled run: nothing dropped,
            # nothing duplicated, across the scale-up AND the drain.
            results = ctl.results()
            for i in range(16):
                assert list(results[f"a{i}"]) == \
                    list(reference[f"s{i}"]), i
            # The feed gates green: both events inside the budget, no
            # canary to promote.
            assert slo_report.main(
                [str(tmp_path / "ctl.jsonl"), "--controller",
                 "--check", "--max-scale-seconds", "60"]) == 0
            ctl.stop()
        finally:
            for eng in engines:
                eng.close()
