"""smp.serving: continuous batching over a paged KV cache.

Tiers (SURVEY §4 style): pure-host allocator units + the randomized
admit/finish fuzz (acceptance: never double-assign, never leak), one
composite engine end-to-end (greedy + stochastic sampling parity against
``smp.generate`` token-for-token, EOS early stop with immediate block
release, chunked prefill interleaving, exactly-two-programs, telemetry +
report rendering — all on a single pair of compiled programs), the X-ray
golden gate for the tp2 decode program (zero replicated-KV findings),
and the pure-python recovery-report tool check. Heavy extra-compile cases
(replicated-pool detector, exec-cache warm start) are slow-tiered in
conftest; the 2-process replica-failover E2E lives in
tests/test_multiprocess.py.
"""

import io
import json
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.models.transformer_lm import (
    TransformerLM,
)
from smdistributed_modelparallel_tpu.serving import (
    BlockAllocator,
    ServeRequest,
    ServingEngine,
)
from smdistributed_modelparallel_tpu.utils.exceptions import (
    SMPValidationError,
)
from smdistributed_modelparallel_tpu.utils.telemetry import telemetry


class TestBlockAllocator:
    def test_reserve_then_lazy_growth(self):
        a = BlockAllocator(num_blocks=10, block_tokens=4,
                           max_blocks_per_seq=8)
        assert a.free_blocks == 9  # block 0 reserved (trash)
        a.reserve("s0", 13)        # worst case 4 blocks
        assert a.used_blocks == 0 and a.reserved_unallocated == 4
        a.ensure("s0", 5)          # 2 blocks materialize
        assert a.used_blocks == 2 and a.reserved_unallocated == 2
        table = a.table("s0")
        assert len(table) == 8 and table[2:] == [0] * 6
        assert 0 not in table[:2]
        a.ensure("s0", 13)
        assert a.used_blocks == 4
        assert a.release("s0") == 4
        assert a.free_blocks == 9 and a.reserved_unallocated == 0

    def test_admission_counts_promises(self):
        a = BlockAllocator(num_blocks=9, block_tokens=4,
                           max_blocks_per_seq=8)
        a.reserve("s0", 16)        # promises 4 of the 8 free
        assert a.can_reserve(16)   # 4 left
        a.reserve("s1", 16)
        assert not a.can_reserve(1)  # everything promised
        a.release("s0")
        assert a.can_reserve(16)

    def test_errors(self):
        a = BlockAllocator(num_blocks=6, block_tokens=4,
                           max_blocks_per_seq=4)
        a.reserve("s0", 8)
        with pytest.raises(ValueError, match="already admitted"):
            a.reserve("s0", 4)
        with pytest.raises(ValueError, match="never reserved"):
            a.ensure("ghost", 4)
        with pytest.raises(ValueError, match="past its reservation"):
            a.ensure("s0", 12)
        with pytest.raises(ValueError, match="cannot admit"):
            a.reserve("too_long", 100)  # exceeds max_blocks_per_seq

    def test_fuzz_never_double_assigns_or_leaks(self):
        """Acceptance: randomized admit/grow/finish against the invariant
        auditor — every block in exactly one place at every step."""
        rng = random.Random(1234)
        a = BlockAllocator(num_blocks=24, block_tokens=4,
                           max_blocks_per_seq=10)
        live = {}
        sid = 0
        for step in range(2000):
            op = rng.random()
            if op < 0.4 and live:
                s = rng.choice(list(live))
                cap = live[s]
                cur = a.blocks_for_tokens(cap[1]) if cap[1] else 0
                grown = min(cap[1] + rng.randint(1, 6), cap[0])
                a.ensure(s, grown)
                live[s] = (cap[0], grown)
            elif op < 0.7:
                tokens = rng.randint(1, 40)
                if a.blocks_for_tokens(tokens) <= a.max_blocks_per_seq \
                        and a.can_reserve(tokens):
                    name = f"s{sid}"
                    sid += 1
                    a.reserve(name, tokens)
                    live[name] = (tokens, 0)
            elif live:
                s = rng.choice(list(live))
                a.release(s)
                del live[s]
            assert a.check() == [], f"invariants broken at step {step}"
        for s in list(live):
            a.release(s)
        assert a.check() == []
        assert a.free_blocks == 23 and a.used_blocks == 0


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


def _generate_ref(mod, params, prompt, max_new, **kw):
    """smp.generate at batch 1 — the parity oracle for every engine
    stream (same key schedule, same sampler composition)."""
    out = np.asarray(smp.generate(
        mod, jnp.asarray(prompt, jnp.int32)[None, :], max_new,
        params=params, **kw,
    ))
    return list(out[0, len(prompt):])


def _truncate_at_eos(tokens, eos):
    if eos is None:
        return list(tokens)
    out = []
    for t in tokens:
        out.append(int(t))
        if int(t) == eos:
            break
    return out


class TestEngineEndToEnd:
    """One engine, one pair of compiled programs, every fast-tier
    behavioral claim — compiles are the expensive part of this suite, so
    the claims share them."""

    def test_continuous_batching_composite(self):
        smp.init({})
        mod = _zoo(pos_type="rotary")
        probe = jnp.zeros((1, 4), jnp.int32)
        params = mod.init(jax.random.key(0), probe)["params"]
        # Pool deliberately tight: 3 slots but only ~2 long sequences'
        # worth of blocks, so admission has to wait for released blocks
        # (paging under contention, not a provisioned rectangle).
        engine = ServingEngine(
            mod, params=params, max_slots=3, num_blocks=13,
            block_tokens_override=4, prefill_chunk=4,
        )

        # -- batch A: ragged greedy, incl. a multi-chunk prompt ---------
        specs = [
            ("g0", _prompt(10, 7), 6),
            ("g1", _prompt(11, 11), 4),    # 3 prefill chunks
            ("g2", _prompt(12, 3), 9),
            ("g3", _prompt(13, 5), 5),
            ("g4", _prompt(14, 9), 7),
        ]
        res = engine.run(
            [ServeRequest(rid, p, m) for rid, p, m in specs],
            timeout_s=300,
        )
        for rid, p, m in specs:
            assert list(res[rid]) == _generate_ref(mod, params, p, m), rid
        assert len(engine._programs) == 2  # prefill-chunk + decode-step
        assert engine.stats["prefill_chunks"] >= 5
        # Continuous batching does strictly fewer decode dispatches than
        # the static batch-max schedule needs slot-steps.
        static_steps = -(-len(specs) // 3) * max(m for _, _, m in specs)
        assert engine.stats["decode_steps"] < static_steps
        # Pool drained: every block released, invariants hold.
        assert engine.alloc.used_blocks == 0
        assert engine.alloc.check() == []

        # -- EOS early-stop + immediate block release -------------------
        p0 = _prompt(20, 6)
        greedy = _generate_ref(mod, params, p0, 8)
        eos = int(greedy[2])  # freeze after 3 tokens
        long_rid = ServeRequest("long", _prompt(21, 6), 12)
        eos_rid = ServeRequest("eos", p0, 8, eos_token_id=eos)
        engine.submit(long_rid)
        engine.submit(eos_rid)
        saw_release = False
        while engine.busy:
            engine.step()
            if "eos" in engine.finished and "long" not in engine.finished:
                # The EOS stream's blocks are back in the pool the moment
                # it finished, while the long stream still decodes.
                assert set(engine.alloc._owned) == {"long"}
                assert engine.alloc.used_blocks == len(
                    engine.alloc._owned["long"]
                )
                saw_release = True
        assert saw_release
        want = _truncate_at_eos(
            _generate_ref(mod, params, p0, 8, eos_token_id=eos), eos
        )
        assert list(engine.results["eos"]) == want
        assert list(engine.results["long"]) == _generate_ref(
            mod, params, _prompt(21, 6), 12
        )

        # -- batch B: stochastic sampling parity (same programs — the
        # sampling params are device inputs, so nothing recompiles) -----
        assert len(engine._programs) == 2
        stoch = [
            ("t0", _prompt(30, 5), 7,
             dict(temperature=1.0, seed=3)),
            ("t1", _prompt(31, 8), 6,
             dict(temperature=0.8, top_k=11, seed=9)),
            ("t2", _prompt(32, 6), 8,
             dict(temperature=1.2, top_p=0.85, seed=4)),
            ("t3", _prompt(33, 7), 5,
             dict(temperature=0.7, top_k=9, top_p=0.9, seed=8)),
        ]
        res = engine.run(
            [ServeRequest(rid, p, m, **kw) for rid, p, m, kw in stoch],
            timeout_s=300,
        )
        for rid, p, m, kw in stoch:
            gen_kw = dict(kw)
            seed = gen_kw.pop("seed")
            want = _generate_ref(
                mod, params, p, m, rng=jax.random.key(seed), **gen_kw
            )
            assert list(res[rid]) == want, rid
        assert len(engine._programs) == 2

        # -- SLO telemetry + report rendering ---------------------------
        rep = telemetry.report()["metrics"]
        events = {
            s["labels"]["event"]: s["value"]
            for s in rep["smp_serve_requests_total"]["series"]
        }
        assert events["admitted"] == 11 and events["finished"] == 11
        kinds = {
            s["labels"]["kind"]: s["value"]
            for s in rep["smp_serve_tokens_total"]["series"]
        }
        assert kinds["generated"] == sum(
            len(engine.results[r]) for r in engine.results
        )
        stats = {
            s["labels"]["stat"]: s["value"]
            for s in rep["smp_serve_ttft_seconds"]["series"]
        }
        assert stats["mean"] > 0 and stats["last"] > 0
        assert any(
            s["labels"].get("state") == "total" and s["value"] == 13
            for s in rep["smp_serve_kv_blocks"]["series"]
        )
        assert rep["smp_serve_programs"]["series"][0]["value"] == 2

        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts",
        ))
        import telemetry_report

        buf = io.StringIO()
        telemetry_report.render(telemetry.report(), out=buf)
        text = buf.getvalue()
        assert "-- serving --" in text
        assert "ttft" in text and "kv pool" in text
        assert "compiled programs: 2" in text

    def test_requires_paged_capable_module(self):
        smp.init({})
        from smdistributed_modelparallel_tpu.nn.transformer import (
            DistributedTransformerLMHead,
        )

        head = DistributedTransformerLMHead(
            num_layers=1, num_attention_heads=2, attention_head_size=8,
            hidden_size=16, intermediate_size=32, vocab_size=31,
            num_positions=16, causal_mask_size=16,
            attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
            embedding_dropout_prob=0.0, deterministic=True,
        )
        with pytest.raises(SMPValidationError, match="paged"):
            ServingEngine(head, params={})

    def test_submit_validation_and_idempotency(self):
        smp.init({})
        mod = _zoo(max_len=16)
        params = mod.init(jax.random.key(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
        engine = ServingEngine(
            mod, params=params, max_slots=2, block_tokens_override=4,
            prefill_chunk=4,
        )
        with pytest.raises(SMPValidationError, match="position limit"):
            engine.submit(ServeRequest("big", list(range(10)), 10))
        assert engine.submit(ServeRequest("a", [1, 2, 3], 2))
        # Same rid queued again: skipped (idempotent re-admission).
        assert not engine.submit(ServeRequest("a", [1, 2, 3], 2))
        # A fully-resumed request completes without generating.
        assert engine.submit(ServeRequest(
            "done", [1, 2], 2, resume_tokens=(5, 6)
        ))
        assert engine.results["done"] == [5, 6]
        assert not engine.submit(ServeRequest("done", [1, 2], 2))


class TestServingXray:
    def test_tp2_decode_golden_and_zero_kv_replication(self, request):
        """ISSUE 14 satellite: the decode program rides the PR-9 audit —
        committed golden fingerprint, and the replicated-KV-pool detector
        reports ZERO findings (the pool shards over tp on the head
        axis)."""
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices for tp2")
        smp.init({"tensor_parallel_degree": 2, "ddp": True})
        mod = TransformerLM(
            vocab_size=64, max_len=32, d_model=32, n_layers=2, n_heads=4,
        )
        ids = jax.random.randint(jax.random.key(1), (1, 6), 0, 64)
        params = mod.init(jax.random.key(0), ids)["params"]
        engine = ServingEngine(
            mod, params=params, max_slots=2, block_tokens_override=4,
            prefill_chunk=4,
        )
        engine._program("decode")
        audit = engine.audits["decode"]
        assert audit is not None
        assert audit.findings == [], audit.findings
        assert audit.collective_count("all-reduce") >= 1  # tp attention
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit, "serving_decode_tp2")
        # The audited program actually serves: tp2 tokens == tp1 oracle.
        p = _prompt(40, 6, vocab=64)
        res = engine.run([ServeRequest("x", p, 4)], timeout_s=300)
        assert list(res["x"]) == _generate_ref(mod, params, p, 4)

    def test_detector_fires_on_replicated_pool(self, monkeypatch):
        """Detector e2e (PR-9 style): neuter the pool's sharding
        constraint and the tp2 decode program must produce a
        replicated_kv_cache finding sized to the pool."""
        if jax.device_count() < 2:
            pytest.skip("needs >= 2 devices for tp2")
        from smdistributed_modelparallel_tpu.nn import utils as nn_utils

        monkeypatch.setattr(
            nn_utils.PagedKVCache, "_shard", lambda self, pool: pool
        )
        smp.init({"tensor_parallel_degree": 2, "ddp": True})
        mod = TransformerLM(
            vocab_size=64, max_len=32, d_model=32, n_layers=2, n_heads=4,
        )
        params = mod.init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
        )["params"]
        engine = ServingEngine(
            mod, params=params, max_slots=2, block_tokens_override=4,
            prefill_chunk=4,
        )
        engine._program("decode")
        audit = engine.audits["decode"]
        assert audit is not None
        kinds = {f["kind"] for f in audit.findings}
        assert "replicated_kv_cache" in kinds, audit.findings
        kv = [f for f in audit.findings
              if f["kind"] == "replicated_kv_cache"]
        assert all(f["bytes_wasted"] > 0 for f in kv)


class TestExecCacheWarmStart:
    def test_serving_programs_warm_start(self, tmp_path, monkeypatch):
        """The two serving programs ride the PR-11 persistent cache: a
        second engine (fresh object, same geometry) deserializes instead
        of compiling, and serves identical tokens."""
        monkeypatch.setenv("SMP_EXEC_CACHE", "on")
        monkeypatch.setenv("SMP_EXEC_CACHE_DIR", str(tmp_path))
        smp.init({})
        mod = _zoo()
        params = mod.init(jax.random.key(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
        p = _prompt(50, 6)

        def serve():
            engine = ServingEngine(
                mod, params=params, max_slots=2,
                block_tokens_override=4, prefill_chunk=4,
            )
            return engine.run(
                [ServeRequest("w", p, 5)], timeout_s=300
            )["w"]

        cold = serve()
        rep = telemetry.report()["metrics"]
        outcomes = {
            s["labels"]["result"]: s["value"]
            for s in rep.get("smp_exec_cache_total", {"series": []})["series"]
        }
        assert outcomes.get("miss", 0) >= 2  # both programs stored
        warm = serve()
        rep = telemetry.report()["metrics"]
        outcomes = {
            s["labels"]["result"]: s["value"]
            for s in rep["smp_exec_cache_total"]["series"]
        }
        assert outcomes.get("hit", 0) >= 2, outcomes
        assert list(cold) == list(warm)


class TestChaosKillReplica:
    def test_spec_parses(self):
        from smdistributed_modelparallel_tpu.resilience.chaos import (
            parse_spec,
        )

        rules = parse_spec("kill_replica@request=2:rank=1")
        assert len(rules) == 1
        assert rules[0].fault == "kill_replica"
        assert rules[0].kv == {"request": "2", "rank": "1"}

    def test_seam_does_not_fire_out_of_scope(self, monkeypatch):
        """The seam must not SIGKILL when the rule targets another rank,
        when request N is unadmitted, finished, or has no tokens yet."""
        import importlib

        # (attribute access would hit the ChaosInjector instance the
        # resilience package re-exports under the same name)
        chaos_mod = importlib.import_module(
            "smdistributed_modelparallel_tpu.resilience.chaos"
        )

        killed = []
        monkeypatch.setattr(
            chaos_mod.os, "kill", lambda pid, sig: killed.append(sig)
        )
        monkeypatch.setenv("SMP_CHAOS", "kill_replica@request=2:rank=5")
        chaos_mod.chaos.reset()
        chaos_mod.chaos.on_serve_decode(lambda n: (3, False))
        assert killed == []  # wrong rank
        monkeypatch.setenv("SMP_CHAOS", "kill_replica@request=2")
        chaos_mod.chaos.reset()
        chaos_mod.chaos.on_serve_decode(lambda n: None)       # unadmitted
        chaos_mod.chaos.on_serve_decode(lambda n: (0, False))  # no tokens
        chaos_mod.chaos.on_serve_decode(lambda n: (4, True))   # finished
        assert killed == []
        chaos_mod.chaos.on_serve_decode(lambda n: (1, False))  # mid-decode
        assert killed, "kill_replica must fire mid-decode"
        chaos_mod.chaos.reset()


class TestRecoveryReportTool:
    def test_recovery_report_parses_serving_failover(self, tmp_path):
        """resilience_probe --recovery understands the serving phase
        vocabulary (detect/readmit/first_token) and holds it to the same
        consistency gates as training recoveries."""
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts",
        ))
        import resilience_probe

        telem = {
            "metrics": {
                "smp_failures_detected_total": {"series": [
                    {"labels": {"kind": "dead"}, "value": 1}
                ]},
                "smp_recoveries_total": {"series": [
                    {"labels": {}, "value": 1}
                ]},
            }
        }
        (tmp_path / "telemetry.rank0.json").write_text(json.dumps(telem))
        flight_lines = [
            {"kind": "meta", "rank": 0},
            {"kind": "supervisor", "event": "recover_begin",
             "wall_us": 1000, "detail": "mode=serving kind=dead"},
            {"kind": "supervisor", "event": "recovery_done",
             "wall_us": 500000,
             "detail": "mttr=1.250s detect=1.000 readmit=0.050 "
                       "first_token=0.200"},
        ]
        (tmp_path / "flight.rank0.jsonl").write_text(
            "\n".join(json.dumps(l) for l in flight_lines) + "\n"
        )
        report = resilience_probe.recovery_report(str(tmp_path))
        assert report["problems"] == [], report["problems"]
        assert report["recoveries_total"] == 1
        rec = report["recoveries"][0]
        assert rec["mode"] == "serving"
        assert rec["phases"] == {
            "detect": 1.0, "readmit": 0.05, "first_token": 0.2
        }
        assert rec["first_step_source"] == "n/a"
        # The cold-recovery gate exempts serving failovers.
        gated = resilience_probe.recovery_report(
            str(tmp_path), max_cold_recoveries=0
        )
        assert gated["problems"] == [], gated["problems"]


class TestBf16AndChips:
    """Found bringing the engine up on the chip (PR 21): nothing had run it
    under ``bf16: True``, and it counted the host's chips as its own."""

    def test_bf16_engine_matches_generate(self):
        """The pools take the dtype of the K/V the programs write (the
        cache used to be shaped from un-cast fp32 parameters, and the bf16
        programs then failed to trace)."""
        smp.init({"bf16": True}, devices=jax.devices()[:1])
        mod = _zoo()
        params = mod.init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
        engine = ServingEngine(
            mod, params=params, max_slots=2, block_tokens_override=4,
            prefill_chunk=4,
        )
        pools = [
            leaf for leaf in jax.tree_util.tree_leaves(engine._cache)
            if leaf.ndim == 5
        ]
        assert pools and all(p.dtype == jnp.bfloat16 for p in pools)
        p = _prompt(50, 6)
        res = engine.run([ServeRequest("x", p, 4)], timeout_s=300)
        assert list(res["x"]) == _generate_ref(mod, params, p, 4)

    @pytest.mark.parametrize("n", [1, 2])
    def test_chips_are_the_mesh_devices(self, n):
        smp.init({}, devices=jax.devices()[:n])
        mod = _zoo()
        params = mod.init(
            jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
        engine = ServingEngine(mod, params=params, max_slots=2)
        assert engine._chips == n
