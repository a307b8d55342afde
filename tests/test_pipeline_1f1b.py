"""1F1B ("interleaved") schedule tests.

Parity targets: reference ``torch/pipeline.py:136-145`` (backward-first
interleaving) and ``torch/server_queue.py:629-676`` (``active_microbatches``
in-flight cap). Covers: static-schedule invariants (plain and virtual-stage
interleaved), interleaved-vs-simple loss/grad parity, virtual-stage
(``virtual_pipeline_degree``) parity + bubble accounting + HLO regression
guards (the ``smp.xray`` census + committed golden fingerprints), the
peak-memory advantage (compiled-HLO temp buffer sizes), and window
sensitivity.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from smdistributed_modelparallel_tpu.backend.state import state
from smdistributed_modelparallel_tpu.utils import hlo_audit
from smdistributed_modelparallel_tpu.parallel.pipeline_1f1b import (
    build_1f1b_schedule,
    build_interleaved_1f1b_schedule,
    interleaved_phase_bounds,
    schedule_occupancy,
)
from smdistributed_modelparallel_tpu.models.transformer_lm import TransformerLM
from tests.models import softmax_xent


class TestSchedule:
    @pytest.mark.parametrize("S,M,W", [
        (2, 4, 3), (4, 8, 5), (4, 8, 2), (4, 4, 1), (3, 7, 4), (1, 4, 2),
    ])
    def test_invariants(self, S, M, W):
        fwd, bwd = build_1f1b_schedule(S, M, W)
        n_ticks = fwd.shape[0]
        fwd_tick, bwd_tick = {}, {}
        for t in range(n_ticks):
            for s in range(S):
                if fwd[t, s] >= 0:
                    fwd_tick[(s, fwd[t, s])] = t
                if bwd[t, s] >= 0:
                    bwd_tick[(s, bwd[t, s])] = t
        # Every microbatch forwarded and backwarded exactly once per stage.
        assert set(fwd_tick) == {(s, m) for s in range(S) for m in range(M)}
        assert set(bwd_tick) == set(fwd_tick)
        for s in range(S):
            for m in range(M):
                if s > 0:
                    assert fwd_tick[(s - 1, m)] < fwd_tick[(s, m)]
                if s < S - 1:
                    assert bwd_tick[(s + 1, m)] < bwd_tick[(s, m)]
                assert fwd_tick[(s, m)] <= bwd_tick[(s, m)]
        # In-flight cap: at any tick, per stage, #fwd-done - #bwd-done <= W.
        for s in range(S):
            for t in range(n_ticks):
                fwd_done = sum(1 for m in range(M) if fwd_tick[(s, m)] <= t)
                bwd_done = sum(1 for m in range(M) if bwd_tick[(s, m)] <= t)
                assert fwd_done - bwd_done <= W

    def test_window_caps_depth(self):
        # W=1 means strictly alternating F/B per stage.
        fwd, bwd = build_1f1b_schedule(4, 8, 1)
        assert fwd.shape == bwd.shape

    def test_larger_window_is_shorter_or_equal(self):
        f1, _ = build_1f1b_schedule(4, 8, 2)
        f2, _ = build_1f1b_schedule(4, 8, 6)
        assert f2.shape[0] <= f1.shape[0]


class TestInterleavedSchedule:
    """Generalized (chunk, microbatch) schedule: virtual pipeline stages."""

    @pytest.mark.parametrize("S,M,W,V", [
        (2, 4, 3, 2), (2, 8, 4, 2), (2, 8, 4, 4), (4, 8, 8, 2),
        (3, 7, 6, 3), (2, 8, 2, 2), (4, 4, 2, 2), (2, 3, 1, 3),
        (1, 4, 2, 2), (3, 9, 6, 2),
    ])
    def test_invariants(self, S, M, W, V):
        fk, fm, bk, bm = build_interleaved_1f1b_schedule(S, M, W, V)
        C = S * V
        n_ticks = fm.shape[0]
        fwd_tick, bwd_tick = {}, {}
        for t in range(n_ticks):
            for s in range(S):
                if fm[t, s] >= 0:
                    c = fk[t, s] * S + s
                    assert (c, fm[t, s]) not in fwd_tick
                    fwd_tick[(c, fm[t, s])] = t
                if bm[t, s] >= 0:
                    c = bk[t, s] * S + s
                    assert (c, bm[t, s]) not in bwd_tick
                    bwd_tick[(c, bm[t, s])] = t
        # Every (chunk, microbatch) forwarded and backwarded exactly once.
        want = {(c, m) for c in range(C) for m in range(M)}
        assert set(fwd_tick) == want
        assert set(bwd_tick) == want
        for c in range(C):
            for m in range(M):
                # Cross-chunk ordering (chunk c -> c+1 crosses one stage
                # boundary, so strictly-earlier ticks).
                if c > 0:
                    assert fwd_tick[(c - 1, m)] < fwd_tick[(c, m)]
                if c < C - 1:
                    assert bwd_tick[(c + 1, m)] < bwd_tick[(c, m)]
                # Per-chunk fwd before bwd (same tick only legal on the
                # last chunk, whose cotangent comes from the loss).
                assert fwd_tick[(c, m)] <= bwd_tick[(c, m)]
                if fwd_tick[(c, m)] == bwd_tick[(c, m)]:
                    assert c == C - 1
        # In-flight window cap, per (stage, chunk).
        for c in range(C):
            for t in range(n_ticks):
                fdone = sum(1 for m in range(M) if fwd_tick[(c, m)] <= t)
                bdone = sum(1 for m in range(M) if bwd_tick[(c, m)] <= t)
                assert fdone - bdone <= W, (c, t)

    @pytest.mark.parametrize("S,M,W", [
        (2, 4, 3), (4, 8, 5), (4, 4, 1), (3, 7, 4), (1, 4, 2),
    ])
    def test_v1_reduces_to_plain_schedule(self, S, M, W):
        """At virtual=1 the generalized scheduler IS the plain one: the
        default path's baked schedule (and so its HLO) cannot drift."""
        fk, fm, bk, bm = build_interleaved_1f1b_schedule(S, M, W, 1)
        fwd, bwd = build_1f1b_schedule(S, M, W)
        assert np.array_equal(fm, fwd)
        assert np.array_equal(bm, bwd)
        assert (fk[fm >= 0] == 0).all() and (bk[bm >= 0] == 0).all()

    def test_occupancy_hits_interleaved_floor_at_pp2(self):
        """(pp=2, mb=8, v=2, default window pp+2): occupancy over executed
        sub-steps equals the interleaved bound 1/17 (vs 1/9 at v=1)."""
        for V, want in ((1, 1 / 9), (2, 1 / 17)):
            fk, fm, bk, bm = build_interleaved_1f1b_schedule(2, 8, 4, V)
            t_b0, t_fe = interleaved_phase_bounds(fm, bm)
            busy, total = schedule_occupancy(
                fm, bm, fwd_ticks=t_fe, bwd_ticks=fm.shape[0] - t_b0
            )
            assert busy == 2 * 2 * V * 8  # chunk sub-steps: 2*S*V*M
            assert 1 - busy / total == pytest.approx(want)

    def test_occupancy_default_args_match_v1_executor(self):
        """What the v=1 executor records: the denominator holds only the
        sub-steps it runs (no backward on warmup ticks, no forward on
        cooldown ticks), not the paired 2*T*S that schedule_occupancy's
        defaults give."""
        fwd, bwd = build_1f1b_schedule(2, 4, 3)
        n_ticks = fwd.shape[0]
        busy, paired = schedule_occupancy(fwd, bwd)
        assert (busy, paired) == (2 * 2 * 4, 2 * n_ticks * 2)
        t_b0, t_fe = interleaved_phase_bounds(fwd, bwd)
        assert (t_b0, t_fe, n_ticks) == (1, 5, 6)
        busy, total = schedule_occupancy(
            fwd, bwd, fwd_ticks=t_fe, bwd_ticks=n_ticks - t_b0
        )
        assert (busy, total) == (16, 2 * 5 * 2)     # bubble 1/5, was 1/3

    def test_phase_bounds_split_warmup_and_cooldown(self):
        fk, fm, bk, bm = build_interleaved_1f1b_schedule(2, 8, 4, 2)
        t_b0, t_fe = interleaved_phase_bounds(fm, bm)
        assert 0 < t_b0 < t_fe <= fm.shape[0]
        assert (bm[:t_b0] < 0).all()       # warmup: no backward anywhere
        assert (fm[t_fe:] < 0).all()       # cooldown: no forward anywhere
        assert (bm[t_b0] >= 0).any() and (fm[t_fe - 1] >= 0).any()


def _train(cfg, steps=2, n_layers=4, batch=8, step_fn=None):
    smp.reset()
    smp.init(cfg)
    module = TransformerLM(
        vocab_size=32, max_len=12, d_model=16, n_layers=n_layers, n_heads=2,
    )
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    ids = jax.random.randint(jax.random.key(0), (batch, 12), 0, 32)

    if step_fn is None:
        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss
    else:
        train_step = step_fn

    losses, grads = [], None
    for i in range(steps):
        out = train_step(model, ids)
        if i == 0:
            grads = jax.device_get(model.grads)
        losses.append(float(out.reduce_mean()))
        optimizer.step()
    report = state.last_compile_report
    return losses, grads, report


def _run_step(cfg, M):
    """One step of a (loss, logits) train step on 2*M seeded rows: the
    per-microbatch losses and outputs, the gradients, the step function."""
    smp.reset()
    smp.init(dict(cfg, microbatches=M))
    module = TransformerLM(
        vocab_size=32, max_len=12, d_model=16, n_layers=4, n_heads=2,
    )
    model = smp.DistributedModel(module)
    ids = jax.random.randint(jax.random.key(0), (2 * M, 12), 0, 32)

    @smp.step
    def train_step(model, batch):
        logits = model(batch)
        loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
        model.backward(loss)
        return loss, logits

    loss, logits = train_step(model, ids).stack()
    return (np.asarray(loss), np.asarray(logits),
            jax.device_get(model.grads), train_step)


@functools.lru_cache(maxsize=None)
def _reference_run(S, M):
    """The fill-drain executor at pp=S (S > 1) or the unsplit model
    (S == 1), once per (S, M) of the parity matrix below."""
    cfg = {"_device_count_override": S}
    if S > 1:
        cfg.update(pipeline_parallel_degree=S, pipeline="simple", ddp=True)
    return _run_step(cfg, M)[:3]


def _plain_1f1b_cfg(S, W, M):
    """The plain executor at window W (the config refuses a window above
    the microbatch count; the executor clamps its own default the same)."""
    return {"pipeline_parallel_degree": S, "active_microbatches": min(W, M),
            "ddp": True, "_device_count_override": S}


def _op_names(hlo_text, scope):
    """The op_name of every instruction of the compiled text that sits
    under ``scope`` (the op index keeps the nearest scope, not the path)."""
    return [m.group(1) for m in hlo_audit._OP_NAME_RE.finditer(hlo_text)
            if scope in m.group(1).split(";", 1)[0]]


class TestPlainSkipsEmptySubSteps:
    """The plain (v=1) executor runs only the sub-steps its baked schedule
    uses: no backward sub-step on the leading ticks that have no backward
    on any stage, no forward sub-step on the trailing ticks that have no
    forward."""

    @pytest.mark.parametrize("S,M,W", [
        (2, 8, 3), (2, 4, 3), (4, 8, 5), (2, 8, 2), (3, 7, 4), (2, 2, 3),
    ])
    def test_parity_and_recorded_bubble(self, S, M, W):
        losses, outs, grads, _ = _run_step(_plain_1f1b_cfg(S, W, M), M)
        measured, _, _ = _bubble_gauges()
        fwd, bwd = build_1f1b_schedule(S, M, min(W, M))
        n_ticks = fwd.shape[0]
        t_b0, t_fe = interleaved_phase_bounds(fwd, bwd)
        busy = 2 * S * M
        assert measured == pytest.approx(
            1 - busy / (S * (t_fe + n_ticks - t_b0))
        )
        assert measured < 1 - busy / (2 * S * n_ticks)      # the paired tick's
        for ref_S in (S, 1):
            ref_losses, ref_outs, ref_grads = _reference_run(ref_S, M)
            np.testing.assert_allclose(
                losses, ref_losses, rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(outs, ref_outs, rtol=1e-4, atol=1e-5)
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=1e-3, atol=1e-5
                ),
                grads, ref_grads,
            )

    def test_sub_steps_sit_behind_the_tick_conditionals(self, monkeypatch):
        """Read off the compiled pp=2, mb=4 step: every instruction of a
        sub-step (``tick_fwd``; ``head``, ``tick_bwd``) is inside a
        ``lax.cond`` branch of the one tick loop, and the op index still
        attributes it to its sub-step. With bounds that leave no tick
        without a forward or a backward (t_b0 == 0, t_fe == n_ticks) no
        conditional is emitted, and that program, the one this executor
        used to compile, gives the same numbers bit for bit: the sub-steps
        skipped only ever wrote masked zeros."""
        from smdistributed_modelparallel_tpu.parallel import pipeline_1f1b

        subs = ("smp/pipeline/tick_fwd", "smp/pipeline/head",
                "smp/pipeline/tick_bwd")
        skipping = _run_step(_plain_1f1b_cfg(2, 3, 4), 4)
        text = _compiled_step_hlo(skipping[3])
        for sub in subs:
            names = _op_names(text, sub)
            assert names
            assert all("cond/branch_1_fun/" in n[:n.index(sub)]
                       for n in names), sub
        assert not _op_names(text, "smp/pipeline/warmup")
        assert not _op_names(text, "smp/pipeline/cooldown")
        index = _audit_of(skipping[3]).op_index
        assert set(subs) <= {r["scope"] for r in index.values()}

        monkeypatch.setattr(
            pipeline_1f1b, "interleaved_phase_bounds",
            lambda fwd, bwd: (0, int(fwd.shape[0])),
        )
        paired = _run_step(_plain_1f1b_cfg(2, 3, 4), 4)
        measured, _, _ = _bubble_gauges()
        assert measured == pytest.approx(1 - 16 / 24)
        text = _compiled_step_hlo(paired[3])
        for sub in subs:
            names = _op_names(text, sub)
            assert names and not any(
                "cond/" in n[:n.index(sub)] for n in names), sub
        np.testing.assert_array_equal(skipping[0], paired[0])
        np.testing.assert_array_equal(skipping[1], paired[1])
        jax.tree_util.tree_map(
            np.testing.assert_array_equal, skipping[2], paired[2]
        )

    def test_health_rows_name_stage_and_microbatch(self, monkeypatch):
        """The sentinel's rows ride in the tick carry and are written by
        the forward sub-step only: a token whose embedding is NaN and that
        only microbatch 2 holds is put down to microbatch 2 on both
        stages; a NaN in layer 2 (stage 1) to stage 1 alone."""
        from smdistributed_modelparallel_tpu.utils import health

        monkeypatch.setenv("SMP_HEALTH_CHECK", "cheap")
        smp.reset()
        smp.init(dict(_plain_1f1b_cfg(2, 3, 4), microbatches=4))
        module = TransformerLM(
            vocab_size=32, max_len=12, d_model=16, n_layers=4, n_heads=2,
        )
        model = smp.DistributedModel(module)
        ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 31)
        ids = ids.at[4:6, 3].set(31)        # rows 4-5 are microbatch 2

        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

        def stage_rows():
            train_step(model, ids)
            health.monitor.flush()
            tags = health.monitor.last_check["tags"]
            return [tags[f"pp/1f1b/stage{s}"] for s in range(2)]

        rows = stage_rows()
        assert [r["bad"] for r in rows] == [0, 0]
        assert all(r["absmax"] > 0 for r in rows)
        clean = jax.device_get(model.params)

        def poisoned(path, index):
            params = jax.tree_util.tree_map(jnp.asarray, clean)
            node = params
            for key in path[:-1]:
                node = node[key]
            node[path[-1]] = node[path[-1]].at[index].set(jnp.nan)
            return params

        embed_path = next(
            tuple(k.key for k in path)
            for path, leaf in jax.tree_util.tree_leaves_with_path(clean)
            if leaf.shape == (32, 16)
        )
        model.params = poisoned(embed_path, 31)
        rows = stage_rows()
        assert [r["microbatch"] for r in rows] == [2, 2]
        assert all(r["bad"] > 0 for r in rows)

        model.params = poisoned(
            ("layers", "block", "attn", "qkv", "kernel"), 2)
        rows = stage_rows()
        assert rows[0]["bad"] == 0 and rows[0]["microbatch"] == -1
        assert rows[1]["bad"] > 0 and rows[1]["microbatch"] == 0


class TestInterleavedParity:
    def test_interleaved_matches_simple_and_baseline(self):
        base, base_grads, _ = _train({"microbatches": 4})
        simple, s_grads, _ = _train({
            "pipeline_parallel_degree": 4, "microbatches": 4,
            "pipeline": "simple", "ddp": True,
        })
        inter, i_grads, _ = _train({
            "pipeline_parallel_degree": 4, "microbatches": 4,
            "pipeline": "interleaved", "ddp": True,
        })
        np.testing.assert_allclose(simple, base, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(inter, base, rtol=1e-4, atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5),
            i_grads, base_grads,
        )

    def test_active_microbatches_window_parity(self):
        base, _, _ = _train({"microbatches": 8})
        for w in (2, 4):
            windowed, _, _ = _train({
                "pipeline_parallel_degree": 4, "microbatches": 8,
                "active_microbatches": w, "ddp": True,
            })
            np.testing.assert_allclose(windowed, base, rtol=1e-4, atol=1e-5)


def _bubble_gauges():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    metrics = telemetry.report()["metrics"]

    def one(name):
        series = [
            s for s in metrics.get(name, {}).get("series", [])
            if s.get("labels", {}).get("schedule") == "1f1b"
        ]
        return series[0]["value"] if series else None

    return (one("smp_pipeline_bubble_fraction"),
            one("smp_pipeline_bubble_fraction_theoretical"),
            one("smp_pipeline_virtual_stages"))


class TestVirtualStages:
    def test_v2_trains_reports_bubble_and_retraces(self):
        """Fast-tier end-to-end: one shared @smp.step function trained at
        (pp=2, mb=8, v=1) then re-initialized at v=2. Asserts the
        acceptance numbers — theoretical bubble 1/9 -> 1/17 with the
        measured occupancy gauge agreeing — plus loss parity between the
        two virtual degrees and a fresh compile (cache retrace) for the
        changed ``virtual_pipeline_degree``."""
        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

        v1, _, _ = _train(
            {"pipeline_parallel_degree": 2, "microbatches": 8, "ddp": True},
            step_fn=train_step,
        )
        measured, theoretical, virt = _bubble_gauges()
        assert theoretical == pytest.approx(1 / 9)
        assert virt == 1.0
        keys_after_v1 = set(train_step._cache)

        v2, _, _ = _train(
            {"pipeline_parallel_degree": 2, "microbatches": 8, "ddp": True,
             "virtual_pipeline_degree": 2},
            step_fn=train_step,
        )
        measured, theoretical, virt = _bubble_gauges()
        assert theoretical == pytest.approx(1 / 17)
        assert measured == pytest.approx(1 / 17)
        assert virt == 2.0
        # Changed v -> a NEW compiled entry (the pipeline tuple is part of
        # the cache key; serving the v=1 program would replay the wrong
        # schedule).
        new_keys = set(train_step._cache) - keys_after_v1
        assert new_keys, "v=2 did not produce a fresh compiled step"
        assert any(k[1][2] == 2 for k in new_keys)
        np.testing.assert_allclose(v2, v1, rtol=1e-4, atol=1e-5)

    def test_chunked_partition_layout(self):
        """Round-robin chunk placement: L=8 over pp2 x v2 -> 4 chunks of 2,
        chunk c on stage c % 2, and the flight recorder's schedule slots
        carry the chunk coordinate."""
        from smdistributed_modelparallel_tpu.utils.flight_recorder import (
            flight_recorder,
        )

        flight_recorder.clear()
        _train(
            {"pipeline_parallel_degree": 2, "microbatches": 4, "ddp": True,
             "virtual_pipeline_degree": 2},
            steps=1, n_layers=8,
        )
        spec = state.model._pipeline_spec
        assert spec.virtual_degree == 2
        assert spec.boundaries == [(0, 2), (2, 4), (4, 6), (6, 8)]
        assignment = state.model._partition_result
        assert assignment["layers/block#0"] == 0   # chunk 0 -> stage 0
        assert assignment["layers/block#2"] == 1   # chunk 1 -> stage 1
        assert assignment["layers/block#4"] == 0   # chunk 2 -> stage 0
        assert assignment["layers/block#6"] == 1   # chunk 3 -> stage 1
        slots = [e for e in flight_recorder.snapshot()
                 if e["kind"] == "slot" and e.get("schedule") == "1f1b"]
        assert slots and all("chunk" in e for e in slots)
        # Slots carry GLOBAL chunk (boundary) ids; chunk c runs on stage
        # c % pp.
        assert {e["chunk"] for e in slots} == {0, 1, 2, 3}
        assert all(e["chunk"] % 2 == e["stage"] for e in slots)

    def test_manual_pins_rejected_under_virtual(self):
        from smdistributed_modelparallel_tpu.utils.exceptions import (
            PartitionError,
        )

        smp.reset()
        smp.init({"pipeline_parallel_degree": 2, "microbatches": 4,
                  "ddp": True, "virtual_pipeline_degree": 2})
        smp.set_partition("layers/block#0", 1)
        module = TransformerLM(
            vocab_size=32, max_len=12, d_model=16, n_layers=4, n_heads=2,
        )
        model = smp.DistributedModel(module)
        ids = jax.random.randint(jax.random.key(0), (8, 12), 0, 32)

        @smp.step
        def train_step(model, batch):
            logits = model(batch)
            loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
            model.backward(loss)
            return loss

        with pytest.raises(PartitionError, match="virtual_pipeline_degree"):
            train_step(model, ids)

    def test_config_rejects_virtual_with_simple_schedule(self):
        from smdistributed_modelparallel_tpu.utils.exceptions import ConfigError

        with pytest.raises(ConfigError):
            smp.ModelParallelConfig({
                "pipeline": "simple", "virtual_pipeline_degree": 2,
            })

    def test_config_alias_and_default(self):
        cfg = smp.ModelParallelConfig({"virtual_pipeline_parallel_degree": 3})
        assert cfg.virtual_pipeline_degree == 3
        assert smp.ModelParallelConfig({}).virtual_pipeline_degree == 1


def _strip_hlo(text):
    """The program without its source positions: the per-instruction
    ``metadata={...}`` and the module header's FileNames ... StackFrames
    tables (two call sites of one step differ there and nowhere else)."""
    text = re.sub(r"metadata=\{[^}]*\}", "", text)
    return re.sub(
        r"(?ms)^FileNames\n.*?^StackFrames\n(?:\d+ \{[^\n]*\}\n)*", "", text
    )


def _mk_step():
    """A fresh @smp.step train step (identical source each call, so the
    lowered programs of two instances are comparable byte-for-byte)."""

    @smp.step
    def train_step(model, batch):
        logits = model(batch)
        loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
        model.backward(loss)
        return loss

    return train_step


def _compiled_step_hlo(step_fn):
    runners = list(step_fn._cache.values())
    assert len(runners) == 1
    compiled = runners[0].holder.get("compiled")
    if compiled is None:
        pytest.skip("AOT step executable unavailable on this backend")
    return compiled.as_text()


def _audit_of(step_fn):
    """The smp.xray audit of the step's single compiled program."""
    audit = hlo_audit.of_step_function(step_fn)
    if audit is None:
        pytest.skip("AOT step executable unavailable on this backend")
    return audit


class TestVirtualHLOGuard:
    """No perf tax on the default path; permutes scale as expected.

    Replication guard (the PR-5 failure class) now goes through the
    ``smp.xray`` census — per-axis attributed counts instead of raw HLO
    substring counting — plus the committed golden fingerprints, so the
    gate survives HLO text-format drift and catches any unexplained
    structural change, not just a vanished permute.
    """

    def test_v1_explicit_knob_is_byte_identical(self):
        """virtual_pipeline_degree=1 AND pipeline="interleaved" AND
        recompute="full" (explicit) vs unset: the compiled pp=2 step must
        be byte-identical — neither the virtual machinery, nor the
        zero-bubble schedule dispatch, nor the recompute planner may leak
        into the default path. A stray budget env var must also be inert
        at the default knob (idle-value canonicalization)."""
        import os

        step_a, step_b = _mk_step(), _mk_step()
        _train({"pipeline_parallel_degree": 2, "microbatches": 4,
                "ddp": True}, steps=1, step_fn=step_a)
        default_hlo = _compiled_step_hlo(step_a)
        os.environ["SMP_RECOMPUTE_BUDGET_MB"] = "7"
        try:
            _train({"pipeline_parallel_degree": 2, "microbatches": 4,
                    "ddp": True, "virtual_pipeline_degree": 1,
                    "pipeline": "interleaved", "recompute": "full"},
                   steps=1, step_fn=step_b)
        finally:
            del os.environ["SMP_RECOMPUTE_BUDGET_MB"]
        explicit_hlo = _compiled_step_hlo(step_b)
        assert _strip_hlo(default_hlo) == _strip_hlo(explicit_hlo)
        # The pp permutes are present in the default program (the guard
        # below compares against this count).
        assert _audit_of(step_b).collective_count(
            "collective-permute", axis="pp"
        ) > 0

    def test_v2_keeps_pipeline_permutes(self):
        """The v=2 program must still be pipeline-partitioned: the chunked
        gather breaks GSPMD's sharding propagation, and without the
        executor's stage-axis pins XLA silently replicates the whole tick
        loop (0 pp-axis collective-permutes — each device computing every
        stage). Static permute count is bounded: the double-buffered
        transfers add no per-chunk permutes (rolls stay
        one-per-direction-per-tick; the tick count, not the op count,
        scales with v). Both programs must also recompile to a clean
        semantic diff against their committed golden fingerprints."""
        step_a, step_b = _mk_step(), _mk_step()
        _train({"pipeline_parallel_degree": 2, "microbatches": 4,
                "ddp": True}, steps=1, step_fn=step_a)
        audit_v1 = _audit_of(step_a)
        _train({"pipeline_parallel_degree": 2, "microbatches": 4,
                "ddp": True, "virtual_pipeline_degree": 2},
               steps=1, step_fn=step_b)
        audit_v2 = _audit_of(step_b)
        v1_count = audit_v1.collective_count("collective-permute", axis="pp")
        v2_count = audit_v2.collective_count("collective-permute", axis="pp")
        assert v1_count > 0
        assert v2_count > 0, "v=2 program lost its pipeline partitioning"
        # Three scan bodies (warmup/steady/cooldown) instead of one, each
        # with the same per-tick permute pair: bounded static growth.
        assert v2_count <= 10 * v1_count
        # The detector agrees: no replication findings on either program.
        assert audit_v1.findings == []
        assert audit_v2.findings == []
        from tests.conftest import assert_matches_hlo_golden

        assert_matches_hlo_golden(audit_v1, "1f1b_pp2_mb4")
        assert_matches_hlo_golden(audit_v2, "interleaved_v2_pp2_mb4")


class TestVirtualParity:
    def test_v2_matches_baseline_and_fill_drain(self):
        """The tentpole numerical contract at (pp=2, v=2): grads, losses
        and outputs interchangeable with the fill-drain executor and the
        pp=1 baseline on the same inputs (same tolerances as the existing
        1F1B parity guarantee)."""
        base, base_grads, _ = _train({"microbatches": 4})
        simple, s_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4,
            "pipeline": "simple", "ddp": True,
        })
        inter, i_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4,
            "virtual_pipeline_degree": 2, "ddp": True,
        })
        np.testing.assert_allclose(inter, base, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(inter, simple, rtol=1e-4, atol=1e-5)
        for got, want in ((i_grads, base_grads), (i_grads, s_grads)):
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    a, b, rtol=1e-3, atol=1e-5
                ),
                got, want,
            )

    def test_v2_uneven_layers_and_window(self):
        """Uneven chunking (L=6 over 4 chunks) and a tight in-flight
        window both preserve parity."""
        base, base_grads, _ = _train({"microbatches": 4}, n_layers=6)
        v2, v2_grads, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 4,
            "virtual_pipeline_degree": 2, "ddp": True,
        }, n_layers=6)
        np.testing.assert_allclose(v2, base, rtol=1e-4, atol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5),
            v2_grads, base_grads,
        )
        base8, _, _ = _train({"microbatches": 8})
        tight, _, _ = _train({
            "pipeline_parallel_degree": 2, "microbatches": 8,
            "virtual_pipeline_degree": 2, "active_microbatches": 2,
            "ddp": True,
        })
        np.testing.assert_allclose(tight, base8, rtol=1e-4, atol=1e-5)


class TestMemory:
    def test_interleaved_uses_less_temp_memory_than_simple(self):
        """The point of 1F1B: bounded in-flight activations. Compare the
        compiled step's temp buffer allocation at pp4 x mb8."""
        _, _, rep_simple = _train({
            "pipeline_parallel_degree": 4, "microbatches": 8,
            "pipeline": "simple", "ddp": True,
        }, steps=1)
        _, _, rep_inter = _train({
            "pipeline_parallel_degree": 4, "microbatches": 8,
            "pipeline": "interleaved", "active_microbatches": 2, "ddp": True,
        }, steps=1)
        assert rep_simple and rep_simple.get("temp_size_in_bytes")
        assert rep_inter and rep_inter.get("temp_size_in_bytes")
        assert (
            rep_inter["temp_size_in_bytes"] < rep_simple["temp_size_in_bytes"]
        ), (rep_inter, rep_simple)
