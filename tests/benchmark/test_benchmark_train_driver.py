"""The ``train_steps`` driver end to end at a tiny size on the CPU: the
harness's look for a chip is skipped, the rest of a run is driven. With the
timed path broken underneath, ``correct`` comes out false; and the control
(the reference in the precision below the configuration's) fails the tiny
limits where the stated precision passes them."""

import json

import pytest

import benchtiny

CELLS = benchtiny.cells_of_kind("train_steps")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("train"))


@pytest.fixture(scope="module")
def sound(root):
    from benchmark import harness

    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=2 ** 31 + 77)
    outcome = cell.driver().run(run)
    return cell, run, outcome, harness.result_line(run, outcome)


def test_sound_run_is_correct(sound):
    _, run, outcome, line = sound
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    assert line["correct"] is True and line["failed"] == 0


@pytest.mark.parametrize("cell_name", CELLS[1:])
def test_every_other_train_cell_runs_sound_at_a_tiny_size(root, cell_name):
    """The four-chip cell on four virtual devices, pp=2 x tp=2, against the
    reference sharded over the same four."""
    cell, run = benchtiny.cpu_run(root, cell_name, seed=11)
    outcome = cell.driver().run(run)
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    assert len(run.devices) == cell.chips
    if cell.chips > 1:
        assert outcome["context"]["collective_bytes_per_step"] > 0


def test_result_line_has_the_contracts_keys(sound):
    cell, run, outcome, line = sound
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    for m in cell.end_to_end():
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
        assert line["metrics"][m["name"]]["value"] > 0
    json.dumps(line)


def test_rate_counts_every_step_of_the_window(sound):
    cell, run, outcome, _ = sound
    mix = cell.traffic
    steps = outcome["attempted"]
    assert steps >= 2
    rate = outcome["end_to_end"]["train.tokens_per_s_per_chip"]
    assert rate == pytest.approx(
        steps * mix["batch"] * mix["seq"] / run.window_s)
    assert run.window_s >= run.seconds


def test_host_only_per_layer_readers_read_the_context(sound):
    cell, run, outcome, _ = sound
    from benchmark import peaks

    ctx = dict(outcome["context"], run=run, cell=cell,
               peaks=peaks.peaks_for("TPU v5 lite"))
    assert cell.metric_reader("step.dispatch_ms")(ctx) > 0
    # On the CPU the share of the v5e's peak is minute; it must be a share.
    assert 0 < cell.metric_reader("step.mfu")(ctx) < 100


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        root, monkeypatch):
    import smdistributed_modelparallel_tpu as smp

    monkeypatch.setattr(smp.DistributedOptimizer, "step", lambda self: None)
    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=5)
    outcome = cell.driver().run(run)
    assert outcome["correct"] is False


def test_part_of_the_batch_left_out_is_not_correct(root, monkeypatch):
    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=6)
    def half_batch_step(smp):
        import jax.numpy as jnp

        @smp.step
        def train_step(model, ids):
            tgt = jnp.concatenate(
                [ids[:, 1:], jnp.full_like(ids[:, :1], -100)], axis=1)
            tgt = tgt.at[:, ids.shape[1] // 2:].set(-100)
            per = model(ids, targets=tgt)
            loss = jnp.sum(per) / (per.shape[0] * (per.shape[1] // 2))
            model.backward(loss)
            return loss

        return train_step

    monkeypatch.setattr(cell.builder(), "train_step", half_batch_step)
    assert cell.driver().run(run)["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_float8_fails_where_bfloat16_passes(root, seed):
    """The control: the reference put in the program's place, computed in
    float8 (the nearest precision below the configuration's bfloat16)."""
    from benchmark.reference import check

    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=seed)
    driver = cell.driver()
    follow = lambda p: driver.follow_with_reference(  # noqa: E731
        cell.config, cell.traffic, seed, cell.traffic["check_steps"], p)
    exact = follow("float32")
    limits = {k: v for k, v in benchtiny.TINY_TRAIN_LIMITS.items()
              if "gap" in k}
    stated, _ = check.train_numbers(follow("bfloat16"), exact)
    control, _ = check.train_numbers(follow("float8"), exact)
    assert check.judge(stated, limits)[0] is True, stated
    assert check.judge(control, limits)[0] is False, control
    assert control["first_grad_norm_gap"] > 3 * stated["first_grad_norm_gap"]
