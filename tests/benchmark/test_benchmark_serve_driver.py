"""The ``serve_open_loop`` driver end to end at a tiny size on the CPU,
with a token altered where it is produced (``correct`` false), and the
float8 control of the greedy-regret comparison."""

import json
import math

import pytest

import benchtiny

CELLS = benchtiny.cells_of_kind("serve_open_loop")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchtiny.tiny_root(tmp_path_factory.mktemp("serve"))


@pytest.fixture(scope="module")
def sound(root):
    from benchmark import harness

    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=2 ** 31 + 5,
                                  seconds=3.0)
    outcome = cell.driver().run(run)
    return cell, run, outcome, harness.result_line(run, outcome)


def test_sound_run_is_correct(sound):
    _, run, outcome, line = sound
    assert outcome["correct"] is True and outcome["failed"] == 0
    assert run.compiles_in_window == 0
    assert line["correct"] is True
    assert outcome["attempted"] >= 10


def test_result_line_has_the_cells_metrics(sound):
    cell, _, _, line = sound
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    for name, entry in line["metrics"].items():
        assert math.isfinite(entry["value"]) and entry["value"] > 0, name
    json.dumps(line)


def test_counters_feed_the_per_layer_readers(sound):
    cell, run, outcome, _ = sound
    ctx = dict(outcome["context"], run=run, cell=cell)
    assert cell.metric_reader("engine.tick_ms")(ctx) > 0
    assert 0 < cell.metric_reader("engine.batch_occupancy")(ctx) <= 100
    per_tick = cell.metric_reader("engine.prefill_tokens_per_tick")(ctx)
    assert 0 < per_tick <= 32           # SMP_PREFILL_CHUNK's default


def test_latency_arithmetic_counts_unfinished_as_infinity(root):
    cell, _ = benchtiny.cpu_run(root, CELLS[0])
    driver = cell.driver()
    reqs = [{"id": f"r{i}", "due_s": 0.1 * i, "prompt": [1, 2],
             "max_new_tokens": 3} for i in range(10)]
    token_t = {r["id"]: [r["due_s"] + 0.05, r["due_s"] + 0.07,
                         r["due_s"] + 0.10] for r in reqs}
    loop = {"token_t": token_t, "done": {r["id"] for r in reqs}}
    e2e, failed, _ = driver.latency_metrics(reqs, loop, seconds=1.0)
    assert failed == 0
    assert e2e["serve.ttft_p90_ms"] == pytest.approx(50.0)
    assert e2e["serve.itl_p95_ms"] == pytest.approx(30.0)
    # r9's last token (due 0.9 + 0.10 = 1.0) is at the edge; r9's are 3.
    assert e2e["serve.out_tokens_per_s"] == pytest.approx(30.0)
    loop["done"] -= {"r8", "r9"}            # two of ten never finished
    e2e, failed, _ = driver.latency_metrics(reqs, loop, seconds=1.0)
    assert failed == 2 and e2e["serve.ttft_p90_ms"] == math.inf


def test_a_token_altered_where_it_is_produced_is_not_correct(
        root, monkeypatch):
    from smdistributed_modelparallel_tpu.serving import engine as engine_mod

    real = engine_mod._sample_rows

    def altered(logits, *rest):
        return (real(logits, *rest) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine_mod, "_sample_rows", altered)
    cell, run = benchtiny.cpu_run(root, CELLS[0], seed=9, seconds=2.0)
    outcome = cell.driver().run(run)
    assert outcome["correct"] is False


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_in_float8_has_a_wider_regret_than_bfloat16(root, seed):
    """At each position of the same prompts and tokens, the regret of the
    token the lower precision puts first."""
    from benchmark import traffic

    cell, _ = benchtiny.cpu_run(root, CELLS[0], seed=seed)
    driver = cell.driver()
    reqs = traffic.requests(cell.traffic, seed, 2.0,
                            cell.config["vocab_size"])[:6]
    results = {r["id"]: r["prompt"][:r["max_new_tokens"]] for r in reqs}
    widest = {}
    for control in ("bfloat16", "float8"):
        regrets = driver.reference_regrets(
            cell.config, seed, reqs, results, control=control)
        widest[control] = max(float(v.max()) for v in regrets.values())
    limit = benchtiny.TINY_SERVE_LIMITS["greedy_regret_max"]
    assert widest["bfloat16"] <= limit < widest["float8"], widest
