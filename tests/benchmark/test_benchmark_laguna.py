"""The Laguna family's benchmark files: the configuration against the
catalog's row, the new driver end to end at tiny widths on the CPU (the
program against the plain reference through the driver's own functions, the
float8 control failing the same limits), the builder's translation against
the repo's numpy translator, the reference's blocks, the token law, the
FLOP and byte counts by hand, and each new reader on a made-up context."""

import json
import os

import pytest

import benchtiny
import lagunatiny
from benchmark import laguna_flops, loader

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Laguna-S-2.1":
                return row
    pytest.fail("no Laguna-S-2.1 row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, lagunatiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_or_lists_it_as_reduced():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key


def test_config_keeps_every_width_and_states_its_share():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["sliding_window"],
            cfg["num_experts_per_tok"]) == (3072, 128, 12288, 1024, 1024,
                                            512, 10)
    assert cfg["num_experts_published"] == 256 and cfg["num_experts"] == 8
    assert cfg["num_attention_heads_per_layer"] == [6, 9, 9, 9, 6]
    assert cfg["layer_types"] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["vocab_size"] * 8 == 100352
    # the published 72 : 48 query heads in 9 : 6, one KV head for each group
    assert [h * 8 for h in cfg["num_attention_heads_per_layer"]] == \
        [48, 72, 72, 72, 48]
    for key in cfg["reduced"]:
        assert cfg["reduced"][key]
    assert cfg["assumed"] and "32 chips" in cfg["deployment"]


def test_held_parameters_are_what_the_issue_counted():
    import numpy as np

    from benchmark import laguna_weights

    spec = laguna_weights.spec_for(committed())
    by_kind = {}
    for name, (shape, _, _) in spec.items():
        kind = name.split(".")[2] if name.startswith("model.layers.") \
            else "ends"
        by_kind[kind] = by_kind.get(kind, 0) + int(np.prod(shape))
    assert round(by_kind["lead_dense"] / 1e6, 1) == 118.8
    assert round(by_kind["window"] / 3e6, 1) == 93.6
    assert round(by_kind["full"] / 1e6, 2) == 91.25       # the issue: 91.2
    assert round(sum(by_kind.values()) / 1e6, 1) == 568.0


# ------------------------------------------------------ counts, by hand

@pytest.mark.parametrize("seq,window,pairs", [
    (4, None, 10),      # 1 + 2 + 3 + 4
    (4, 2, 7),          # 1 + 2 + 2 + 2
    (3, 8, 6),          # the window reaches past the start: the triangle
    (8192, 512, 512 * 513 // 2 + (8192 - 512) * 512),
    (256, 512, 256 * 257 // 2),     # a window longer than the sequence
])
def test_window_pairs(seq, window, pairs):
    assert laguna_flops.window_pairs(seq, window) == pairs


def test_attention_counts_the_band_and_one_kv_head():
    cfg = committed()
    full, band = 8192 * 8193 // 2, 512 * 513 // 2 + 7680 * 512
    forward = 4 * 128 * (2 * 6 * full + 3 * 9 * band)
    assert laguna_flops.attention_forward_flops(cfg, 8192) == forward
    assert laguna_flops.train_attention_flops_per_step(cfg, 4, 8192) == \
        3 * forward * 4
    # six tensors the size of the query heads, six the size of the KV head
    assert laguna_flops.train_attention_bytes_per_step(cfg, 4, 8192) == \
        2 * 6 * 4 * 8192 * 128 * ((6 + 1) * 2 + (9 + 1) * 3)
    # a window far shorter than a block still counts 512 keys a query
    assert band / full < 1 / 8


def test_matmul_counts_by_hand():
    cfg = committed()
    D, hd = 3072, 128
    attention = lambda H: D * hd * (2 * H + 2) + D * H       # noqa: E731
    dense = attention(6) + 3 * D * 12288
    sparse = lambda H: attention(H) + D * 256 + 3 * D * 1024  # noqa: E731
    expected = D * 12544 + dense + 3 * sparse(9) + sparse(6)
    assert laguna_flops.dense_matmul_params(cfg) == expected
    assert laguna_flops.expert_flops_per_row(cfg) == 18 * D * 1024
    step = laguna_flops.train_flops_per_step(cfg, 4, 8192, 10240)
    assert step == (6 * expected * 32768 + 18 * D * 1024 * 10240
                    + laguna_flops.train_attention_flops_per_step(
                        cfg, 4, 8192))
    # about 1.5 GFLOP a token trained, as the issue reckoned
    assert 1.4e9 < step / 32768 < 1.6e9
    assert laguna_flops.grouped_matmul_bytes(cfg, 100, 2) == 2 * (
        5 * 100 * D + 3 * 8 * 3 * D * 1024 * 2)


def test_token_law_has_no_heavy_id():
    import jax
    import numpy as np

    from benchmark import laguna_weights

    ids = np.asarray(jax.jit(lambda s: laguna_weights.token_batches(
        s, 8, 4, 8192, 12544, 1000))(np.uint32(5)))
    assert ids.min() >= 0 and ids.max() < 12544
    counts = np.bincount(ids.reshape(-1), minlength=12544) / ids.size
    assert counts.max() < 0.001             # 0.04% expected, 0.1% allowed
    low, high = counts[:500].mean(), counts[-500:].mean()
    assert 9 < low / high < 15              # (12544 + 1000) / 1000 = 13.5
    assert len({tuple(r) for r in ids.reshape(-1, 8192)[:, :16]}) == 32


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lagunatiny.tiny_root(tmp_path_factory.mktemp("laguna"))


@pytest.fixture(scope="module")
def sound(root):
    cell, run = benchtiny.cpu_run(root, lagunatiny.CELL, seed=2 ** 31 + 5,
                                  seconds=1.0)
    run.control = "float8"
    lines = []
    from benchmark import harness

    said = harness.say
    harness.say = lambda what, **f: lines.append((what, f))
    try:
        outcome = cell.driver().run(run)
    finally:
        harness.say = said
    return cell, run, outcome, dict(lines)


def test_program_follows_the_reference_through_the_new_driver(sound):
    _, run, outcome, said = sound
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    rows = {r["number"]: r for r in said["compared"]["rows"]}
    assert set(rows) == set(lagunatiny.TINY_LIMITS)
    assert rows["moe_dropped_assignments"]["value"] == 0
    assert said["compared"]["routing_difference"] < 0.05
    assert said["compared"]["smp_moe_local_assignments"] > 0
    by_step = said["compared"]["moe_rows_by_step"]
    assert len(by_step) == outcome["attempted"]
    assert sum(by_step) == said["compared"]["smp_moe_local_assignments"]
    assert rows["weights_moved_in_window"]["value"] == 0.0
    first = said["compared"]["moe_rows_first_checked_step"]
    assert abs(first["program"] - first["reference"]) <= 0.05 * first[
        "reference"]


def test_control_fails_the_limits_the_program_passes(sound):
    from benchmark.reference import check

    *_, said = sound
    correct, rows = check.judge(
        said["control"]["numbers"], lagunatiny.TINY_LIMITS)
    assert correct is False
    failed = {r["number"] for r in rows if not r["ok"]}
    assert failed & {"loss_gap_step1", "first_grad_norm_gap"}


def test_context_counts_the_rows_the_program_counted(sound):
    cell, _, outcome, said = sound
    ctx, cfg = outcome["context"], cell.config
    rows = said["compared"]["smp_moe_local_assignments"]
    assert ctx["moe"]["rows_in_window"] == rows
    assert ctx["moe"]["grouped_flops_in_window"] == \
        laguna_flops.expert_flops_per_row(cfg) * rows
    assert ctx["flops_per_step"] == pytest.approx(
        laguna_flops.train_flops_per_step(
            cfg, 4, 32, rows / outcome["attempted"]))
    # 4 expert layers x 4 rows x 32 tokens x 4 a token x 4 of 16 held
    assert 0.5 < rows / outcome["attempted"] / 512 < 2.0


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"]["train.tokens_per_s_per_chip"]["value"] > 0


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import laguna_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import laguna

    cell = loader.Manifest(root).cell(lagunatiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: laguna_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    # the same weights as a per-layer Hugging Face state dict
    pattern, kinds = laguna_weights.plan(cfg)
    sd, seen = {}, {}
    for i, kind in enumerate(pattern):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        prefix = f"model.layers.{kind}."
        for name, value in w.items():
            if not name.startswith(prefix):
                continue
            tail, value = name[len(prefix):], np.asarray(value[j])
            if tail.startswith("mlp.experts."):
                for e in range(value.shape[0]):
                    sd[f"model.layers.{i}.mlp.experts."
                       f"{cfg['experts_held_first'] + e}."
                       f"{tail[len('mlp.experts.'):]}"] = value[e]
            else:
                sd[f"model.layers.{i}.{tail}"] = value
    for name in ("model.embed_tokens.weight", "model.norm.weight",
                 "lm_head.weight"):
        sd[name] = np.asarray(w[name])
    theirs = laguna.translate_hf_state_dict(sd, laguna_weights.hf_view(cfg))
    assert set(theirs) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(np.asarray(flat[key]), theirs[key])
    back = builder.hf_from_flat(cfg, flat)
    assert set(back) == set(w)
    for key in w:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(w[key]))


def test_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Eight query blocks and four token blocks give what one block
    gives: the band's keys, the padding before position 0, the sums."""
    import jax
    import numpy as np

    from benchmark import laguna_weights
    from benchmark.reference import laguna as reference

    cfg = lagunatiny.config()
    w = jax.jit(lambda s: laguna_weights.make_weights(cfg, s))(np.uint32(9))
    ids = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)

    def loss_and_grad():
        return jax.value_and_grad(
            lambda w: reference.next_token_loss_sum(
                cfg, w, ids, "float32")[0])(w)

    whole, g_whole = loss_and_grad()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 4)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 8)
    blocks, g_blocks = loss_and_grad()
    np.testing.assert_allclose(float(blocks), float(whole), rtol=1e-5)
    for key in g_whole:
        np.testing.assert_allclose(
            np.asarray(g_blocks[key]), np.asarray(g_whole[key]),
            rtol=2e-3, atol=2e-5)


# ------------------------------------------------------------ the readers

class FakeTrace(dict):
    pass


def reader_context():
    seconds = {"fusion.1": 2.0, "smp_flash_fwd.3": 1.0, "smp_flash_fwd.4": 0.5,
               "ragged-dot.7": 3.0, "fusion.9": 1.5, "sort.2": 1.0,
               "fusion.20": 1.0, "unknown.1": 10.0}
    trace = FakeTrace(op_self_s=seconds, busy_s_by_device=[20.0])
    index = {
        # an op under one scope has ``scope`` alone; nested ones, ``scopes``
        "fusion.1": {"phase": "forward", "scope": "smp/layer/lead_dense"},
        "smp_flash_fwd.3": {"scopes": ("smp/layer/window", "smp/attn/window")},
        "smp_flash_fwd.4": {"scopes": ("smp/layer/full", "smp/attn/full")},
        "ragged-dot.7": {"scopes": ("smp/layer/window", "smp/moe/experts")},
        "fusion.9": {"scopes": ("smp/layer/full", "smp/moe/shared")},
        "sort.2": {"scopes": ("smp/layer/full", "smp/moe/dispatch")},
        "fusion.20": {"scopes": ("smp/layer/window", "smp/attn/window")},
    }
    ctx = {"trace": trace, "peaks": {"bf16_flops_per_s": 100.0,
                                     "hbm_bytes_per_s": 10.0},
           "moe": {"grouped_flops_in_window": 150.0,
                   "grouped_bytes_in_window": 6.0}}
    return ctx, index


@pytest.mark.parametrize("metric,expected", [
    ("moe.time_share", 100 * (3.0 + 1.5 + 1.0) / 20),
    ("moe.dispatch_time_share", 100 * 1.0 / 20),
    ("flash.window_time_share", 100 * 1.0 / 20),
    ("step.lead_dense_time_share", 100 * 2.0 / 20),
    ("moe.grouped_matmul_roofline", 100 * (150.0 / 100.0) / 3.0),
])
def test_new_reader_on_a_made_up_context(monkeypatch, metric, expected):
    cell = loader.Manifest().cell(lagunatiny.CELL)
    read = cell.metric_reader(metric)
    ctx, index = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(expected)
    # a program from before the scopes: nothing to read, nothing raised
    monkeypatch.setattr(
        scopes, "step_index", lambda: {k: {"phase": "other", "scope": None}
                                       for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_rows_reader_and_drift():
    cell = loader.Manifest().cell(lagunatiny.CELL)
    read = cell.metric_reader("moe.rows_per_step")
    assert read({"moe": {"rows_per_step": 40960.5}}) == 40960.5
    assert read({}) is None and read({"moe": None}) is None
    drift = cell.driver().rows_drift
    assert drift([100] * 9) == 1.0
    assert drift([100, 100, 0, 0, 0, 0, 300, 300]) == 3.0
    assert drift([100]) == 1.0


def test_learning_rate_in_the_state_is_optax_adamw_bit_for_bit():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    driver = loader.Manifest().cell(lagunatiny.CELL).driver()
    params = {"w": jnp.linspace(-1.0, 1.0, 12).reshape(3, 4)}
    grads = {"w": jnp.cos(jnp.arange(12.0)).reshape(3, 4)}
    ours, theirs = driver.adamw_lr_in_state(1e-4), optax.adamw(1e-4)
    state, want_state = ours.init(params), theirs.init(params)
    for _ in range(2):
        got, state = jax.jit(ours.update)(grads, state, params)
        want, want_state = jax.jit(theirs.update)(grads, want_state, params)
        np.testing.assert_array_equal(np.asarray(got["w"]),
                                      np.asarray(want["w"]))
    # the same function at rate 0: moments move on, parameters do not
    held = (*state[:-1], driver.HeldLr(jnp.zeros((), jnp.float32)))
    got, after = jax.jit(ours.update)(grads, held, params)
    assert not np.asarray(got["w"]).any()
    assert int(after[0].count) == 3


def test_load_reader_reads_the_programs_gauges(monkeypatch):
    cell = loader.Manifest().cell(lagunatiny.CELL)
    read = cell.metric_reader("moe.expert_load_max_over_mean")
    scopes = read.__globals__["_scopes"]
    monkeypatch.setattr(scopes, "_series", lambda name: [
        {"value": 1.2, "labels": {"layer": "a#0"}},
        {"value": 1.4, "labels": {"layer": "b#0"}}]
        if name == "smp_moe_expert_load_max_over_mean" else [])
    assert read({}) == pytest.approx(1.3)
    monkeypatch.setattr(scopes, "_series", lambda name: [])
    assert read({}) is None


def test_new_metrics_are_listed_for_the_new_cell(manifest):
    new = {"moe.time_share", "moe.dispatch_time_share",
           "moe.grouped_matmul_roofline", "moe.expert_load_max_over_mean",
           "flash.window_time_share", "step.lead_dense_time_share",
           "moe.rows_per_step"}
    for name in new:
        metric = benchtiny.entry_listing(manifest, name, [lagunatiny.CELL])
        assert metric["moves"] == "train.tokens_per_s_per_chip"
    cell = manifest.cell(lagunatiny.CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps_experts"
    assert cell.traffic["batch"] * cell.traffic["seq"] == 32768
