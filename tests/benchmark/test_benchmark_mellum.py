"""The Mellum family's benchmark files: the configuration against the
catalog's row, the family driver end to end at tiny widths on the CPU (the
program against the plain reference through the driver's own functions, the
float8 control failing the same limits), the builder's translation against
the repo's numpy translator, the reference's blocks, the FLOP and byte
counts by hand, and each new reader on a made-up context."""

import json
import os
import types

import pytest

import benchtiny
import mellumtiny
from benchmark import loader, mellum_flops

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"moe.experts_time_share": "expert layers",
               "moe.row_time_us": "expert layers",
               "moe.rows_per_token": "expert layers",
               "attn.qk_norm_time_share": "model step"}


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Mellum2-12B-A2.5B-Instruct":
                return row
    pytest.fail("no Mellum2-12B-A2.5B-Instruct row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, mellumtiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_or_lists_it_as_reduced():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
            assert key in cfg["published"] or key.endswith("layer_types")
        else:
            assert cfg[key] == value, key
    for key, value in cfg["published"].items():
        if key in row["config"]:
            assert value == row["config"][key], key


def test_config_keeps_every_width_and_states_its_share():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["sliding_window"],
            cfg["num_experts_per_tok"]) == (2304, 128, 7168, 896, 1024, 8)
    assert cfg["num_experts_published"] == 64 and cfg["num_experts"] == 16
    assert cfg["experts_held_first"] == 0
    # a quarter of the heads, the KV heads, the experts and the vocabulary
    assert (cfg["num_attention_heads"] * 4, cfg["num_key_value_heads"] * 4,
            cfg["num_experts"] * 4, cfg["vocab_size"] * 4) == (
                32, 4, 64, 98304)
    assert cfg["layer_types"] == ["sliding_attention"] * 3 + \
        ["full_attention"]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_hidden_layers"] == 28
    for key in cfg["reduced"]:
        assert cfg["reduced"][key]
    assert "qk_norm" in cfg["assumed"] and "mtp_head" in cfg["departures"]
    # token vectors of unit rms (mellum_weights.py says why); the rest 0.02
    assert cfg["embedding_range"] == 1.0 and cfg["initializer_range"] == 0.02
    assert "embedding_range" in cfg["assumed"]
    assert "4 chips" in cfg["deployment"] and cfg["family"] == "mellum"


def test_held_parameters_are_what_the_issue_counted():
    import numpy as np

    from benchmark import mellum_weights

    spec = mellum_weights.spec_for(committed())
    by_kind = {}
    for name, (shape, _, _) in spec.items():
        kind = name.split(".")[2] if name.startswith("model.layers.") \
            else "ends"
        by_kind[kind] = by_kind.get(kind, 0) + int(np.prod(shape))
    assert by_kind["window"] == 3 * by_kind["full"]
    assert round(by_kind["full"] / 1e6, 1) == 104.6
    assert round(by_kind["ends"] / 1e6, 1) == 113.2
    assert round(sum(by_kind.values()) / 1e6, 1) == 531.5
    layer = {name.split(".", 3)[3]: int(np.prod(shape))
             for name, (shape, _, _) in spec.items() if ".full." in name}
    assert round(sum(v for k, v in layer.items()
                     if k.startswith("self_attn.")) / 1e6, 2) == 5.31
    assert round(layer["mlp.gate.weight"] / 1e6, 2) == 0.15
    assert spec["model.embed_tokens.weight"][1:] == ("normal", 1.0)
    assert spec["lm_head.weight"][1:] == ("normal", 0.02)
    assert round(sum(v for k, v in layer.items()
                     if k.startswith("mlp.experts.")) / 16e6, 2) == 6.19


# ------------------------------------------------------ counts, by hand

def test_attention_counts_the_band_and_one_kv_head():
    cfg = committed()
    full, band = 8192 * 8193 // 2, 1024 * 1025 // 2 + 7168 * 1024
    forward = 4 * 128 * 8 * (full + 3 * band)
    assert mellum_flops.train_attention_flops_per_step(cfg, 4, 8192) == \
        3 * forward * 4
    # six tensors the size of the 8 query heads, six the size of the KV head
    assert mellum_flops.train_attention_bytes_per_step(cfg, 4, 8192) == \
        2 * 6 * 4 * 8192 * 128 * (8 + 1) * 4
    assert [s["window"] for s in mellum_flops.layer_shapes(cfg)] == \
        [1024, 1024, 1024, None]
    assert all(s["sparse"] for s in mellum_flops.layer_shapes(cfg))


def test_matmul_counts_by_hand():
    cfg = committed()
    D, hd = 2304, 128
    layer = D * hd * (2 * 8 + 2 * 1) + D * 64    # q, o, k, v; the router
    expected = D * 24576 + 4 * layer
    assert mellum_flops.dense_matmul_params(cfg) == expected
    assert mellum_flops.expert_flops_per_row(cfg) == 18 * D * 896
    rows = 4 * 16 * 4096              # layers x held experts x rows each
    assert rows == 32768 * 8 * 4 // 4 == 262144
    step = mellum_flops.train_flops_per_step(cfg, 4, 8192, rows)
    assert step == (6 * expected * 32768 + 18 * D * 896 * rows
                    + mellum_flops.train_attention_flops_per_step(
                        cfg, 4, 8192))
    # the issue: 37.2 MFLOP a row; about 0.85 GFLOP a token trained
    assert round(18 * D * 896 / 1e6, 1) == 37.2
    assert 0.8e9 < step / 32768 < 0.9e9
    assert mellum_flops.grouped_matmul_bytes(cfg, 100, 2) == 2 * (
        5 * 100 * D + 3 * 16 * 3 * D * 896 * 2)


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return mellumtiny.tiny_root(tmp_path_factory.mktemp("mellum"))


@pytest.fixture(scope="module")
def sound(root):
    cell, run = benchtiny.cpu_run(root, mellumtiny.CELL, seed=2 ** 31 + 7,
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


def test_program_follows_the_reference_through_the_family_driver(sound):
    _, run, outcome, said = sound
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    rows = {r["number"]: r for r in said["compared"]["rows"]}
    assert set(rows) == set(mellumtiny.TINY_LIMITS)
    assert rows["moe_dropped_assignments"]["value"] == 0
    assert rows["weights_moved_in_window"]["value"] == 0.0
    assert said["compared"]["routing_difference"] < 0.05
    by_step = said["compared"]["moe_rows_by_step"]
    assert len(by_step) == outcome["attempted"]
    assert sum(by_step) == said["compared"]["smp_moe_local_assignments"] > 0
    first = said["compared"]["moe_rows_first_checked_step"]
    assert abs(first["program"] - first["reference"]) <= 0.05 * first[
        "reference"]


def test_control_fails_the_limits_the_program_passes(sound):
    from benchmark.reference import check

    *_, said = sound
    correct, rows = check.judge(
        said["control"]["numbers"], mellumtiny.TINY_LIMITS)
    assert correct is False
    failed = {r["number"] for r in rows if not r["ok"]}
    assert failed & {"loss_gap_step1", "first_grad_norm_gap"}


def test_context_counts_the_rows_with_this_familys_flops(sound):
    cell, _, outcome, said = sound
    ctx, cfg = outcome["context"], cell.config
    rows = said["compared"]["smp_moe_local_assignments"]
    assert ctx["moe"]["rows_in_window"] == rows
    assert ctx["moe"]["grouped_flops_in_window"] == \
        mellum_flops.expert_flops_per_row(cfg) * rows
    assert ctx["flops_per_step"] == pytest.approx(
        mellum_flops.train_flops_per_step(
            cfg, 4, 32, rows / outcome["attempted"]))
    # 4 expert layers x 4 rows x 32 tokens x 4 a token x 4 of 16 held
    assert 0.5 < rows / outcome["attempted"] / 512 < 2.0
    read = cell.metric_reader("moe.rows_per_token")
    assert read(dict(ctx, cell=cell)) == pytest.approx(
        rows / outcome["attempted"] / 128 / 4)


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"]["train.tokens_per_s_per_chip"]["value"] > 0


def test_family_driver_binds_its_own_copy_of_the_experts_driver(
        root, monkeypatch):
    """The configuration's ``family`` key picks the weights, FLOPs and
    reference; Laguna's cell keeps Laguna's."""
    from benchmark import laguna_weights, mellum_weights
    from benchmark.reference import mellum as reference

    manifest = loader.Manifest(root)
    cell = manifest.cell(mellumtiny.CELL)
    bound = cell.driver().bind({"family": "mellum"})
    assert bound.laguna_weights is mellum_weights
    assert bound.laguna_flops is mellum_flops
    laguna = manifest.cell("laguna-s-2.1.train-8k-1chip").driver()
    assert laguna is not bound and laguna.laguna_weights is laguna_weights
    called = []
    monkeypatch.setattr(
        reference, "follow_steps",
        lambda *args: called.append(args) or ([], {}, {}, []))
    assert bound.follow_with_reference(cell.config, cell.traffic, 5, 1) == {
        "losses": [], "first_grad": {}, "change": {}, "first_loads": []}
    assert called[0][-2:] == ("float32", 1)
    with pytest.raises(ModuleNotFoundError):
        cell.driver().bind({"family": "no_such_family"})


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import mellum_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import mellum

    cell = loader.Manifest(root).cell(mellumtiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: mellum_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    # the same weights as a per-layer Hugging Face state dict
    pattern, _ = mellum_weights.plan(cfg)
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
    theirs = mellum.translate_hf_state_dict(sd, mellum_weights.hf_view(cfg))
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

    from benchmark import mellum_weights
    from benchmark.reference import laguna as shared
    from benchmark.reference import mellum as reference

    cfg = mellumtiny.config()
    w = jax.jit(lambda s: mellum_weights.make_weights(cfg, s))(np.uint32(9))
    ids = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)

    def loss_and_grad():
        return jax.value_and_grad(
            lambda w: reference.next_token_loss_sum(
                cfg, w, ids, "float32")[0])(w)

    whole, g_whole = loss_and_grad()
    monkeypatch.setattr(shared, "QUERY_BLOCK", 4)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 8)
    blocks, g_blocks = loss_and_grad()
    np.testing.assert_allclose(float(blocks), float(whole), rtol=1e-5)
    for key in g_whole:
        np.testing.assert_allclose(
            np.asarray(g_blocks[key]), np.asarray(g_whole[key]),
            rtol=2e-3, atol=2e-5)


# ------------------------------------------------------------ the readers

def reader_context():
    seconds = {"fusion.1": 2.0, "smp_flash_fwd.3": 1.0, "convert_add.7": 3.0,
               "fusion.8": 1.0, "sort.2": 1.0, "fusion.20": 0.5,
               "fusion.21": 0.25, "unknown.1": 7.5,
               "ragged-dot-none.11": 2.0, "ragged-dot-none.14": 0.5}
    trace = dict(op_self_s=seconds, busy_s_by_device=[20.0])
    index = {
        "fusion.1": {"phase": "forward", "scope": "smp/layer/window"},
        "smp_flash_fwd.3": {"scopes": ("smp/layer/window", "smp/attn/window")},
        "convert_add.7": {"scopes": ("smp/layer/window", "smp/moe/experts")},
        # the compiler's own kernel for a grouped product: a phase, no scope
        "ragged-dot-none.11": {"phase": "backward", "scope": None},
        "fusion.8": {"scopes": ("smp/layer/full", "smp/moe/experts")},
        "sort.2": {"scopes": ("smp/layer/full", "smp/moe/dispatch")},
        "fusion.20": {"scopes": ("smp/layer/window", "smp/attn/window",
                                 "smp/attn/qk_norm")},
        "fusion.21": {"scopes": ("smp/layer/full", "smp/attn/full",
                                 "smp/attn/qk_norm")},
    }
    cell = types.SimpleNamespace(config=committed())
    ctx = {"trace": trace, "cell": cell, "tokens_per_step": 1000,
           "moe": {"rows_in_window": 2_000_000, "rows_per_step": 8000.0}}
    return ctx, index


def scopes_module_of(read):
    """The ``_scopes`` module in which a reader's arithmetic looks up the
    step's op index."""
    shared = read.__globals__
    return (shared["_experts"]._moe if "_experts" in shared
            else shared["_moe"])._scopes


@pytest.mark.parametrize("metric,expected", [
    ("moe.experts_time_share", 100 * (3.0 + 1.0 + 2.0 + 0.5) / 20),
    ("moe.row_time_us", 1e6 * 6.5 / 2_000_000),
    ("attn.qk_norm_time_share", 100 * 0.75 / 20),
])
def test_new_trace_reader_on_a_made_up_context(monkeypatch, metric, expected):
    cell = loader.Manifest().cell(mellumtiny.CELL)
    read = cell.metric_reader(metric)
    ctx, index = reader_context()
    scopes = scopes_module_of(read)
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(expected)
    # a program from before the scopes (the parent): nothing to read, nothing
    # raised; nor with no index, nor with no count of rows
    monkeypatch.setattr(
        scopes, "step_index", lambda: {k: {"phase": "other", "scope": None}
                                       for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_row_time_needs_the_programs_count_of_rows(monkeypatch):
    cell = loader.Manifest().cell(mellumtiny.CELL)
    read = cell.metric_reader("moe.row_time_us")
    ctx, index = reader_context()
    monkeypatch.setattr(scopes_module_of(read), "step_index", lambda: index)
    assert read(dict(ctx, moe=None)) is None
    assert read({k: v for k, v in ctx.items() if k != "moe"}) is None
    assert read(dict(ctx, moe={"rows_in_window": 0})) is None


def test_rows_per_token_reader():
    cell = loader.Manifest().cell(mellumtiny.CELL)
    read = cell.metric_reader("moe.rows_per_token")
    ctx, _ = reader_context()
    assert read(ctx) == pytest.approx(8000.0 / 1000 / 4)
    assert read({}) is None and read({"moe": None}) is None
    assert read({"moe": {"rows_per_step": 0}}) is None


def test_new_metrics_are_listed_for_the_new_cell(manifest):
    for name, layer in NEW_METRICS.items():
        metric = benchtiny.entry_listing(manifest, name, [mellumtiny.CELL])
        assert metric["moves"] == "train.tokens_per_s_per_chip"
        assert metric["better"] == "lower" and metric["layer"] == layer
    assert manifest._entry("per_layer", "moe.row_time_us")["unit"] == "us"
    cell = manifest.cell(mellumtiny.CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) | {"flash.time_share", "flash_roofline",
                               "step.mfu", "device.idle_share.train",
                               "device.hbm_peak_gb.train"} <= reported
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_steps_expert_family"
    assert cell.traffic["batch"] * cell.traffic["seq"] == 32768
    laguna = manifest.cell("laguna-s-2.1.train-8k-1chip").traffic
    assert {k: v for k, v in cell.traffic.items()
            if k not in ("kind", "why")} == \
        {k: v for k, v in laguna.items() if k not in ("kind", "why")}
