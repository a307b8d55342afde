"""The LFM2 family's benchmark files: the configuration against the
catalog's row, the family driver end to end at tiny widths on the CPU (the
program against the plain reference through the driver's own functions, the
float8 control failing the same limits), the builder's translation against
the repo's numpy translator, the reference's blocks, the FLOP and byte
counts by hand (the program's own gauge of the convolution's bytes against
the benchmark's count), and each new reader on a made-up op index."""

import json
import os
import types

import pytest

import benchtiny
import lfm2tiny
from benchmark import lfm2_flops, loader

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"conv.time_share": ("model step", "lower"),
               "conv.core_time_share": ("model step", "lower"),
               "conv.core_roofline": ("kernels", "higher")}


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "LFM2-24B-A2B":
                return row
    pytest.fail("no LFM2-24B-A2B row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, lfm2tiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_or_lists_it_as_reduced():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
            assert key in cfg["published"] or key == "layer_types"
        else:
            assert cfg[key] == value, key
    for key, value in cfg["published"].items():
        if key in row["config"]:
            assert value == row["config"][key], key
    # the layers kept are the published list's 0 and 2-5
    published = row["config"]["layer_types"]
    assert cfg["layer_types"] == [published[0]] + published[2:6]


def test_config_keeps_every_width_and_states_its_share():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["conv_L_cache"],
            cfg["num_experts_per_tok"]) == (2048, 64, 11776, 1536, 3, 4)
    assert cfg["num_experts_published"] == 64 and cfg["num_experts"] == 8
    assert cfg["experts_held_first"] == 0
    # an eighth of the heads, the KV heads, the experts and the vocabulary
    assert (cfg["num_attention_heads"] * 8, cfg["num_key_value_heads"] * 8,
            cfg["num_experts"] * 8, cfg["vocab_size"] * 8) == (
                32, 8, 64, 65536)
    assert cfg["layer_types"] == ["conv", "full_attention"] + ["conv"] * 3
    assert cfg["num_dense_layers"] == 1 and cfg["num_hidden_layers"] == 40
    assert set(cfg["reduced"]) == {
        "layer_types", "num_dense_layers", "num_attention_heads",
        "num_key_value_heads", "num_experts", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["reduced"][key]
    for key in ("tie_word_embeddings", "head_dim", "state_dict_names",
                "routing", "expert_bias", "embedding_range", "aux_loss"):
        assert key in cfg["assumed"], key
    for key in ("widths_held_whole", "expert_bias_repeated",
                "embedding_range"):
        assert key in cfg["departures"], key
    # the tied table as every other matrix (the file says what 1.0 read)
    assert cfg["embedding_range"] == cfg["initializer_range"] == 0.02
    assert cfg["tie_word_embeddings"] is True and cfg["use_expert_bias"]
    assert "8 chips" in cfg["deployment"] and cfg["family"] == "lfm2"
    assert "held whole" in cfg["deployment"]
    assert cfg["module"] == {"activation_checkpointing": True}
    assert cfg["smp"]["fused_step_donation"] is True


def test_held_parameters_are_what_the_issue_counted():
    import numpy as np

    from benchmark import lfm2_weights

    spec = lfm2_weights.spec_for(committed())
    by_kind = {}
    for name, (shape, _, _) in spec.items():
        kind = name.split(".")[2] if name.startswith("model.layers.") \
            else "ends"
        by_kind[kind] = by_kind.get(kind, 0) + int(np.prod(shape))
    assert round(by_kind["lead_dense_conv"] / 1e6, 1) == 89.1
    assert round(by_kind["full"] / 1e6, 1) == 76.9
    assert round(by_kind["conv"] / 3e6, 1) == 92.4
    assert round(by_kind["ends"] / 1e6, 2) == 16.78       # the tied table
    total = sum(by_kind.values())
    assert abs(total / 460e6 - 1) < 0.01 and round(total / 1e6, 1) == 460.1
    layer = {name.split(".", 3)[3]: int(np.prod(shape))
             for name, (shape, _, _) in spec.items() if ".conv." in name}
    assert round(sum(v for k, v in layer.items()
                     if k.startswith("conv.")) / 3e6, 2) == 16.78
    assert layer["feed_forward.gate.weight"] == 3 * 2048 * 64
    assert layer["feed_forward.expert_bias"] == 3 * 64
    assert spec["model.embed_tokens.weight"][1:] == ("normal", 0.02)
    assert "lm_head.weight" not in spec
    assert spec["model.layers.conv.feed_forward.expert_bias"][1:] == (
        "normal", 0.02)


# ------------------------------------------------------ counts, by hand

def test_attention_counts_one_full_layer_of_four_heads():
    cfg = committed()
    triangle = 8192 * 8193 // 2
    assert lfm2_flops.train_attention_flops_per_step(cfg, 4, 8192) == \
        3 * 4 * 64 * 4 * triangle * 4
    # six tensors the size of the 4 query heads, six the size of the KV head
    assert lfm2_flops.train_attention_bytes_per_step(cfg, 4, 8192) == \
        2 * 6 * 4 * 8192 * 64 * (4 + 1)
    shapes = lfm2_flops.layer_shapes(cfg)
    assert [s["conv"] for s in shapes] == [True, False, True, True, True]
    assert [s["sparse"] for s in shapes] == [False, True, True, True, True]
    assert [s["heads"] for s in shapes] == [0, 4, 0, 0, 0]


def test_matmul_and_convolution_counts_by_hand():
    cfg = committed()
    D = 2048
    mixers = 4 * 4 * D * D                  # in_proj 3 D^2 + out_proj D^2
    attention = D * 64 * 2 * (4 + 1)
    expected = (D * 8192 + mixers + attention + 3 * D * 11776
                + 4 * D * 64)               # head, lead MLP, four routers
    assert lfm2_flops.dense_matmul_params(cfg) == expected
    assert lfm2_flops.expert_flops_per_row(cfg) == 18 * D * 1536
    rows = 4 * 8 * 2048               # layers x held experts x rows each
    assert rows == 32768 * 4 * 4 * 8 // 64 == 65536
    step = lfm2_flops.train_flops_per_step(cfg, 4, 8192, rows)
    assert step == (6 * expected * 32768 + 18 * D * 1536 * rows
                    + lfm2_flops.train_attention_flops_per_step(
                        cfg, 4, 8192))
    # the issue: about 1.07 GFLOP a token trained
    assert 1.06e9 < step / 32768 < 1.08e9
    assert lfm2_flops.grouped_matmul_bytes(cfg, 100, 2) == 2 * (
        5 * 100 * D + 3 * 8 * 3 * D * 1536 * 2)
    # eleven [tokens, hidden] bf16 tensors a mixer a step
    assert lfm2_flops.conv_core_bytes_per_step(cfg, 4, 8192) == \
        11 * 4 * 32768 * D * 2


def test_programs_gauge_of_the_convolutions_bytes_is_the_benchmarks_count():
    """``smp_conv_core_bytes{pass}`` (one mixer call: a microbatch of a
    layer) times the mixers and the microbatches of a step is
    ``lfm2_flops.conv_core_bytes_per_step``."""
    import jax
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.conv import DistributedShortConv
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = lfm2tiny.config()
    batch, seq, microbatches = 4, 32, 2
    layer = DistributedShortConv(hidden_size=cfg["hidden_size"])
    jax.eval_shape(layer.init, jax.random.key(0), jnp.zeros(
        (batch // microbatches, seq, cfg["hidden_size"]), jnp.bfloat16))
    series = telemetry.report()["metrics"]["smp_conv_core_bytes"]["series"]
    a_call = {s["labels"]["pass"]: s["value"] for s in series}
    assert set(a_call) == {"fwd", "bwd"}
    assert sum(a_call.values()) * 4 * microbatches == \
        lfm2_flops.conv_core_bytes_per_step(cfg, batch, seq)


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lfm2tiny.tiny_root(tmp_path_factory.mktemp("lfm2"))


@pytest.fixture(scope="module")
def sound(root):
    cell, run = benchtiny.cpu_run(root, lfm2tiny.CELL, seed=2 ** 31 + 7,
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
    assert set(rows) == set(lfm2tiny.TINY_LIMITS)
    assert rows["moe_dropped_assignments"]["value"] == 0
    assert rows["weights_moved_in_window"]["value"] == 0.0
    assert said["compared"]["routing_difference"] < 0.05
    by_step = said["compared"]["moe_rows_by_step"]
    assert len(by_step) == outcome["attempted"]
    assert sum(by_step) == said["compared"]["smp_moe_local_assignments"] > 0
    first = said["compared"]["moe_rows_first_checked_step"]
    assert abs(first["program"] - first["reference"]) <= 0.05 * first[
        "reference"]
    assert 3.5 < said["compared"]["reference_losses"][0] < 6  # ln 64 = 4.2


def test_control_fails_the_limits_the_program_passes(sound):
    from benchmark.reference import check

    *_, said = sound
    correct, rows = check.judge(
        said["control"]["numbers"], lfm2tiny.TINY_LIMITS)
    assert correct is False
    failed = {r["number"] for r in rows if not r["ok"]}
    assert failed & {"loss_gap_step1", "first_grad_norm_gap"}


def test_context_counts_the_rows_with_this_familys_flops(sound):
    cell, _, outcome, said = sound
    ctx, cfg = outcome["context"], cell.config
    rows = said["compared"]["smp_moe_local_assignments"]
    assert ctx["moe"]["rows_in_window"] == rows
    assert ctx["moe"]["grouped_flops_in_window"] == \
        lfm2_flops.expert_flops_per_row(cfg) * rows
    assert ctx["flops_per_step"] == pytest.approx(
        lfm2_flops.train_flops_per_step(
            cfg, 4, 32, rows / outcome["attempted"]))
    # 4 routed layers x 4 rows x 32 tokens x 4 a token x 4 of 16 held
    assert 0.5 < rows / outcome["attempted"] / 512 < 2.0
    assert len(said["compared"]["moe_load_max_over_mean"]) == 4


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"]["train.tokens_per_s_per_chip"]["value"] > 0


def test_family_driver_binds_this_familys_files(root):
    from benchmark import lfm2_weights
    from benchmark.reference import lfm2 as reference

    cell = loader.Manifest(root).cell(lfm2tiny.CELL)
    bound = cell.driver().bind(cell.config)
    assert bound.laguna_weights is lfm2_weights
    assert bound.laguna_flops is lfm2_flops
    assert bound.follow_with_reference.args[:2] == (reference, lfm2_weights)


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import lfm2_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import lfm2_moe

    cell = loader.Manifest(root).cell(lfm2tiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    # the same weights as a per-layer Hugging Face state dict
    pattern, _ = lfm2_weights.plan(cfg)
    sd, seen = {}, {}
    experts = "feed_forward.experts."
    for i, kind in enumerate(pattern):
        j = seen.get(kind, 0)
        seen[kind] = j + 1
        prefix = f"model.layers.{kind}."
        for name, value in w.items():
            if not name.startswith(prefix):
                continue
            tail, value = name[len(prefix):], np.asarray(value[j])
            if tail.startswith(experts):
                for e in range(value.shape[0]):
                    sd[f"model.layers.{i}.{experts}"
                       f"{cfg['experts_held_first'] + e}."
                       f"{tail[len(experts):]}"] = value[e]
            else:
                sd[f"model.layers.{i}.{tail}"] = value
    for name in ("model.embed_tokens.weight", "model.embedding_norm.weight"):
        sd[name] = np.asarray(w[name])
    theirs = lfm2_moe.translate_hf_state_dict(sd, lfm2_weights.hf_view(cfg))
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
    gives: the head, the experts, the dense MLP, the sums."""
    import jax
    import numpy as np

    from benchmark import lfm2_weights
    from benchmark.reference import laguna as shared
    from benchmark.reference import lfm2 as reference

    cfg = lfm2tiny.config()
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(9))
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
        scale = float(np.max(np.abs(np.asarray(g_whole[key])))) + 1e-9
        np.testing.assert_allclose(
            np.asarray(g_blocks[key]) / scale,
            np.asarray(g_whole[key]) / scale, atol=2e-4, err_msg=key)


def test_reference_holds_the_selection_bias_through_its_steps():
    import jax
    import numpy as np

    from benchmark import lfm2_weights, weights
    from benchmark.reference import lfm2 as reference

    cfg = lfm2tiny.config()
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(9))
    batches = jax.random.randint(jax.random.key(1), (2, 2, 16), 0, 64)
    _, first_grad, change, loads = reference.follow_steps(
        *reference.hashable(cfg), w, batches, np.uint32(9), 1e-3, "float32",
        2)
    biases = [k for k in change if k.endswith("expert_bias")]
    assert len(biases) == 2
    # a change norm subtracts the leaf as ``weights.make_leaf`` makes it:
    # for the bias that is its constant distance from the repeated values
    spec = lfm2_weights.spec_for(cfg)
    again = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(9))
    for key in biases:
        raw = weights.make_leaf(np.uint32(9), key, *spec[key])
        assert float(change[key]) == pytest.approx(
            float(np.sqrt(np.sum(np.square(again[key] - raw)))), rel=1e-5)
        assert float(first_grad[key]) == 0.0
    assert all(float(v) > 1e-3 for k, v in change.items()
               if k not in biases)
    assert loads.shape == (4, 4)


def test_held_experts_bias_is_repeated_over_every_group():
    import jax
    import numpy as np

    from benchmark import lfm2_weights

    cfg = lfm2tiny.config()                    # experts 4-7 of 16 held
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(5))
    bias = np.asarray(w["model.layers.conv.feed_forward.expert_bias"])
    assert bias.shape == (3, 16) and np.abs(bias).min() > 0
    for group in range(4):
        np.testing.assert_array_equal(bias[:, 4 * group:4 * group + 4],
                                      bias[:, 4:8])
    assert len(np.unique(bias[0])) == 4
    # a count of experts the held ones do not divide: left as seeded
    odd = lfm2_weights.held_bias_everywhere(
        dict(cfg, num_experts=3), w["model.layers.conv.feed_forward."
                                    "expert_bias"])
    np.testing.assert_array_equal(np.asarray(odd), bias)


# ------------------------------------------------------------ the readers

def reader_context():
    seconds = {"fusion.1": 2.0, "smp_flash_fwd.3": 1.0, "fusion.30": 3.0,
               "fusion.31": 1.0, "fusion.32": 0.5, "fusion.33": 0.25,
               "fusion.34": 0.25, "unknown.1": 12.0}
    trace = dict(op_self_s=seconds, busy_s_by_device=[20.0])
    index = {
        "fusion.1": {"phase": "forward", "scope": "smp/layer/conv"},
        "smp_flash_fwd.3": {"scopes": ("smp/layer/full", "smp/attn/full")},
        "fusion.30": {"scopes": ("smp/layer/conv", "smp/conv/in_proj")},
        "fusion.31": {"scopes": ("smp/layer/conv", "smp/conv/out_proj")},
        "fusion.32": {"scopes": ("smp/layer/conv", "smp/conv/core")},
        "fusion.33": {"scopes": ("smp/layer/lead_dense_conv",
                                 "smp/conv/core")},
        # the layer kind's own name is no part of the mixer
        "fusion.34": {"scopes": ("smp/layer/conv",)},
    }
    cell = types.SimpleNamespace(
        config=committed(), traffic={"batch": 4, "seq": 8192})
    ctx = {"trace": trace, "cell": cell, "steps": 10,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    return ctx, index


# 0.75 s of the core over 10 steps against 11 tensors x 4 mixers x 32,768
# tokens x 2,048 channels x 2 bytes over 819 GB/s
ROOFLINE = 100 * (11 * 4 * 32768 * 2048 * 2 / 819e9) / (0.75 / 10)


@pytest.mark.parametrize("metric,expected", [
    ("conv.time_share", 100 * (3.0 + 1.0 + 0.5 + 0.25) / 20),
    ("conv.core_time_share", 100 * 0.75 / 20),
    ("conv.core_roofline", ROOFLINE),
])
def test_new_reader_on_a_made_up_op_index(monkeypatch, metric, expected):
    cell = loader.Manifest().cell(lfm2tiny.CELL)
    read = cell.metric_reader(metric)
    ctx, index = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(expected)
    assert metric != "conv.core_roofline" or 0 < read(ctx) < 100
    # a program from before the scopes (the parent): nothing to read, nothing
    # raised; nor with no index
    monkeypatch.setattr(
        scopes, "step_index", lambda: {k: {"phase": "other", "scope": None}
                                       for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_roofline_needs_the_windows_steps(monkeypatch):
    cell = loader.Manifest().cell(lfm2tiny.CELL)
    read = cell.metric_reader("conv.core_roofline")
    ctx, index = reader_context()
    monkeypatch.setattr(read.__globals__["_moe"]._scopes, "step_index",
                        lambda: index)
    assert read(dict(ctx, steps=0)) is None
    assert read({k: v for k, v in ctx.items() if k != "steps"}) is None


def test_new_entries_are_appended_for_the_new_cell(manifest):
    for name, (layer, better) in NEW_METRICS.items():
        metric = benchtiny.entry_listing(manifest, name, [lfm2tiny.CELL])
        assert metric["moves"] == "train.tokens_per_s_per_chip"
        assert metric["better"] == better and metric["layer"] == layer
        assert metric["unit"] == "%" and metric["source"] == "device_trace"
    cell = manifest.cell(lfm2tiny.CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) | {
        "step.mfu", "step.dispatch_ms", "device.idle_share.train",
        "device.hbm_peak_gb.train"} <= reported
    assert "moe.rows_per_token" not in reported    # reads mlp_layer_types
    assert {m["name"] for m in cell.end_to_end()} == {
        "train.tokens_per_s_per_chip", "setup_s"}
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_steps_expert_family"
    assert cell.traffic["batch"] * cell.traffic["seq"] == 32768
    mellum = manifest.cell("mellum2-12b-a2.5b.train-8k-group-1chip").traffic
    assert {k: v for k, v in cell.traffic.items() if k != "why"} == \
        {k: v for k, v in mellum.items() if k != "why"}
    config = manifest._entry("configs", "lfm2-24b-a2b-5l-ep8")
    assert sorted(config["reduced"]) == sorted(committed()["reduced"])
