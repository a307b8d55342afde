"""The Xing4.0 family's benchmark files: the configuration against the
catalog's row, the family driver end to end at tiny widths on the CPU (the
program against the plain reference through the driver's own functions, the
float8 control failing the same limits), the builder's translation against
the repo's numpy translator, the reference's blocks, the FLOP and byte
counts by hand (the program's own gauge of the hyper-connections' bytes
against the benchmark's count), and each new reader on a made-up op
index."""

import json
import os
import types

import pytest

import benchtiny
import xing4tiny
from benchmark import loader, xing4_flops

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {"mhc.time_share": ("model step", "lower"),
               "mhc.roofline": ("kernels", "higher"),
               "attn.latent_proj_time_share": ("model step", "lower")}
REDUCED = {"layer_types", "first_k_dense_replace", "num_nextn_predict_layers",
           "num_attention_heads", "num_key_value_heads", "n_routed_experts",
           "vocab_size"}


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Xing4.0-29B-A4B":
                return row
    pytest.fail("no Xing4.0-29B-A4B row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, xing4tiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_or_lists_it_as_reduced():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    # the two keys the source does not have, each said to be added
    assert "layer_types" in cfg["reduced"]
    assert "layer_types" not in row["config"]
    assert "mlp_layer_types" in cfg["added_keys"]
    assert "mlp_layer_types" not in row["config"]


def test_config_keeps_every_width_and_states_its_share():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["hc_mult"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (
                3584, 9216, 1024, 768, 512, 128, 64, 128, 4, 4, 1)
    assert cfg["n_routed_experts_published"] == 64
    assert cfg["n_routed_experts"] == 8 and cfg["experts_held_first"] == 0
    # an eighth of the heads, the experts and the vocabulary
    assert (cfg["num_attention_heads"] * 8, cfg["num_key_value_heads"] * 8,
            cfg["n_routed_experts"] * 8, cfg["vocab_size"] * 8) == (
                32, 32, 64, 131072)
    assert cfg["layer_types"] == ["full_attention"] * 5
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["first_k_dense_replace"] == 1
    assert cfg["num_nextn_predict_layers"] == 0
    assert cfg["num_hidden_layers"] == 40
    assert set(cfg["reduced"]) == REDUCED
    for key in cfg["reduced"]:
        assert cfg["reduced"][key]
    for key in ("streams_in_and_out", "mhc", "mhc_seeded_values",
                "state_dict_names", "rotary", "routing",
                "e_score_correction_bias", "aux_loss", "initializer_range"):
        assert key in cfg["assumed"], key
    for key in ("mtp_module", "layer_1", "widths_held_whole",
                "selection_bias_repeated", "routing_epsilon"):
        assert key in cfg["departures"], key
    assert "8 chips" in cfg["deployment"] and cfg["family"] == "xing4"
    assert "held whole" in cfg["deployment"]
    assert cfg["module"] == {"activation_checkpointing": True}
    assert cfg["smp"] == {"microbatches": 8, "bf16": True,
                          "fused_step_donation": True}
    for key in cfg["smp"]:
        assert key == "bf16" or cfg["smp_why"][key]
    assert cfg["module_why"]["activation_checkpointing"]
    assert cfg["scoring_func"] == "sigmoid"
    assert cfg["topk_method"] == "noaux_tc"
    assert (cfg["n_group"], cfg["topk_group"],
            cfg["routed_scaling_factor"]) == (1, 1, 2)


def test_held_parameters_are_what_the_issue_counted():
    import numpy as np

    from benchmark import xing4_weights

    spec = xing4_weights.spec_for(committed())
    by_kind, layer = {}, {}
    for name, (shape, _, _) in spec.items():
        kind = name.split(".")[2] if name.startswith("model.layers.") \
            else "ends"
        by_kind[kind] = by_kind.get(kind, 0) + int(np.prod(shape))
        if kind == "lead_dense":
            layer[name.split(".", 3)[3]] = int(np.prod(shape))
    assert round(by_kind["lead_dense"] / 1e6, 1) == 107.6
    assert round(by_kind["full"] / 4e6, 1) == 107.8
    assert round(by_kind["ends"] / 1e6, 1) == 117.4     # table and head
    total = sum(by_kind.values())
    assert abs(total / 656e6 - 1) < 0.01 and round(total / 1e6, 1) == 656.3
    attention = sum(v for k, v in layer.items()
                    if k.startswith("self_attn.") and "proj" in k)
    assert round(attention / 1e6, 2) == 7.77
    assert layer["self_attn.q_a_proj.weight"] == 3584 * 768
    assert layer["self_attn.kv_a_proj_with_mqa.weight"] == 3584 * 576
    assert layer["self_attn.q_b_proj.weight"] == 768 * 4 * 192
    assert layer["self_attn.kv_b_proj.weight"] == 512 * 4 * 256
    assert layer["self_attn.o_proj.weight"] == 4 * 128 * 3584
    connections = sum(v for k, v in layer.items() if "_hc.phi" in k)
    assert connections == 2 * 14336 * 24
    assert layer["mlp.gate_proj.weight"] * 3 == 3 * 3584 * 9216
    routed = {name.split(".", 3)[3]: shape
              for name, (shape, _, _) in spec.items() if ".full." in name}
    assert routed["mlp.gate.weight"] == (4, 64, 3584)
    assert routed["mlp.gate.e_score_correction_bias"] == (4, 64)
    assert routed["mlp.experts.up_proj.weight"] == (4, 8, 1024, 3584)
    assert routed["mlp.shared_experts.down_proj.weight"] == (4, 3584, 1024)
    assert spec["lm_head.weight"] == ((16384, 3584), "normal", 0.02)


# ------------------------------------------------------ counts, by hand

def test_attention_counts_two_sizes_on_four_heads():
    cfg = committed()
    triangle = 4096 * 4097 // 2
    # a pair: 2 x 192 for the score, 2 x 128 for the value; forward + 2
    assert xing4_flops.train_attention_flops_per_step(cfg, 8, 4096) == \
        3 * 8 * 5 * 2 * (192 + 128) * 4 * triangle
    # six tensors at 192 and six at 128 a head, five layers
    assert xing4_flops.train_attention_bytes_per_step(cfg, 8, 4096) == \
        2 * 5 * 6 * 8 * 4096 * 4 * (192 + 128)
    shapes = xing4_flops.layer_shapes(cfg)
    assert [s["sparse"] for s in shapes] == [False, True, True, True, True]
    assert [s["heads"] for s in shapes] == [4] * 5


def test_matmul_and_connection_counts_by_hand():
    cfg = committed()
    D = 3584
    attention = (D * 768 + 768 * 4 * 192 + D * 576 + 512 * 4 * 256
                 + 4 * 128 * D)
    assert xing4_flops.attention_params(cfg) == attention
    assert xing4_flops.connection_params(cfg) == 4 * D * 24
    expected = (D * 16384 + 5 * (attention + 2 * 4 * D * 24)
                + 3 * D * 9216 + 4 * (D * 64 + 3 * D * 1024))
    assert xing4_flops.dense_matmul_params(cfg) == expected
    assert xing4_flops.expert_flops_per_row(cfg) == 18 * D * 1024
    rows = 4 * 8 * 2048               # layers x held experts x rows each
    assert rows == 32768 * 4 * 4 * 8 // 64 == 65536
    step = xing4_flops.train_flops_per_step(cfg, 8, 4096, rows)
    assert step == (6 * expected * 32768 + 18 * D * 1024 * rows
                    + xing4_flops.train_attention_flops_per_step(
                        cfg, 8, 4096))
    # the issue: about 1.7 GFLOP a token trained
    assert 1.65e9 < step / 32768 < 1.72e9
    assert xing4_flops.grouped_matmul_bytes(cfg, 100, 2) == 2 * (
        5 * 100 * D + 3 * 8 * 3 * D * 1024 * 2)
    # 7 n + 5 = 33 [tokens, hidden] bf16 tensors a sub-layer, ten a step
    assert xing4_flops.mhc_bytes_per_step(cfg, 8, 4096) == \
        33 * 10 * 32768 * D * 2


def test_programs_gauge_of_the_connections_bytes_is_the_benchmarks_count():
    """``smp_mhc_bytes{pass}`` (one sub-layer's call: a microbatch of half
    a layer) times the sub-layers and the microbatches of a step is
    ``xing4_flops.mhc_bytes_per_step``."""
    import jax
    import jax.numpy as jnp

    from smdistributed_modelparallel_tpu.nn.hyper_connection import (
        DistributedHyperConnection,
    )
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = xing4tiny.config()
    batch, seq, microbatches = 4, 32, 2
    layer = DistributedHyperConnection(
        streams=cfg["hc_mult"], hidden_size=cfg["hidden_size"])
    jax.eval_shape(layer.init, jax.random.key(0), jnp.zeros(
        (batch // microbatches, seq, cfg["hc_mult"], cfg["hidden_size"]),
        jnp.bfloat16))
    series = telemetry.report()["metrics"]["smp_mhc_bytes"]["series"]
    a_call = {s["labels"]["pass"]: s["value"] for s in series}
    assert set(a_call) == {"fwd", "bwd"}
    assert sum(a_call.values()) * 2 * 5 * microbatches == \
        xing4_flops.mhc_bytes_per_step(cfg, batch, seq)


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return xing4tiny.tiny_root(tmp_path_factory.mktemp("xing4"))


@pytest.fixture(scope="module")
def sound(root):
    cell, run = benchtiny.cpu_run(root, xing4tiny.CELL, seed=2 ** 31 + 7,
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
    assert set(rows) == set(xing4tiny.TINY_LIMITS)
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
        said["control"]["numbers"], xing4tiny.TINY_LIMITS)
    assert correct is False
    failed = {r["number"] for r in rows if not r["ok"]}
    assert failed & {"loss_gap_step1", "first_grad_norm_gap"}


def test_context_counts_the_rows_with_this_familys_flops(sound):
    cell, _, outcome, said = sound
    ctx, cfg = outcome["context"], cell.config
    rows = said["compared"]["smp_moe_local_assignments"]
    assert ctx["moe"]["rows_in_window"] == rows
    assert ctx["moe"]["grouped_flops_in_window"] == \
        xing4_flops.expert_flops_per_row(cfg) * rows
    assert ctx["flops_per_step"] == pytest.approx(
        xing4_flops.train_flops_per_step(
            cfg, 4, 32, rows / outcome["attempted"]))
    assert ctx["attention_bytes_per_step"] == \
        xing4_flops.train_attention_bytes_per_step(cfg, 4, 32)
    # 4 routed layers x 4 rows x 32 tokens x 4 a token x 4 of 16 held
    assert 0.5 < rows / outcome["attempted"] / 512 < 2.0
    assert len(said["compared"]["moe_load_max_over_mean"]) == 4
    # what ``moe.rows_per_token`` divides by
    read = cell.metric_reader("moe.rows_per_token")
    assert read({"moe": ctx["moe"], "cell": cell,
                 "tokens_per_step": 128}) == pytest.approx(
        ctx["moe"]["rows_per_step"] / 128 / 4)


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"]["train.tokens_per_s_per_chip"]["value"] > 0


def test_family_driver_binds_this_familys_files(root):
    from benchmark import xing4_weights
    from benchmark.reference import xing4 as reference

    cell = loader.Manifest(root).cell(xing4tiny.CELL)
    bound = cell.driver().bind(cell.config)
    assert bound.laguna_weights is xing4_weights
    assert bound.laguna_flops is xing4_flops
    assert bound.follow_with_reference.args[:2] == (reference, xing4_weights)


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import xing4_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import xing4

    cell = loader.Manifest(root).cell(xing4tiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    # the same weights as a per-layer Hugging Face state dict
    pattern, _ = xing4_weights.plan(cfg)
    sd, seen = {}, {}
    experts = "mlp.experts."
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
    for name in ("model.embed_tokens.weight", "model.norm.weight",
                 "lm_head.weight"):
        sd[name] = np.asarray(w[name])
    theirs = xing4.translate_hf_state_dict(sd, xing4_weights.hf_view(cfg))
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

    from benchmark import xing4_weights
    from benchmark.reference import xing4 as reference

    cfg = xing4tiny.config()
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(9))
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
        scale = float(np.max(np.abs(np.asarray(g_whole[key])))) + 1e-9
        np.testing.assert_allclose(
            np.asarray(g_blocks[key]) / scale,
            np.asarray(g_whole[key]) / scale, atol=2e-4, err_msg=key)


def test_reference_holds_the_selection_bias_through_its_steps():
    import jax
    import numpy as np

    from benchmark import weights, xing4_weights
    from benchmark.reference import xing4 as reference

    cfg = xing4tiny.config()
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(9))
    batches = jax.random.randint(jax.random.key(1), (2, 2, 16), 0, 64)
    _, first_grad, change, loads = reference.follow_steps(
        *reference.hashable(cfg), w, batches, np.uint32(9), 1e-3, "float32",
        2)
    biases = [k for k in change if k.endswith("e_score_correction_bias")]
    assert len(biases) == 1
    # a change norm subtracts the leaf as ``weights.make_leaf`` makes it:
    # for the bias that is its constant distance from the repeated values
    spec = xing4_weights.spec_for(cfg)
    again = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(9))
    for key in biases:
        raw = weights.make_leaf(np.uint32(9), key, *spec[key])
        assert float(change[key]) == pytest.approx(
            float(np.sqrt(np.sum(np.square(again[key] - raw)))), rel=1e-5)
        assert float(first_grad[key]) == 0.0
    assert all(float(v) > 1e-3 for k, v in change.items()
               if k not in biases)
    assert all(float(v) > 0 for k, v in first_grad.items()
               if k not in biases)
    assert loads.shape == (4, 4)


def test_seeded_leaves_that_stand_on_a_constant():
    import jax
    import numpy as np

    from benchmark import xing4_weights

    cfg = xing4tiny.config()                   # experts 4-7 of 16 held
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(5))
    bias = np.asarray(w["model.layers.full.mlp.gate.e_score_correction_bias"])
    assert bias.shape == (4, 16) and np.abs(bias).min() > 0
    for group in range(4):
        np.testing.assert_array_equal(bias[:, 4 * group:4 * group + 4],
                                      bias[:, 4:8])
    assert len(np.unique(bias[0])) == 4
    for site in ("attn_hc", "ffn_hc"):
        alpha = np.asarray(w[f"model.layers.full.{site}.alpha"])
        assert alpha.shape == (4, 3)
        assert np.abs(alpha - 0.01).max() < 0.01 and alpha.std() > 0
        b = np.asarray(w[f"model.layers.full.{site}.bias"])
        offset = xing4_weights.connection_bias_offset(4)
        assert b.shape == (4, 24) and np.abs(b - offset).max() < 0.1
        np.testing.assert_allclose(offset[:4], -np.log(3), rtol=1e-6)
        assert offset[4:8].tolist() == [0] * 4
        assert offset[8:].reshape(4, 4).tolist() == (6 * np.eye(4)).tolist()


# ------------------------------------------------------------ the readers

def reader_context():
    seconds = {"fusion.1": 2.0, "smp_flash_fwd.3": 1.0, "fusion.30": 3.0,
               "fusion.31": 1.0, "fusion.32": 0.5, "fusion.33": 0.25,
               "fusion.34": 0.25, "fusion.40": 0.5, "fusion.41": 0.25,
               "fusion.42": 0.125, "fusion.43": 0.125, "fusion.44": 0.5,
               "fusion.45": 0.5, "unknown.1": 10.0}
    trace = dict(op_self_s=seconds, busy_s_by_device=[20.0])
    lead, full = "smp/layer/lead_dense", "smp/layer/full"
    index = {
        "fusion.1": {"phase": "forward", "scope": full},
        "smp_flash_fwd.3": {"scopes": (full, "smp/attn/full",
                                       "smp/attn/core")},
        "fusion.30": {"scopes": (full, "smp/mhc/post_res")},
        "fusion.31": {"scopes": (full, "smp/mhc/pre")},
        "fusion.32": {"scopes": (full, "smp/mhc/coeff")},
        "fusion.33": {"scopes": (lead, "smp/mhc/sinkhorn")},
        # the layer kind's own name is no part of the connection
        "fusion.34": {"scopes": (full,)},
        "fusion.40": {"scopes": (full, "smp/attn/full",
                                 "smp/latent/q_down")},
        "fusion.41": {"scopes": (full, "smp/attn/full",
                                 "smp/latent/kv_up")},
        "fusion.42": {"scopes": (lead, "smp/attn/full",
                                 "smp/latent/rope")},
        "fusion.43": {"scopes": (lead, "smp/attn/full",
                                 "smp/latent/q_up")},
        # every attention has an output projection: not a latent one's own
        "fusion.44": {"scopes": (full, "smp/attn/full",
                                 "smp/latent/out")},
        "fusion.45": {"scopes": (full, "smp/attn/full",
                                 "smp/latent/kv_down")},
    }
    cell = types.SimpleNamespace(
        config=committed(), traffic={"batch": 8, "seq": 4096})
    ctx = {"trace": trace, "cell": cell, "steps": 10,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    return ctx, index


# 4.75 s of the connections over 10 steps against 33 tensors x 10
# sub-layers x 32,768 tokens x 3,584 x 2 bytes over 819 GB/s
ROOFLINE = 100 * (33 * 10 * 32768 * 3584 * 2 / 819e9) / (4.75 / 10)


@pytest.mark.parametrize("metric,expected", [
    ("mhc.time_share", 100 * (3.0 + 1.0 + 0.5 + 0.25) / 20),
    ("mhc.roofline", ROOFLINE),
    ("attn.latent_proj_time_share",
     100 * (0.5 + 0.25 + 0.125 + 0.125 + 0.5) / 20),
])
def test_new_reader_on_a_made_up_op_index(monkeypatch, metric, expected):
    cell = loader.Manifest().cell(xing4tiny.CELL)
    read = cell.metric_reader(metric)
    ctx, index = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(expected)
    assert metric != "mhc.roofline" or 0 < read(ctx) < 100
    # a program from before the scopes (the parent): nothing to read, nothing
    # raised; nor with no index
    monkeypatch.setattr(
        scopes, "step_index", lambda: {k: {"phase": "other", "scope": None}
                                       for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_roofline_needs_the_windows_steps(monkeypatch):
    cell = loader.Manifest().cell(xing4tiny.CELL)
    read = cell.metric_reader("mhc.roofline")
    ctx, index = reader_context()
    monkeypatch.setattr(read.__globals__["_moe"]._scopes, "step_index",
                        lambda: index)
    assert read(dict(ctx, steps=0)) is None
    assert read({k: v for k, v in ctx.items() if k != "steps"}) is None


def test_new_entries_are_appended_for_the_new_cell(manifest):
    for name, (layer, better) in NEW_METRICS.items():
        metric = benchtiny.entry_listing(manifest, name, [xing4tiny.CELL])
        assert metric["moves"] == "train.tokens_per_s_per_chip"
        assert metric["better"] == better and metric["layer"] == layer
        assert metric["unit"] == "%" and metric["source"] == "device_trace"
    cell = manifest.cell(xing4tiny.CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) | {
        "step.mfu", "step.dispatch_ms", "device.idle_share.train",
        "device.hbm_peak_gb.train", "flash_roofline", "flash.time_share",
        "moe.row_time_us", "moe.rows_per_token", "moe.rows_per_step",
        "step.lead_dense_time_share", "mlp.time_share", "attn.time_share",
        "head.time_share", "step.unscoped_time_share"} <= reported
    assert not reported & {
        "conv.time_share", "attn.qk_norm_time_share",
        "flash.window_time_share", "pipeline.glue_time_share"}
    assert {m["name"] for m in cell.end_to_end()} == {
        "train.tokens_per_s_per_chip", "setup_s"}
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_steps_expert_family"
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (8, 4096)
    lfm2 = manifest.cell("lfm2-24b-a2b.train-8k-group8-1chip").traffic
    same = ("kind", "lr", "batch_pool", "check_steps", "in_flight",
            "token_law")
    assert {k: cell.traffic[k] for k in same} == {k: lfm2[k] for k in same}
    assert cell.traffic["batch"] * cell.traffic["seq"] == \
        lfm2["batch"] * lfm2["seq"]
    config = manifest._entry("configs", "xing4.0-29b-a4b-5l-ep8")
    assert sorted(config["reduced"]) == sorted(committed()["reduced"])
    limits = cell.manifest.dir + "/limits/" + cell.name + ".json"
    with open(limits) as f:
        assert set(json.load(f)["limits"]) == set(xing4tiny.TINY_LIMITS)
