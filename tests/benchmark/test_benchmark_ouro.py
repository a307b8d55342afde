"""The Ouro family's benchmark files: the configuration against the
catalog's row, the looped driver end to end at tiny widths on the CPU (the
program against the plain reference through the driver's own functions, the
float8 control failing the same limits, a program with fewer passes, no
gate or no entropy term failing this family's own numbers), the builder's
translation against the repo's numpy translator, the reference's blocks,
the FLOP and byte counts by hand, each new reader on a made-up op index,
and the new entries last in their lists."""

import json
import os
import types

import pytest

import benchtiny
import ourotiny
from benchmark import loader, ouro_flops

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
DEPTH = ourotiny.DEPTH
# By hand, at the 7 layers kept: parameters (millions), fp32 parameters,
# gradients and AdamW moments (GB), required work a step of 16,384 tokens
# (TFLOP) and a token forward (GFLOP), the four heads' share of the work.
BY_HAND = {"parameters_M": 561.0, "state_GB": 8.98, "step_TFLOP": 227.2,
           "forward_GFLOP_a_token": 4.62, "heads_share": 0.174}
RATE = "train.tokens_per_s_per_chip"
NEW_METRICS = {
    "loop.layer_passes_per_step": ("layers/step", "higher",
                                   "program_counter"),
    "loop.exit_gate_time_share": ("%", "lower", "device_trace"),
    "loop.branch_norm_time_share": ("%", "lower", "device_trace"),
    "loop.carry_time_share": ("%", "lower", "device_trace"),
}
LISTED_FOR_THE_CELL = {
    "flash.time_share", "flash_roofline", "head.time_share",
    "attn.time_share", "mlp.time_share", "optimizer.time_share",
    "step.forward_time_share", "step.backward_time_share",
    "step.recompute_time_share", "step.accumulate_time_share",
    "step.unattributed_time_share", "step.unscoped_time_share",
    "step.user_code_time_share", "step.host_outside_dispatch_ms"}


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Ouro-2.6B":
                return row
    pytest.fail("no Ouro-2.6B row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, ourotiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_and_reduces_the_depth_alone():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["reduced"]) == {"layer_types"}
    assert row["config"]["layer_types"] == ["full_attention"] * 48
    assert cfg["layer_types"] == ["full_attention"] * DEPTH
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"] \
        == 48


def test_config_keeps_every_width_and_states_what_it_assumed():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["total_ut_steps"], cfg["rope_theta"],
            cfg["tie_word_embeddings"], cfg["rms_norm_eps"]) == (
                2048, 16, 16, 128, 5632, 49152, 4, 1000000, False, 1e-6)
    for key in ("sandwich_placement", "norm_after_every_pass", "exit_gate",
                "loss", "rotary", "attention_bias", "initializer_range",
                "dropout"):
        assert cfg["assumed"][key], key
    for key in ("depth_cut_and_the_loop", "stage_II"):
        assert cfg["departures"][key], key
    assert "arXiv:2510.25741" in cfg["assumed"]["sandwich_placement"]
    assert cfg["exit_entropy_weight"] == 0.05
    assert cfg["exit_gate_bias"] == -1.0 and cfg["initializer_range"] == 0.02
    assert set(cfg["added_keys"]) == {
        "published, initializer_range, exit_entropy_weight, exit_gate_bias"}
    assert "pipeline" in cfg["deployment"] and "16 bytes" in cfg["deployment"]
    assert cfg["builder"] == "ouro_looped"
    assert cfg["smp"] == {"microbatches": 2, "bf16": True,
                          "fused_step_donation": True}
    assert cfg["module"] == {"activation_checkpointing": True,
                             "loop_head_positions": 2048}
    assert all(cfg["smp_why"][k] for k in cfg["smp"])
    assert all(cfg["module_why"][k] for k in cfg["module"])


def test_held_parameters_by_hand():
    import numpy as np

    from benchmark import ouro_weights

    cfg = committed()
    spec = ouro_weights.spec_for(cfg)
    count = lambda keep: sum(                                # noqa: E731
        int(np.prod(shape)) for name, (shape, _, _) in spec.items()
        if keep(name))
    layers = count(lambda n: n.startswith("model.layers."))
    attention = count(lambda n: ".self_attn." in n)
    mlp = count(lambda n: ".mlp." in n)
    assert attention == DEPTH * 4 * 2048 * 2048
    assert mlp == DEPTH * 3 * 2048 * 5632
    assert round(attention / DEPTH / 1e6, 2) == 16.78
    assert round(mlp / DEPTH / 1e6, 2) == 34.6
    assert layers == attention + mlp + DEPTH * 4 * 2048
    ends = count(lambda n: not n.startswith("model.layers."))
    assert ends == 2 * 49152 * 2048 + 2048 + 2048 + 1
    total = ouro_weights.parameters(cfg)
    assert total == layers + ends
    assert round(total / 1e6, 1) == BY_HAND["parameters_M"]
    assert round(total * 16 / 1e9, 2) == BY_HAND["state_GB"]
    assert spec["model.early_exit_gate.weight"][0] == (1, 2048)
    assert spec["model.early_exit_gate.bias"] == (
        (1,), ("gate_bias", -1.0), 0.02)
    # ISSUE 49's count, at the 8 layers it asked for
    eight = dict(cfg, layer_types=["full_attention"] * 8)
    assert round(ouro_weights.parameters(eight) / 1e6, 1) == 612.4
    assert round(ouro_weights.parameters(eight) * 16 / 1e9, 2) == 9.80


def test_gate_bias_stands_on_its_constant_and_every_leaf_is_seeded():
    import jax
    import numpy as np

    from benchmark import ouro_weights

    cfg = ourotiny.config()
    make = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))
    w, other = make(np.uint32(5)), make(np.uint32(6))
    bias = float(w["model.early_exit_gate.bias"][0])
    assert abs(bias + 1.0) < 0.1 and bias != -1.0
    spec = ouro_weights.spec_for(cfg)
    for name, leaf in w.items():
        assert leaf.shape == spec[name][0], name
        assert float(np.max(np.abs(leaf - other[name]))) > 0, name
        again = ouro_weights.make_leaf(np.uint32(5), name, *spec[name])
        np.testing.assert_allclose(np.asarray(again), np.asarray(leaf),
                                   rtol=1e-6, atol=1e-8)
    scale = np.asarray(w["model.layers.input_layernorm_2.weight"])
    assert scale.shape == (2, 32) and abs(scale.mean() - 1) < 0.02


# ------------------------------------------------------ counts, by hand

def test_flops_count_four_passes_of_layers_attention_and_heads():
    cfg = committed()
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert ouro_flops.layer_matmul_params(cfg) == layer == 51380224
    a_pass = DEPTH * layer + 2048 * 49152 + 2048
    assert ouro_flops.pass_matmul_params(cfg) == a_pass
    triangle = 8192 * 8193 // 2
    attention = 4 * DEPTH * 4 * 2048 * triangle
    assert ouro_flops.attention_forward_flops(cfg, 8192) == attention
    assert ouro_flops.train_attention_flops_per_step(cfg, 2, 8192) == \
        3 * 2 * attention
    step = ouro_flops.train_flops_per_step(cfg, 2, 8192)
    assert step == 6 * 4 * a_pass * 16384 + 3 * 2 * attention
    assert round(step / 1e12, 1) == BY_HAND["step_TFLOP"]
    assert round(ouro_flops.forward_flops_per_token(cfg, 8192) / 1e9, 2) \
        == BY_HAND["forward_GFLOP_a_token"]
    assert ouro_flops.train_attention_bytes_per_step(cfg, 2, 8192) == \
        4 * DEPTH * 12 * 2 * 8192 * 2048 * 2
    assert ouro_flops.layer_passes_per_step(cfg, 2) == 4 * DEPTH * 2
    # the four heads' share of the required work
    heads = 6 * 4 * 2048 * 49152 * 16384
    assert round(heads / step, 3) == BY_HAND["heads_share"]


def test_flops_at_the_issues_eight_layers_are_the_issues_numbers():
    """ISSUE 49: 5.17 GFLOP a token forward, 15.5 trained, 254 TFLOP a step
    of 16,384 tokens, the heads 15.6% of the work, 64 layer passes."""
    cfg = dict(committed(), layer_types=["full_attention"] * 8)
    assert round(ouro_flops.forward_flops_per_token(cfg, 8192) / 1e9, 2) \
        == 5.17
    step = ouro_flops.train_flops_per_step(cfg, 2, 8192)
    assert round(step / 16384 / 1e9, 1) == 15.5
    assert round(step / 1e12) == 254
    assert round(100 * 4 * 2 * 2048 * 49152
                 / ouro_flops.forward_flops_per_token(cfg, 8192), 1) == 15.6
    assert ouro_flops.layer_passes_per_step(cfg, 2) == 64
    # one pass of one layer counts as flops.py counts a dense decoder
    from benchmark import flops

    dense = dict(hidden_size=2048, num_hidden_layers=8,
                 intermediate_size=5632 * 3 // 2, vocab_size=49152)
    once = dict(cfg, total_ut_steps=1)
    assert ouro_flops.train_flops_per_step(once, 2, 8192) == \
        flops.train_flops_per_step(dense, 2, 8192) + 6 * 2048 * 16384


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return ourotiny.tiny_root(tmp_path_factory.mktemp("ouro"))


def driven(root, **wrong):
    """The driver's outcome and what it said, on the tiny configuration.
    With ``wrong``, the program is built from the configuration with those
    keys changed and the reference from the configuration as it stands."""
    from benchmark import harness

    path = os.path.join(root, ourotiny.CONFIG)
    with open(path, "w") as f:
        json.dump(ourotiny.config(**wrong), f)
    try:
        cell, run = benchtiny.cpu_run(root, ourotiny.CELL, seed=2 ** 31 + 7,
                                      seconds=1.0)
    finally:
        with open(path, "w") as f:
            json.dump(ourotiny.config(), f)
    driver = cell.driver()
    if wrong:
        follow = driver.steps.follow_with_reference
        driver.steps.follow_with_reference = \
            lambda cfg, *a, **k: follow(ourotiny.config(), *a, **k)
    run.control = None if wrong else "float8"
    lines = []
    said = harness.say
    harness.say = lambda what, **f: lines.append((what, f))
    try:
        outcome = driver.run(run)
    finally:
        harness.say = said
    return cell, run, outcome, dict(lines)


@pytest.fixture(scope="module")
def sound(root):
    return driven(root)


def test_program_follows_the_reference_through_the_looped_driver(sound):
    _, run, outcome, said = sound
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    rows = {r["number"]: r for r in said["compared"]["rows"]}
    assert set(rows) == set(ourotiny.TINY_LIMITS)
    assert all(r["ok"] for r in rows.values())
    assert 3.5 < said["compared"]["reference_losses"][0] < 6  # ln 64 = 4.2
    assert rows["loss_rise_over_window"]["value"] < 0         # lr 1e-4
    steps = said["exit_gate"]["steps"]
    assert len(steps) == 3 and said["exit_gate"][
        "layer_passes_per_step"] == 4 * 2 * 2
    for step in steps:
        assert sum(step["exit_share"]) == pytest.approx(1.0, abs=1e-5)
        assert len(step["pass_loss"]) == 4
        # the seeded bias of about -1: no pass is starved, the last leads
        assert all(0.1 < p < 0.45 for p in step["exit_share"])
        assert step["exit_share"][3] == max(step["exit_share"])


def test_control_fails_the_limits_the_program_passes(sound):
    from benchmark.reference import check

    *_, said = sound
    correct, rows = check.judge(
        said["control"]["numbers"], ourotiny.TINY_LIMITS)
    assert correct is False
    assert "first_grad_norm_gap" in {r["number"] for r in rows
                                     if not r["ok"]}
    assert set(said["control"]["numbers"]) == set(ourotiny.TINY_LIMITS) - {
        "loss_rise_over_window", "flash_kernels_missing"}


@pytest.mark.parametrize("wrong,fails", [
    ({"total_ut_steps": 3}, "pass_loss_gap_4"),
    ({"exit_gate_bias": 0.0}, "exit_share_gap"),
    ({"exit_entropy_weight": 0.0}, "loss_gap_step1"),
], ids=["a_pass_short", "no_gate_bias", "no_entropy_term"])
def test_a_program_that_leaves_a_mechanism_out_fails_by_its_number(
        root, wrong, fails):
    """The program built from a configuration that differs, the reference
    from the committed one: fewer passes, a gate that starts at 0, a loss
    with no entropy term."""
    *_, outcome, said = driven(root, **wrong)
    assert outcome["correct"] is False
    failed = {r["number"] for r in said["compared"]["rows"] if not r["ok"]}
    assert fails in failed, failed


def test_context_counts_this_familys_flops(sound):
    cell, _, outcome, _ = sound
    ctx, cfg = outcome["context"], cell.config
    assert ctx["flops_per_step"] == ouro_flops.train_flops_per_step(
        cfg, 4, 32)
    assert ctx["attention_flops_per_step"] == \
        ouro_flops.train_attention_flops_per_step(cfg, 4, 32)
    assert ctx["attention_bytes_per_step"] == \
        ouro_flops.train_attention_bytes_per_step(cfg, 4, 32)
    assert ctx["tokens_per_step"] == 128 and ctx["steps"] == \
        outcome["attempted"] > 0


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"][RATE]["value"] > 0


def test_driver_puts_this_familys_files_in_the_base_drivers_names(root):
    from benchmark import ouro_weights

    cell = loader.Manifest(root).cell(ourotiny.CELL)
    driver = cell.driver()
    assert driver.steps.weights is ouro_weights
    assert driver.steps.flops is ouro_flops
    assert driver.steps.Trainer is driver.Trainer
    assert driver.steps.check is driver.Compared
    # its own copy: the accepted cells' driver keeps its tables
    base = loader.Manifest(root).cell("gpt2-xl.train-1chip").driver()
    assert base is not driver.steps
    assert base.weights is not ouro_weights


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import ouro_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import ouro

    cell = loader.Manifest(root).cell(ourotiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    # the same weights as a per-layer Hugging Face state dict
    sd = {}
    for name, value in w.items():
        if name.startswith(ouro_weights.LAYER):
            for i in range(value.shape[0]):
                sd[f"model.layers.{i}.{name[len(ouro_weights.LAYER):]}"] = \
                    np.asarray(value[i])
        else:
            sd[name] = np.asarray(value)
    theirs = ouro.translate_hf_state_dict(sd, ouro_weights.hf_view(cfg))
    assert set(theirs) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(np.asarray(flat[key]), theirs[key])
    back = builder.hf_from_flat(cfg, flat)
    assert set(back) == set(w)
    for key in w:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(w[key]))
    module = builder.module(cfg)
    assert module.loop_steps == 4 and module.branch_layernorm
    assert module.num_layers == 2 and module.activation_checkpointing is False


def test_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Eight query blocks and four token blocks give what one block gives:
    the MLP, the attention, the head, the gate and the sums."""
    import jax
    import numpy as np

    from benchmark import ouro_weights
    from benchmark.reference import laguna as shared
    from benchmark.reference import ouro as reference

    cfg = ourotiny.config()
    w = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))(np.uint32(9))
    ids = jax.random.randint(jax.random.key(1), (2, 32), 0, 64)

    def loss_and_grad():
        return jax.value_and_grad(
            lambda w: reference.loss_parts(cfg, w, ids, "float32")[0])(w)

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


def test_reference_loops_over_the_passes_with_the_same_weights():
    """The passes are written out (four scans over the layers, one after
    the other, each with the norm behind it), every pass on the one set of
    stacked tensors."""
    import jax
    import numpy as np

    from benchmark import ouro_weights
    from benchmark.reference import ouro as reference

    cfg = ourotiny.config()
    w = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))(np.uint32(9))
    ids = jax.random.randint(jax.random.key(1), (1, 8), 0, 64)
    jaxpr = jax.make_jaxpr(
        lambda w: reference.pass_states(cfg, w, ids)[-1])(w)
    over_layers = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in over_layers] == [2] * 4
    states = reference.pass_states(cfg, w, ids)
    assert len(states) == 4
    # a pass of its own weights would differ: same weights, new input
    assert float(np.max(np.abs(states[1] - states[0]))) > 0.01
    kept = reference.pass_states(cfg, w, ids, remat=True)
    np.testing.assert_allclose(kept[-1], states[-1], atol=1e-6)


# ------------------------------------------------------------ the readers

def reader_context():
    seconds = {"fusion.1": 4.0, "fusion.2": 2.0, "fusion.3": 1.0,
               "fusion.4": 0.5, "fusion.5": 0.25, "fusion.6": 0.25,
               "fusion.7": 1.0, "fusion.8": 0.5, "unknown.1": 10.5}
    loop, stack = "smp/model/loop", "smp/model/stack"
    user, block = "smp/step/user", "smp/layer/block"
    index = {
        "fusion.1": {"scopes": (user, loop, stack, block, "smp/mlp/dense")},
        "fusion.2": {"scopes": (user, loop, stack, block,
                                "smp/layer/branch_norm")},
        # the passes' own: the norm after a pass, the stacked states
        "fusion.3": {"scopes": (user, loop)},
        "fusion.4": {"scopes": (loop,)},
        # the layer scan's own work is the stack's, not the loop's
        "fusion.5": {"scopes": (user, loop, stack)},
        "fusion.6": {"scopes": (user, "smp/head/exit_gate")},
        "fusion.7": {"scopes": (user, "smp/head/logits")},
        # a stack that runs once
        "fusion.8": {"scopes": (user, stack, block)},
    }
    cell = types.SimpleNamespace(config=committed())
    return {"trace": dict(op_self_s=seconds), "cell": cell}, index


@pytest.mark.parametrize("metric,expected", [
    ("loop.branch_norm_time_share", 100 * 2.0 / 20),
    ("loop.carry_time_share", 100 * (1.0 + 0.5) / 20),
    ("loop.exit_gate_time_share", 100 * 0.25 / 20),
])
def test_new_reader_on_a_made_up_op_index(monkeypatch, metric, expected):
    cell = loader.Manifest().cell(ourotiny.CELL)
    read = cell.metric_reader(metric)
    ctx, index = reader_context()
    scopes = read.__globals__["_tree"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read(ctx) == pytest.approx(expected)
    # a program from before the scopes (the parent): nothing to read,
    # nothing raised; nor with no index
    monkeypatch.setattr(
        scopes, "step_index", lambda: {k: {"phase": "other", "scope": None}
                                       for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_layer_passes_reader_reads_the_programs_gauge(monkeypatch):
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        record_loop_passes,
        telemetry,
    )

    cell = loader.Manifest().cell(ourotiny.CELL)
    read = cell.metric_reader("loop.layer_passes_per_step")
    ctx, _ = reader_context()
    telemetry.reset()
    monkeypatch.setattr(telemetry, "closed_report", None, raising=False)
    assert read(ctx) is None            # a stack that runs once: no gauge
    record_loop_passes(4, DEPTH)
    assert read(ctx) == 4 * DEPTH * 2
    # after ``smp.shutdown()`` the report the registry kept is read
    scopes = read.__globals__["_scopes"]
    kept = telemetry.report()
    telemetry.reset()
    monkeypatch.setattr(telemetry, "closed_report", kept, raising=False)
    assert read(ctx) == 4 * DEPTH * 2
    assert scopes._series("smp_loop_passes")[0]["value"] == 4


# ------------------------------------------------------------ the entries

def test_new_entries_are_appended_for_the_new_cell(manifest):
    for name, (unit, better, source) in NEW_METRICS.items():
        metric = benchtiny.entry_listing(manifest, name, [ourotiny.CELL])
        assert metric == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "model step", "moves": RATE}
    cell = manifest.cell(ourotiny.CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert reported == set(NEW_METRICS) | LISTED_FOR_THE_CELL | {
        "step.mfu", "step.dispatch_ms", "device.idle_share.train",
        "device.hbm_peak_gb.train"}
    assert {m["name"] for m in cell.end_to_end()} == {RATE, "setup_s"}
    assert cell.chips == 1
    assert cell.traffic["kind"] == "train_steps_looped"
    assert (cell.traffic["batch"], cell.traffic["seq"]) == (2, 8192)
    base = manifest.cell("gpt2-xl.train-1chip").traffic
    assert {k: cell.traffic[k] for k in ("lr", "check_steps", "in_flight")} \
        == {k: base[k] for k in ("lr", "check_steps", "in_flight")}
    assert cell.traffic["token_law"] == {"kind": "zipf_mandelbrot",
                                         "offset": 1000}
    config = manifest._entry("configs", ourotiny.NAME)
    assert config["reduced"] == sorted(committed()["reduced"])
    assert config["source"] == committed()["source"]
    with open(os.path.join(cell.manifest.dir, "limits",
                           cell.name + ".json")) as f:
        limits = json.load(f)
    assert set(limits["limits"]) == set(ourotiny.TINY_LIMITS)
    assert limits["set_from"]


def test_the_committed_entries_stand_last_in_their_lists():
    """On the manifest as committed (a later PR's entries come after):
    nothing that was there moved, and each of this PR's entries follows
    every entry that was there before it."""
    data = benchtiny.manifest_data()
    before = {"configs": "xing4.0-29b-a4b-5l-ep8",
              "workloads": "xing4.0-29b-a4b.train-4k-group8-1chip",
              "per_layer": "attn.latent_proj_time_share"}
    mine = {"configs": [ourotiny.NAME], "workloads": [ourotiny.CELL],
            "per_layer": list(NEW_METRICS)}
    for group, last_before in before.items():
        names = [e["name"] for e in data[group]]
        at = names.index(last_before)
        assert names[at + 1:at + 1 + len(mine[group])] == mine[group]
    for entry in data["end_to_end"] + data["per_layer"]:
        cells = entry.get("workloads", [])
        if ourotiny.CELL in cells and entry["name"] not in NEW_METRICS:
            at = cells.index(ourotiny.CELL)
            assert cells[at - 1] == before["workloads"], entry["name"]
    listed = {e["name"] for e in data["per_layer"]
              if ourotiny.CELL in e.get("workloads", [])}
    assert listed == set(NEW_METRICS) | LISTED_FOR_THE_CELL
