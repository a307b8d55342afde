"""The SDAR family's benchmark files: the configuration against the
catalog's row, the block-diffusion driver end to end at tiny widths on the
CPU (the program against the plain reference through the driver's own
functions, the float8 control failing the same limits), the batches and
the leaves the configuration's ``routing_seeds`` fix, the builder's
translation against the repo's numpy translator, the reference's blocks,
the FLOP and byte counts by hand, and each new reader on a made-up
context."""

import json
import os
import types

import pytest

import benchtiny
import sdartiny
from benchmark import loader, sdar_flops

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    "flash.block_diffusion_time_share": ("kernels", "lower", "%"),
    "flash.block_diffusion_roofline": ("kernels", "higher", "%"),
    "flash.block_diffusion_tiles_visited_over_live": (
        "kernels", "lower", "ratio"),
    "diffusion.head_positions_share": ("model step", "lower", "%"),
    "moe.block_diffusion_rows_per_position": (
        "expert layers", "lower", "rows/position"),
}


# ---------------------------------------------------------------- config

def catalog_row():
    if not os.path.isfile(CATALOG):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(CATALOG) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "SDAR-30B-A3B-Chat":
                return row
    pytest.fail("no SDAR-30B-A3B-Chat row in the catalog")


def committed():
    with open(os.path.join(benchtiny.ROOT, sdartiny.CONFIG)) as f:
        return json.load(f)


def test_config_holds_every_catalog_key_or_lists_it_as_reduced():
    row, cfg = catalog_row(), committed()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value, key
            assert key in cfg["published"]
        else:
            assert cfg[key] == value, key
    for key, value in cfg["published"].items():
        if key in row["config"]:
            assert value == row["config"][key], key
    # the one reduced key the source does not have carries the depth
    assert set(cfg["reduced"]) - set(row["config"]) == {"layer_types"}
    assert set(row["not_given"]) == {"block length", "noise schedule"}
    assert "block_length" in cfg["assumed"]
    assert "noise_schedule" in cfg["assumed"]


def test_config_keeps_every_width_and_states_its_share():
    cfg = committed()
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["rope_theta"]) == (2048, 128, 6144, 768, 8, 1000000)
    assert cfg["num_experts_published"] == 128 and cfg["num_experts"] == 16
    assert cfg["experts_held_first"] == 0
    # an eighth of the heads, the experts and the vocabulary, exactly
    assert (cfg["num_attention_heads"] * 8, cfg["num_experts"] * 8,
            cfg["vocab_size"] * 8) == (32, 128, 151936)
    assert cfg["num_key_value_heads"] == 1
    assert cfg["layer_types"] == ["full_attention"] * 5
    assert cfg["num_hidden_layers"] == 48
    assert cfg["block_length"] == 4
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1 == 18991
    for key in cfg["reduced"]:
        assert cfg["reduced"][key]
    for key in ("block_length", "noise_schedule", "no_shift",
                "mask_token_id", "qk_norm", "state_dict_names", "aux_loss",
                "initializer_range", "embedding_range"):
        assert key in cfg["assumed"], key
    assert cfg["embedding_range"] == 1.0 and cfg["initializer_range"] == 0.02
    assert "8 chips" in cfg["deployment"] and cfg["family"] == "sdar"
    assert cfg["module"] == {"activation_checkpointing": True}
    assert cfg["smp"]["fused_step_donation"] is True
    assert set(cfg["routing_seeds"]) == {"mask_row", "router"}
    assert len(cfg["routing_seeds"]["router"]) == len(cfg["layer_types"])
    assert "routing_seeds" in cfg["departures"]


def test_held_parameters_are_what_the_issue_counted():
    import numpy as np

    from benchmark import sdar_weights

    spec = sdar_weights.spec_for(committed())
    layers = ends = 0
    for name, (shape, _, _) in spec.items():
        if name.startswith("model.layers.full."):
            assert shape[0] == 5
            layers += int(np.prod(shape))
        else:
            ends += int(np.prod(shape))
    assert round(layers / 5e6, 2) == 78.39
    assert round(ends / 1e6, 1) == 77.8
    assert round((layers + ends) / 1e6, 1) == 469.7
    layer = {name.split(".", 3)[3]: int(np.prod(shape)) // 5
             for name, (shape, _, _) in spec.items() if ".full." in name}
    assert round(sum(v for k, v in layer.items()
                     if k.startswith("self_attn.")) / 1e6, 2) == 2.62
    assert round(layer["mlp.gate.weight"] / 1e6, 2) == 0.26
    assert round(sum(v for k, v in layer.items()
                     if k.startswith("mlp.experts.")) / 16e6, 2) == 4.72
    assert spec["model.embed_tokens.weight"][1:] == ("normal", 1.0)
    assert spec["lm_head.weight"][1:] == ("normal", 0.02)


def test_traffic_holds_the_parameters_the_issue_names():
    cell = loader.Manifest().cell(sdartiny.CELL)
    mix = {k: v for k, v in cell.traffic.items() if k != "why"}
    assert mix == {
        "kind": "train_steps_block_diffusion", "batch": 4, "seq": 8192,
        "lr": 0.0001, "batch_pool": 8, "check_steps": 3, "in_flight": 2,
        "token_law": {"kind": "zipf_mandelbrot", "offset": 1000},
        "noise": {"kind": "linear_per_block", "eps": 0.001}}
    assert cell.chips == 1 and cell.config["smp"]["microbatches"] == 4


# ------------------------------------------------------ counts, by hand

def test_live_pairs_are_counted_from_the_definition():
    import numpy as np

    from benchmark.reference import sdar as reference

    for seq, block in ((16, 4), (24, 2), (12, 12)):
        idx = np.arange(2 * seq)
        assert sdar_flops.live_pairs(seq, block) == int(np.asarray(
            reference.live(idx, idx, seq, block)).sum())
    assert sdar_flops.live_pairs(8192, 4) == 8192 * 8192 + 8192 * 4


def test_attention_counts_the_live_pairs_and_one_kv_head():
    cfg = committed()
    pairs = 8192 * 8192 + 8192 * 4
    forward = 5 * 4 * 128 * 4 * pairs
    assert sdar_flops.train_attention_flops_per_step(cfg, 4, 8192) == \
        3 * forward * 4
    # six tensors the size of the 4 query heads, six the size of the KV
    # head, over both copies
    assert sdar_flops.train_attention_bytes_per_step(cfg, 4, 8192) == \
        2 * 6 * 4 * 16384 * 128 * (4 + 1) * 5
    shapes = sdar_flops.layer_shapes(cfg)
    assert len(shapes) == 5 and all(
        s == {"heads": 4, "kv_heads": 1, "window": None, "sparse": True}
        for s in shapes)
    # the issue: 8.4 of 23.6 MFLOP a position a layer forward
    per_position = 4 * 128 * 4 * pairs / 16384
    assert round(per_position / 1e6, 1) == 8.4


def test_matmul_counts_by_hand():
    cfg = committed()
    D, hd = 2048, 128
    layer = D * hd * (2 * 4 + 2 * 1) + D * 128    # q, o, k, v; the router
    assert sdar_flops.layer_matmul_params(cfg) == 5 * layer
    assert sdar_flops.expert_flops_per_row(cfg) == 18 * D * 768
    rows = 5 * 16 * 4096          # layers x held experts x rows each
    assert rows == 65536 * 8 * 5 // 8 == 327680
    step = sdar_flops.train_flops_per_step(cfg, 4, 8192, rows)
    assert step == (6 * 5 * layer * 65536        # both copies
                    + 6 * D * 18992 * 32768      # the head: the noisy copy
                    + 18 * D * 768 * rows
                    + sdar_flops.train_attention_flops_per_step(
                        cfg, 4, 8192))
    # the issue: 30.8 TFLOP a step
    assert round(step / 1e12, 1) == 30.8
    assert sdar_flops.grouped_matmul_bytes(cfg, 100, 2) == 2 * (
        5 * 100 * D + 3 * 16 * 3 * D * 768 * 2)


# --------------------------------------------- batches and fixed leaves

def test_batches_carry_their_noise_from_the_seed():
    import numpy as np

    from benchmark import sdar_weights

    cfg = sdartiny.config()
    mix = dict(loader.Manifest().cell(sdartiny.CELL).traffic,
               **dict(sdartiny.TINY_MIX, seq=256))
    pool = sdar_weights.make_batches(cfg, mix, np.uint32(2 ** 31 + 5))
    again = sdar_weights.make_batches(cfg, mix, np.uint32(2 ** 31 + 5))
    other = sdar_weights.make_batches(cfg, mix, np.uint32(6))
    assert len(pool) == 4 and len(pool[:3]) == 3
    batch = pool[1]
    assert set(batch) == {"clean", "noisy", "rates", "mask_id"}
    assert batch["clean"].shape == batch["noisy"].shape == (4, 256)
    assert batch["rates"].shape == (4, 64) and int(batch["mask_id"]) == 63
    for key in ("clean", "noisy", "rates"):
        np.testing.assert_array_equal(
            np.asarray(batch[key]), np.asarray(again[1][key]))
        assert not np.array_equal(
            np.asarray(batch[key]), np.asarray(other[1][key]))
        np.testing.assert_array_equal(
            np.asarray(pool[:3][1][key]), np.asarray(batch[key]))
    clean, noisy = np.asarray(batch["clean"]), np.asarray(batch["noisy"])
    assert clean.max() < 63                      # no data id is the mask's
    changed = noisy != clean
    assert (noisy[changed] == 63).all() and 0.3 < changed.mean() < 0.7
    rates = np.asarray(batch["rates"])
    assert 1e-3 <= rates.min() and rates.max() <= 1.0
    # every batch of the pool has a draw of its own
    assert not np.array_equal(np.asarray(pool[0]["rates"]), rates)
    with pytest.raises(ValueError, match="unknown token_law or noise"):
        sdar_weights.make_batches(
            cfg, dict(mix, noise={"kind": "cosine"}), np.uint32(1))


def test_routing_seeds_fix_the_routers_and_the_mask_row_alone():
    import jax
    import numpy as np

    from benchmark import sdar_weights

    cfg = sdartiny.config()
    make = jax.jit(lambda s: sdar_weights.make_weights(cfg, s))
    a, b = make(np.uint32(1)), make(np.uint32(2))
    router = "model.layers.full.mlp.gate.weight"
    table = np.asarray(a["model.embed_tokens.weight"])
    for name in a:
        same = np.array_equal(np.asarray(a[name]), np.asarray(b[name]))
        assert same == (name == router), name
    np.testing.assert_array_equal(
        table[63], np.asarray(b["model.embed_tokens.weight"])[63])
    assert not np.array_equal(
        table[62], np.asarray(b["model.embed_tokens.weight"])[62])
    assert abs(float(table[63].std()) - 1.0) < 0.3
    # without the key every leaf follows the run's seed
    free = {k: v for k, v in cfg.items() if k != "routing_seeds"}
    c = jax.jit(lambda s: sdar_weights.make_weights(free, s))(np.uint32(1))
    assert not np.array_equal(np.asarray(c[router]), np.asarray(a[router]))
    np.testing.assert_array_equal(
        np.asarray(c["lm_head.weight"]), np.asarray(a["lm_head.weight"]))
    for name in a:              # leaf by leaf (not jitted: to rounding)
        np.testing.assert_allclose(
            np.asarray(sdar_weights.make_leaf(cfg, np.uint32(1), name)),
            np.asarray(a[name]), rtol=1e-5, atol=1e-7)


# ---------------------------------------------- the driver, tiny, on CPU

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return sdartiny.tiny_root(tmp_path_factory.mktemp("sdar"))


@pytest.fixture(scope="module")
def sound(root):
    cell, run = benchtiny.cpu_run(root, sdartiny.CELL, seed=2 ** 31 + 7,
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


def test_program_follows_the_reference_through_the_driver(sound):
    _, run, outcome, said = sound
    assert outcome["correct"] is True
    assert run.compiles_in_window == 0
    rows = {r["number"]: r for r in said["compared"]["rows"]}
    assert set(rows) == set(sdartiny.TINY_LIMITS)
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
        said["control"]["numbers"], sdartiny.TINY_LIMITS)
    assert correct is False
    failed = {r["number"] for r in rows if not r["ok"]}
    assert "first_grad_sample_gap" in failed
    # the sample's gap is an error, not a difference of norms: it reads
    # the lower precision several times over what the program reads
    sound = {r["number"]: r["value"] for r in said["compared"]["rows"]}
    assert said["control"]["numbers"]["first_grad_sample_gap"] > 3 * sound[
        "first_grad_sample_gap"]
    # every leaf's reading is printed beside its norm's gap, both runs
    leaves = said["first_gradient"]
    assert set(leaves["sample_gaps"]) == set(leaves["norm_gaps"])
    assert len(leaves["sample_gaps"]) == 15


def test_rate_counts_data_tokens_and_flops_count_both_copies(sound):
    cell, run, outcome, said = sound
    ctx, cfg = outcome["context"], cell.config
    assert ctx["tokens_per_step"] == 4 * 32          # not the 256 positions
    assert outcome["end_to_end"]["train.tokens_per_s_per_chip"] == \
        pytest.approx(outcome["attempted"] * 128 / run.window_s)
    rows = said["compared"]["smp_moe_local_assignments"]
    assert ctx["flops_per_step"] == pytest.approx(
        sdar_flops.train_flops_per_step(
            cfg, 4, 32, rows / outcome["attempted"]))
    assert ctx["attention_flops_per_step"] == \
        sdar_flops.train_attention_flops_per_step(cfg, 4, 32)
    # 2 layers x 256 positions x 4 a position x 4 of 16 held
    assert 0.5 < rows / outcome["attempted"] / 512 < 2.0
    # the objective's counters, read back with the expert layers'
    counted = said["diffusion"]
    assert counted["steps"] == outcome["attempted"]
    assert counted["data_tokens"] == outcome["attempted"] * 128
    assert 0.3 < counted["loss_tokens_share"] < 0.7


def test_result_line_reports_the_cells_metrics(sound):
    from benchmark import harness

    cell, run, outcome, _ = sound
    line = harness.result_line(run, outcome)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end()}
    assert line["metrics"]["train.tokens_per_s_per_chip"]["value"] > 0


def test_driver_binds_the_family_and_its_own_batches(root):
    from benchmark import mellum_weights, sdar_weights

    manifest = loader.Manifest(root)
    cell = manifest.cell(sdartiny.CELL)
    driver = cell.driver()
    bound = driver.bind(cell.config)
    assert bound.laguna_weights is sdar_weights
    assert bound.laguna_flops is sdar_flops
    assert bound.make_batches is driver.make_batches
    assert bound.Trainer is driver.Trainer
    mellum = manifest.cell("mellum2-12b-a2.5b.train-8k-group-1chip").driver()
    other = mellum.bind({"family": "mellum"})
    assert other is not bound and other.laguna_weights is mellum_weights
    assert other.make_batches is not driver.make_batches


def test_builder_translates_as_the_repos_numpy_translator_does(root):
    import jax
    import numpy as np

    from benchmark import sdar_weights
    from smdistributed_modelparallel_tpu.nn.huggingface import sdar

    cell = loader.Manifest(root).cell(sdartiny.CELL)
    cfg, builder = cell.config, cell.builder()
    w = jax.jit(lambda s: sdar_weights.make_weights(cfg, s))(np.uint32(3))
    flat = builder.flat_from_hf(cfg, w)
    sd = {}
    for i in range(len(cfg["layer_types"])):
        prefix = "model.layers.full."
        for name, value in w.items():
            if not name.startswith(prefix):
                continue
            tail, value = name[len(prefix):], np.asarray(value[i])
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
    theirs = sdar.translate_hf_state_dict(sd, sdar_weights.hf_view(cfg))
    assert set(theirs) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(np.asarray(flat[key]), theirs[key])
    back = builder.hf_from_flat(cfg, flat)
    assert set(back) == set(w)
    for key in w:
        np.testing.assert_array_equal(np.asarray(back[key]),
                                      np.asarray(w[key]))


def test_reference_in_blocks_is_the_reference_whole(monkeypatch):
    """Eight blocks of query rows and four of positions give what one
    block gives: the mask a block of rows at a time, the sums."""
    import jax
    import numpy as np

    from benchmark import sdar_weights
    from benchmark.reference import sdar as reference

    cfg = sdartiny.config()
    w = jax.jit(lambda s: sdar_weights.make_weights(cfg, s))(np.uint32(9))
    clean = jax.random.randint(jax.random.key(1), (2, 32), 0, 63)
    masked = jax.random.uniform(jax.random.key(2), (2, 32)) < 0.5
    noisy = np.where(masked, 63, clean)
    rates = jax.random.uniform(jax.random.key(3), (2, 8), minval=0.1)

    def loss_and_grad():
        return jax.value_and_grad(
            lambda w: reference.diffusion_loss_sum(
                cfg, w, clean, noisy, rates, 63, "float32")[0])(w)

    whole, g_whole = loss_and_grad()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 8)
    monkeypatch.setattr(reference, "TOKEN_BLOCK", 8)
    blocks, g_blocks = loss_and_grad()
    np.testing.assert_allclose(float(blocks), float(whole), rtol=1e-5)
    for key in g_whole:
        np.testing.assert_allclose(
            np.asarray(g_blocks[key]), np.asarray(g_whole[key]),
            rtol=2e-3, atol=2e-5)


# ------------------------------------------------------------ the readers

def reader_context():
    seconds = {"fusion.1": 2.0, "smp_flash_fwd.3": 1.0,
               "smp_flash_bwd_dq.4": 2.0, "smp_flash_bwd_dkv.5": 1.5,
               "smp_flash_fwd.9": 4.0, "fusion.8": 1.0}
    trace = dict(op_self_s=seconds, busy_s_by_device=[20.0])
    trace["matching"] = None
    under = ("smp/layer/full", "smp/attn/block_diffusion")
    index = {
        "fusion.1": {"scopes": under},
        "smp_flash_fwd.3": {"scopes": under},
        "smp_flash_bwd_dq.4": {"scopes": under},
        "smp_flash_bwd_dkv.5": {"scopes": under},
        # a flash kernel of another kind of layer is not this mask's
        "smp_flash_fwd.9": {"scopes": ("smp/layer/full", "smp/attn/full")},
        "fusion.8": {"scopes": ("smp/layer/full", "smp/moe/experts")},
    }
    return trace, index


def test_time_share_reads_the_kernels_under_the_patterns_scope(monkeypatch):
    cell = loader.Manifest().cell(sdartiny.CELL)
    read = cell.metric_reader("flash.block_diffusion_time_share")
    trace, index = reader_context()
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    assert read({"trace": trace}) == pytest.approx(100 * 4.5 / 20)
    # the parent: no such scope, nothing to read, nothing raised
    monkeypatch.setattr(scopes, "step_index", lambda: {
        k: {"scopes": ("smp/layer/full", "smp/attn/full")} for k in index})
    assert read({"trace": trace}) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read({"trace": trace}) is None


def test_roofline_is_flash_rooflines_arithmetic_under_the_scope(monkeypatch):
    cell = loader.Manifest().cell(sdartiny.CELL)
    read = cell.metric_reader("flash.block_diffusion_roofline")
    seconds, index = reader_context()

    class Trace(dict):
        def matching(self, prefix):
            return sum(s for n, s in self["op_self_s"].items()
                       if n.startswith(prefix))

    trace = Trace(seconds)
    ctx = {"trace": trace, "steps": 10,
           "run": types.SimpleNamespace(devices=[0]),
           "peaks": {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0},
           "attention_flops_per_step": 20.0,
           "attention_bytes_per_step": 1.0}
    scopes = read.__globals__["_moe"]._scopes
    monkeypatch.setattr(scopes, "step_index", lambda: index)
    # least 0.2 s a step (the FLOPs bind); the kernels took 8.5 s / 10
    assert read(ctx) == pytest.approx(100 * 0.2 / 0.85)
    monkeypatch.setattr(scopes, "step_index", lambda: {
        k: {"scopes": ("smp/attn/full",)} for k in index})
    assert read(ctx) is None
    monkeypatch.setattr(scopes, "step_index", lambda: None)
    assert read(ctx) is None


def test_expert_layers_share_counts_the_products_once(monkeypatch):
    """The cell's own ``moe.block_diffusion_time_share`` went with PR 41:
    ``moe.time_share`` lists the cell and reads the same seconds (35.66
    both in one traced run on the chip), now that the op index puts the
    grouped products under ``smp/moe/experts``; ``moe.experts_time_share``
    counts a product once, by its scope or, on an index that gives it none,
    by its name."""
    cell = loader.Manifest().cell(sdartiny.CELL)
    share = cell.metric_reader("moe.time_share")
    experts = cell.metric_reader("moe.experts_time_share")
    trace, index = reader_context()
    trace["op_self_s"] = dict(
        trace["op_self_s"], **{"fusion.20": 3.0, "ragged-dot-7": 2.5})
    index = dict(index, **{
        "fusion.20": {"scopes": ("smp/layer/full", "smp/moe/combine")},
        "ragged-dot-7": {"scopes": ("smp/layer/full", "smp/moe/experts"),
                         "scope": "smp/moe/experts", "kernel": "ragged_dot",
                         "inherited": True}})

    def use(step_index):
        for module in (share.__globals__["_moe"],
                       experts.__globals__["_experts"]._moe):
            monkeypatch.setattr(module._scopes, "step_index",
                                lambda: step_index)

    use(index)
    # combine 3.0 + experts 1.0 + the product 2.5, all under the scopes
    assert share({"trace": trace}) == pytest.approx(100 * 6.5 / 20)
    assert experts({"trace": trace}) == pytest.approx(100 * 3.5 / 20)
    # an index of before PR 39: the product has no scope
    use(dict(index, **{"ragged-dot-7": {"phase": "backward", "scope": None}}))
    assert share({"trace": trace}) == pytest.approx(100 * 4.0 / 20)
    assert experts({"trace": trace}) == pytest.approx(100 * 3.5 / 20)
    # a program with no expert layer: nothing to read, nothing raised
    use({k: {"scopes": ("smp/attn/full",)} for k in index})
    assert share({"trace": trace}) is None
    assert experts({"trace": trace}) is None


def test_rows_and_load_readers_read_the_drivers_count():
    from smdistributed_modelparallel_tpu.utils import telemetry as t

    cell = loader.Manifest().cell(sdartiny.CELL)
    rows = cell.metric_reader("moe.block_diffusion_rows_per_position")
    # its twin of PR 37 went with PR 41: the same gauges, 4.76 both
    load = cell.metric_reader("moe.expert_load_max_over_mean")
    ctx = {"cell": cell, "tokens_per_step": 32768,
           "moe": {"rows_per_step": 327680.0}}
    assert rows(ctx) == pytest.approx(1.0)        # five layers, both copies
    assert rows(dict(ctx, moe={"rows_per_step": 344064.0})) == pytest.approx(
        1.05)                                     # a second held expert
    assert rows({"cell": cell, "tokens_per_step": 32768}) is None
    t.telemetry.reset()
    t.telemetry.closed_report = None
    assert load({}) is None
    for layer, value in (("a", 4.6), ("b", 5.0)):
        t.telemetry.gauge(
            "smp_moe_expert_load_max_over_mean", "test").labels(
                layer=layer).set(value)
    assert load({}) == pytest.approx(4.8)
    t.telemetry.reset()


def test_counter_readers_read_the_programs_gauges():
    from smdistributed_modelparallel_tpu.utils import telemetry as t

    cell = loader.Manifest().cell(sdartiny.CELL)
    tiles = cell.metric_reader(
        "flash.block_diffusion_tiles_visited_over_live")
    head = cell.metric_reader("diffusion.head_positions_share")
    t.telemetry.reset()
    t.telemetry.closed_report = None
    assert tiles({}) is None and head({}) is None     # the parent: no gauge
    for name, visited in (("fwd", 576), ("dq", 576), ("dkv", 600)):
        t.record_flash_tiles(name, visited, 576)
    t.record_lm_head_positions(8192, 16384)
    assert tiles({}) == pytest.approx((576 + 576 + 600) / (3 * 576))
    assert head({}) == pytest.approx(50.0)
    # the driver frees the program before the readers run: what
    # smp.shutdown() dropped is read from the report it kept
    t.telemetry.reset()
    assert tiles({}) == pytest.approx(1752 / 1728)
    assert head({}) == pytest.approx(50.0)
    t.telemetry.closed_report = None


def test_new_metrics_are_listed_for_the_new_cell(manifest):
    data = manifest.data
    for name, (layer, better, unit) in NEW_METRICS.items():
        metric = benchtiny.entry_listing(manifest, name, [sdartiny.CELL])
        assert metric["moves"] == "train.tokens_per_s_per_chip"
        assert (metric["layer"], metric["better"], metric["unit"]) == (
            layer, better, unit)
    rate = manifest._entry("end_to_end", "train.tokens_per_s_per_chip")
    assert sdartiny.CELL in rate["workloads"]
    cell = manifest.cell(sdartiny.CELL)
    reported = {m["name"] for m in cell.per_layer()}
    assert set(NEW_METRICS) | {
        "step.mfu", "step.dispatch_ms", "device.idle_share.train",
        "device.hbm_peak_gb.train"} <= reported
    assert sdartiny.CELL in [w["name"] for w in data["workloads"]]
    assert cell.config_entry in data["configs"]
    assert cell.config_entry["name"] == "sdar-30b-a3b-chat-5l-ep8"
