"""Mellum's layers in the program, at small sizes on the CPU, seeded random
weights, against the plain reference (``benchmark/reference/mellum.py``):
grouped KV heads with an RMSNorm on each query and key head, YaRN on the
whole head in full layers, a stack that starts with its window layers,
expert layers with no shared expert; the four-layer model through
``DistributedModel`` + ``@smp.step`` for three steps; the four chips'
shares of a layer add up to the uncut layer; the Hugging Face translator
there and back. And a stack without the norms lowers to the text it
lowered to before the attention layer had them."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_REPO, os.path.join(_REPO, "tests", "benchmark")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import lagunatiny  # noqa: E402
import mellumtiny  # noqa: E402
from benchmark import laguna_weights, loader, mellum_weights  # noqa: E402
from benchmark.reference import laguna as shared  # noqa: E402
from benchmark.reference import mellum as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import moe, transformer  # noqa: E402
from smdistributed_modelparallel_tpu.nn.huggingface import (  # noqa: E402
    laguna,
    mellum,
)

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "mellum_moe.py"),
    "mellum_moe_for_tests")


@pytest.fixture(autouse=True)
def one_device_mesh():
    """Every test starts on a mesh of one device. ``smp.reset()`` keeps the
    mesh of the last ``smp.init``, so a tp mesh that an earlier file's test
    left behind in this worker would box the parameters (``.../value``),
    and the lowered text depends on the mesh's sharding constraints."""
    import smdistributed_modelparallel_tpu as smp

    smp.reset()
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    yield
    smp.reset()


def flat_of(tree):
    from smdistributed_modelparallel_tpu.module_manager import path_key

    return {path_key(path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def unflatten(flat, like):
    from smdistributed_modelparallel_tpu.module_manager import path_key

    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_key(path)] for path, _ in paths])


def model_and_reference(cfg, seed=0, T=24):
    """The program's module with seeded weights, the same weights under the
    reference's names, and ids."""
    module = builder.module(cfg)
    ids = jax.random.randint(jax.random.key(seed), (2, T), 0,
                             cfg["vocab_size"])
    shapes = jax.eval_shape(module.init, jax.random.key(0), ids)["params"]
    w = jax.jit(lambda s: mellum_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, ids


# ------------------------------------------------- the plan and the stack

def test_plan_is_three_window_layers_and_a_full_one():
    cfg = mellumtiny.config()
    pattern, kinds = mellum_weights.plan(cfg)
    assert pattern == ("window", "window", "window", "full")
    assert transformer.pattern_segments(pattern) == [
        (1, [("window", 3)]), (1, [("full", 1)])]
    for kind, window in (("window", 8), ("full", None)):
        kw = kinds[kind]
        assert kw["qk_norm"] is True and "head_gate" not in kw
        assert kw["window_size"] == window
        assert (kw["num_attention_heads"], kw["num_key_value_heads"]) == (4, 1)
        assert kw["rotary_dim"] == 8 and kw["rotary_emb_base"] == 500000.0
        assert kw["moe_shared_intermediate_size"] == 0
        assert kw["moe_routed_scaling"] == 1.0 and kw["moe_norm_topk"]
        assert (kw["num_experts"], kw["moe_top_k"], kw["moe_held"]) == \
            (16, 4, (4, 4))
    assert kinds["window"]["rotary_yarn"] is None
    assert kinds["full"]["rotary_yarn"][:2] == (4.0, 16)


def test_published_plan_at_full_depth():
    """The committed file seen whole: 28 layers in seven periods, YaRN's
    published attention factor, every layer routed."""
    import json

    with open(os.path.join(_REPO, mellumtiny.CONFIG)) as f:
        cfg = json.load(f)
    whole = dict(cfg, layer_types=(cfg["layer_types"] * 7),
                 mlp_layer_types=["sparse"] * 28, num_attention_heads=32,
                 num_key_value_heads=4, num_experts=64)
    pattern, kinds = mellum.layer_plan(whole)
    assert len(pattern) == 28 and pattern[:4] == (
        "window", "window", "window", "full")
    assert transformer.pattern_segments(pattern) == [
        (7, [("window", 3), ("full", 1)])]
    assert kinds["full"]["rotary_yarn"] == (
        16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert kinds["window"]["window_size"] == 1024
    assert kinds["full"]["num_experts"] == 64
    assert kinds["full"]["moe_held"] is None
    kw = mellum.config_to_smp(whole)
    assert kw["num_layers"] == 28 and kw["vocab_size"] == 24576
    assert kw["layernorm_epsilon"] == 1e-6 and kw["activation"] == "silu"
    assert not kw["tie_input_output_embedding"]


def test_plan_refuses_what_the_family_does_not_have():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    view = mellum_weights.hf_view(mellumtiny.config())
    with pytest.raises(SMPValidationError, match="grouped KV heads"):
        mellum.layer_plan(dict(view, num_key_value_heads=4))
    with pytest.raises(SMPValidationError, match="attention_bias"):
        mellum.config_to_smp(dict(view, attention_bias=True))
    with pytest.raises(SMPValidationError, match="every layer is routed"):
        mellum.layer_plan(
            dict(view, mlp_layer_types=["dense"] + ["sparse"] * 3))
    # a config that turns its windows off runs every layer full
    pattern, _ = mellum.layer_plan(dict(view, use_sliding_window=False))
    assert pattern == ("full",) * 4


# ------------------------------------- each kind of layer, and the model

KINDS = {
    "window": dict(layer_types=["sliding_attention"],
                   mlp_layer_types=["sparse"]),
    "full": dict(layer_types=["full_attention"], mlp_layer_types=["sparse"]),
    "four_layers": {},
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_layer_kind_forward_and_gradients(kind):
    cfg = mellumtiny.config(**KINDS[kind])
    module, params, w, ids = model_and_reference(cfg)
    if kind != "four_layers":
        assert mellum_weights.plan(cfg)[0] == (kind,)
    probe = jax.random.normal(jax.random.key(5), (2, ids.shape[1],
                                                  cfg["vocab_size"]))

    def program(params):
        return jnp.sum(module.apply({"params": params}, ids) * probe)

    def plain(w):
        return jnp.sum(reference.forward(cfg, w, ids)[0] * probe)

    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, ids)),
        np.asarray(reference.forward(cfg, w, ids)[0]), atol=2e-4)
    got = builder.hf_from_flat(cfg, flat_of(jax.grad(program)(params)))
    want = jax.grad(plain)(w)
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(got[name]) / scale, np.asarray(want[name]) / scale,
            atol=2e-4, err_msg=name)
    # the norms' scales take part: their gradients are not zero
    assert float(jnp.max(jnp.abs(
        want[f"model.layers.{mellum_weights.plan(cfg)[0][0]}"
             ".self_attn.q_norm.weight"]))) > 0


def test_window_really_limits_what_a_query_sees():
    for layer_type, moved in (("sliding_attention", False),
                              ("full_attention", True)):
        cfg = mellumtiny.config(
            layer_types=[layer_type], mlp_layer_types=["sparse"])
        module, params, _, ids = model_and_reference(cfg)
        other = ids.at[:, 3].set((ids[:, 3] + 1) % cfg["vocab_size"])
        a = module.apply({"params": params}, ids)[:, -1]
        b = module.apply({"params": params}, other)[:, -1]
        assert bool(jnp.any(jnp.abs(a - b) > 1e-6)) is moved


def test_four_layer_model_trains_three_steps_as_the_reference_does():
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.nn.moe import record_moe_stats

    cfg = mellumtiny.config()
    lr, steps = 1e-3, 3
    batches = jax.random.randint(jax.random.key(2), (steps, 4, 32), 0, 64)
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(optax.adamw(lr), model)
        step = builder.train_step(smp)
        step(model, batches[0])        # the init pass: parameters exist
        w = builder.hf_from_flat(cfg, flat_of(model.params))
        losses, rows = [], []
        for ids in batches:
            out = step(model, ids)
            optimizer.step()
            loss, stats = out.stack()
            losses.append(float(jnp.mean(loss)))
            summary = record_moe_stats(stats)
            assert summary["dropped"] == 0
            rows.append(summary["local"])
        word = np.uint32(0)
        want, _, _, loads = reference.follow_steps(
            *reference.hashable(cfg), dict(w), batches, word, lr, "float32",
            steps)
        np.testing.assert_allclose(losses, np.asarray(want), atol=3e-4)
        assert rows[0] == int(jnp.sum(loads))
        assert len(summary["max_over_mean"]) == 4      # four expert layers
    finally:
        smp.reset()


# ----------------------------------------------------- the shares add up

def _mellum_shares():
    """16 query heads on 4 KV heads and 16 experts over 4 shares, each its
    4 query heads on its own KV head."""
    cfg = mellumtiny.config(
        layer_types=["full_attention"], mlp_layer_types=["sparse"],
        num_attention_heads=16, num_key_value_heads=4, num_experts=16,
        experts_held_first=0)
    run, = reference.layer_runs(cfg)
    return dict(
        cfg=cfg, weights=mellum_weights, shares=4, kv_head=lambda s: s,
        layer=lambda x, lw: reference.layer(cfg, x, lw, run, "float32"))


def _sdar_shares():
    """32 query heads on 4 KV heads (the published group of 8) and 16
    experts over 8 shares, each 4 query heads on the KV head it shares
    with its neighbour, over a two-copy stream under the block-diffusion
    mask."""
    import sdartiny
    from benchmark import sdar_weights
    from benchmark.reference import sdar as sdar_reference

    cfg = sdartiny.config(
        layer_types=["full_attention"], num_attention_heads=32,
        num_key_value_heads=4, num_experts=16, experts_held_first=0)
    return dict(
        cfg=cfg, weights=sdar_weights, shares=8, kv_head=lambda s: s // 2,
        layer=lambda x, lw: sdar_reference.layer(cfg, x, lw, "float32"))


@pytest.mark.parametrize("family", [_mellum_shares, _sdar_shares],
                         ids=["mellum_4_chips", "sdar_8_chips"])
def test_the_chips_shares_add_up_to_the_uncut_layer(monkeypatch, family):
    """The guide's test of a chip's share: each share's attention output
    (its query heads on its KV head, through its rows of W_o) and each
    share's held experts' output, with the layer norms, the q/k norm
    scales and the router counted once, sum to the uncut reference's
    layer."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    case = family()
    cfg, weights_of, n = case["cfg"], case["weights"], case["shares"]
    D, hd, K, F = 32, 8, 4, 16
    H, Hkv, E = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["num_experts"])
    w = jax.jit(lambda s: weights_of.make_weights(cfg, s))(np.uint32(4))
    lw = {k[len("model.layers.full."):]: v[0] for k, v in w.items()
          if k.startswith("model.layers.full.")}
    x = jax.random.normal(jax.random.key(0), (2, 24, D))
    want, loads = case["layer"](x, lw)

    eps = cfg["rms_norm_eps"]
    kinds = weights_of.plan(cfg)[1]["full"]
    normed = shared.rms_norm(x, lw["input_layernorm.weight"], eps)
    a = "self_attn."
    rows = lambda m, s, n: m[s * n * hd:(s + 1) * n * hd]   # noqa: E731
    attended = jnp.zeros_like(x)
    for s in range(n):
        layer = transformer.DistributedAttentionLayer(
            num_attention_heads=H // n, num_key_value_heads=1,
            attention_head_size=hd, hidden_size=D, qk_norm=True,
            qk_norm_epsilon=eps, rotary_dim=kinds["rotary_dim"],
            rotary_emb_base=kinds["rotary_emb_base"],
            rotary_yarn=kinds["rotary_yarn"], gpt_neox_type_rotary=True,
            block_diffusion=kinds.get("block_diffusion"),
            causal_mask_size=64, use_qkv_bias=False,
            use_attn_dense_bias=False, attention_dropout_prob=0.0,
            hidden_dropout_prob=0.0)
        flat = laguna.attention_from_hf(
            rows(lw[a + "q_proj.weight"], s, H // n),
            rows(lw[a + "k_proj.weight"], case["kv_head"](s), 1),
            rows(lw[a + "v_proj.weight"], case["kv_head"](s), 1),
            rows(lw[a + "o_proj.weight"].T, s, H // n).T, None, hd, xp=jnp)
        flat["attention/q_norm/scale"] = lw[a + "q_norm.weight"]
        flat["attention/k_norm/scale"] = lw[a + "k_norm.weight"]
        flat = {k[len("attention/"):]: v for k, v in flat.items()}
        shapes = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]
        assert set(flat_of(shapes)) == set(flat)
        attended = attended + layer.apply(
            {"params": unflatten(flat, shapes)}, normed)
    h = x + attended
    normed = shared.rms_norm(h, lw["post_attention_layernorm.weight"], eps)
    routed, landed, held = jnp.zeros_like(x), 0, E // n
    for s in range(n):
        first = held * s
        layer = moe.DistributedDroplessMoE(
            hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
            held=(first, held))
        part = laguna.experts_from_hf(
            lw["mlp.experts.gate_proj.weight"][first:first + held],
            lw["mlp.experts.up_proj.weight"][first:first + held],
            lw["mlp.experts.down_proj.weight"][first:first + held], xp=jnp)
        part = {k[len("output/"):]: v for k, v in part.items()}
        part["router/kernel"] = lw["mlp.gate.weight"].T
        shapes = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]
        assert set(flat_of(shapes)) == set(part)       # no shared expert
        out, mut = layer.apply({"params": unflatten(part, shapes)}, normed,
                               mutable=["intermediates"])
        stats = mut["intermediates"]["moe_stats"][0]
        np.testing.assert_array_equal(
            np.asarray(stats[:held]), np.asarray(loads[first:first + held]))
        assert int(stats[held]) == 0
        landed += int(jnp.sum(stats[:held]))
        routed = routed + out
    assert landed == 2 * 24 * K            # every assignment landed once
    np.testing.assert_allclose(np.asarray(h + routed), np.asarray(want),
                               atol=3e-4)
    assert _wgrad_engaged()[""] == 0       # eight-row chunks: the products


def _engaged(gauge):
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    series = telemetry.report()["metrics"][gauge]
    return {s["labels"]["layer"]: s["value"] for s in series["series"]}


def _wgrad_engaged():
    return _engaged("smp_moe_wgrad_kernel_engaged")


def test_the_layers_weight_gradients_through_the_kernel(monkeypatch):
    """Mellum's share at tile-sized widths (16 of 64 experts, 8 a token,
    no shared expert; D 256, F 128, 1,024 tokens): chunks of 1,024 rows,
    whole row tiles, so with interpret mode forced the layer's backward
    pass sums its weight gradients in the kernel and says so; without, it
    takes the products and says that. Both give the same gradients for
    the input and every parameter, over two or more chunks a call."""
    from smdistributed_modelparallel_tpu.ops import pallas_grouped_wgrad as gw

    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 512)
    D, F, E, K, held = 256, 128, 64, 8, 16
    rows = moe._chunk_rows(1024, K, held, E)
    assert rows == 1536     # not 1,024: the even load is two of those
    layer = moe.DistributedDroplessMoE(
        hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
        held=(16, held), initializer_range=0.1)
    x = jax.random.normal(jax.random.key(0), (2, 512, D))
    params = layer.init(jax.random.key(1), x)["params"]
    probe = jax.random.normal(jax.random.key(2), x.shape)

    def grads():
        def loss(params, x):
            out, mut = layer.apply({"params": params}, x,
                                   mutable=["intermediates"])
            return jnp.sum(out * probe), mut["intermediates"]["moe_stats"][0]

        (_, stats), got = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        return got, np.asarray(stats)

    products, stats = grads()
    assert _wgrad_engaged()[""] == 0
    assert stats[held] == 0 and stats[:held].sum() > rows     # two chunks
    monkeypatch.setattr(gw, "FORCE_INTERPRET", True)
    kernel, _ = grads()
    assert _wgrad_engaged()[""] == 1
    for got, want in zip(jax.tree_util.tree_leaves(kernel),
                         jax.tree_util.tree_leaves(products)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=2e-5)
    visited = moe.record_moe_stats({"": stats[None, None]})
    visits, pairs = moe._experts_visited(stats[:held], rows)
    assert pairs == held * -(-int(stats[:held].sum()) // rows)
    assert visited["wgrad_visited_share"] == visits / pairs


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_the_layers_rows_summed_back_through_the_kernel(dtype, monkeypatch):
    """The same share of Mellum's layer, two 1,536-row chunks over 1,024
    tokens (two token tiles): with interpret mode forced the layer sums
    its routed rows back to their tokens in the kernel, forward and
    backward, and says so; without, XLA's scatter-add does and the gauge
    says that. Output and every gradient agree to the rounding of an fp32
    sum whose terms may come in another order."""
    from smdistributed_modelparallel_tpu.ops import pallas_row_scatter_add as rs

    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 512)
    D, F, E, K, held = 256, 128, 64, 8, 16
    layer = moe.DistributedDroplessMoE(
        hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
        held=(16, held), initializer_range=0.1, dtype=dtype)
    x = jax.random.normal(jax.random.key(0), (2, 512, D), dtype)
    params = layer.init(jax.random.key(1), x)["params"]
    probe = jax.random.normal(jax.random.key(2), x.shape)

    def run():
        def loss(params, x):
            out, mut = layer.apply({"params": params}, x,
                                   mutable=["intermediates"])
            return (jnp.sum(out.astype(jnp.float32) * probe),
                    (out, mut["intermediates"]["moe_stats"][0]))

        (_, (out, stats)), got = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(params, x)
        return (out, got), np.asarray(stats)

    scatter, stats = run()
    assert _engaged("smp_moe_combine_kernel_engaged")[""] == 0
    rows = moe._chunk_rows(1024, K, held, E)
    assert stats[held] == 0 and stats[:held].sum() > rows     # two chunks
    monkeypatch.setattr(rs, "FORCE_INTERPRET", True)
    calls = []
    real = rs.row_scatter_add
    monkeypatch.setattr(
        rs, "row_scatter_add",
        lambda *a, **k: calls.append(len(a) == 5) or real(*a, **k))
    kernel, _ = run()
    assert _engaged("smp_moe_combine_kernel_engaged")[""] == 1
    assert _wgrad_engaged()[""] == 0          # the other kernel stood aside
    assert sorted(calls) == [False, True]     # dx without weights, out with
    for got, want in zip(jax.tree_util.tree_leaves(kernel),
                         jax.tree_util.tree_leaves(scatter)):
        assert got.dtype == want.dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        scale = float(np.max(np.abs(want))) + 1e-6
        np.testing.assert_allclose(got / scale, want / scale, atol=2e-6)


def test_chunks_are_a_third_of_an_even_routers_load(monkeypatch):
    """Laguna's share keeps the chunk it had; Mellum's, whose even load is
    exactly sixteen of those, takes three larger ones, so a call a few
    rows over or under the even load runs the same chunks."""
    assert moe._chunk_rows(8192, 10, 8, 256) == 1024        # 2,560 rows
    assert moe._chunk_rows(8192, 8, 16, 64) == 6144         # 16,384 rows
    for load in (15_800, 16_384, 16_385, 16_950):           # seen by seed
        assert -(-load // 6144) == 3
    assert moe._chunk_rows(1, 1, 1, 64) == 1024             # never less
    # an even load that would end on a chunk's edge takes the next size:
    # 4 of 64 a token on 8 held over 8,192 tokens, 4,096 rows, is two
    # chunks of 2,048 by thirds, and a call a few rows over ran a third
    assert moe._chunk_rows(8192, 4, 8, 64) == 3072
    for load in (3_850, 4_096, 4_097, 4_400):
        assert -(-load // 3072) == 2
    # ... and the next again while it still would: the same share over
    # 4,096 tokens, 2,048 rows, is one chunk of 1,024 by thirds, then one of
    # 2,048 that a call a few rows over would overflow, then 3,072
    assert moe._chunk_rows(4096, 4, 8, 64) == 3072
    for load in (1_900, 2_048, 2_049, 2_300):
        assert -(-load // 3072) == 1
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    assert moe._chunk_rows(48, 4, 4, 16) == 32     # 48 rows: 16, 24, then 32


# -------------------------------------------------- q/k norms on and off

def attention(qk_norm):
    return transformer.DistributedAttentionLayer(
        num_attention_heads=4, num_key_value_heads=1, attention_head_size=8,
        hidden_size=32, qk_norm=qk_norm, rotary_dim=8,
        gpt_neox_type_rotary=True, causal_mask_size=32, use_qkv_bias=False,
        use_attn_dense_bias=False, attention_dropout_prob=0.0,
        hidden_dropout_prob=0.0, initializer_range=0.5)


def test_qk_norm_on_and_off_differ():
    x = jax.random.normal(jax.random.key(0), (2, 16, 32))
    on, off = attention(True), attention(False)
    params = on.init(jax.random.key(1), x)["params"]
    assert params["q_norm"]["scale"].shape == (8,)
    assert params["k_norm"]["scale"].shape == (8,)
    bare = {k: v for k, v in params.items() if not k.endswith("_norm")}
    assert set(flat_of(off.init(jax.random.key(1), x)["params"])) == \
        set(flat_of(bare))
    with_norm = on.apply({"params": params}, x)
    without = off.apply({"params": bare}, x)
    assert float(jnp.max(jnp.abs(with_norm - without))) > 1e-2
    # the norm is over the head size, one head at a time: scaling one query
    # head's kernel changes nothing (its rms divides it out again)
    scaled = dict(params)
    scaled["query/kernel"] = params["query/kernel"].at[:, 2].multiply(3.0)
    np.testing.assert_allclose(
        np.asarray(on.apply({"params": scaled}, x)), np.asarray(with_norm),
        atol=1e-4)
    assert float(jnp.max(jnp.abs(
        off.apply({"params": {k: scaled[k] for k in bare}}, x)
        - without))) > 1e-2


def test_qk_norm_ops_carry_their_scope_forward_and_backward():
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    x = jnp.ones((1, 16, 32))
    layer = attention(True)
    params = layer.init(jax.random.key(1), x)["params"]

    def loss(p):
        with jax.named_scope("smp/attn/full"):
            return jnp.sum(layer.apply({"params": p}, x))

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    names = [line for line in text.split("\n") if "smp/attn/qk_norm" in line]
    assert any("transpose(" in line for line in names)
    assert any("transpose(" not in line for line in names)
    op_name = "jit(f)/jvp(smp/layer/full/smp/attn/full/smp/attn/qk_norm/" \
              "q_norm)/mul"
    assert hlo_audit.scopes_of(op_name) == (
        "smp/layer/full", "smp/attn/full", "smp/attn/qk_norm")
    assert hlo_audit.scope_of(op_name) == "smp/attn/qk_norm"


# sha256 of the StableHLO text that jax.grad of the tiny five-layer Laguna
# model (tests/benchmark/lagunatiny.py: grouped KV heads, gates, the
# patterned stack, the dropless expert layer) lowered to on the parent
# commit of the PR that gave the attention layer its q/k norms (6c343d6),
# under the matmul precision conftest.py pins and a mesh of one device,
# taken again when PR 43 wrote the expert layer's backward chunk out by
# hand (``nn/moe.py::_chunk_grads``: it held to 0db2c0e8... until then,
# and that chain is all that differs).
_LAGUNA_LOWERED_BEFORE = (
    "88ffcea0281c209eb92f6084b07f03df29f17dc3a8ce23463adc42762febd6cc")


def test_a_stack_without_the_norms_lowers_as_before():
    cfg = lagunatiny.config()
    module = transformer.DistributedTransformerLMHead(
        **laguna.config_to_smp(laguna_weights.hf_view(cfg)))
    ids = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]

    def loss(p, ids):
        return jnp.sum(module.apply({"params": p}, ids).astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _LAGUNA_LOWERED_BEFORE


# ---------------------------------------------------------- the translator

def test_translator_there_and_back():
    cfg = mellumtiny.config()
    view = mellum_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = mellum.translate_state_dict_to_hf(flat, view)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (4 * 8, 32)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (8, 32)
    assert sd["model.layers.3.self_attn.q_norm.weight"].shape == (8,)
    assert sd["model.layers.3.self_attn.k_norm.weight"].shape == (8,)
    assert sd["model.layers.2.mlp.gate.weight"].shape == (16, 32)
    assert not any("g_proj" in k or "shared_expert" in k for k in sd)
    # the held experts keep their published indices 4 .. 7
    assert "model.layers.2.mlp.experts.4.up_proj.weight" in sd
    assert "model.layers.2.mlp.experts.3.up_proj.weight" not in sd
    assert "model.layers.2.mlp.experts.8.up_proj.weight" not in sd
    back = mellum.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_mellum_is_a_registered_family():
    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("MellumForCausalLM")
    assert family.name == "mellum"
    assert huggingface.family_for("mellum") is family
    assert family.config_to_smp is mellum.config_to_smp
    assert huggingface.family_for("laguna").name == "laguna"
