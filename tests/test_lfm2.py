"""LFM2's layers in the program, at small sizes on the CPU, seeded random
weights, against the plain reference (``benchmark/reference/lfm2.py``) and
against loops written out here: the gated short convolution (forward,
gradient, zeros before position 0, tp 2 on the host mesh equal to tp 1),
the sigmoid routing law with a bias that moves the selection and not the
weights and that no step moves, each kind of layer and the five-layer
model through ``DistributedModel`` + ``@smp.step`` with and without
``activation_checkpointing``, the eight chips' shares of a routed layer
adding up to the uncut layer, the Hugging Face translator there and
back."""

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

import lfm2tiny  # noqa: E402
from benchmark import lfm2_weights, loader  # noqa: E402
from benchmark.reference import laguna as shared  # noqa: E402
from benchmark.reference import lfm2 as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import (  # noqa: E402
    conv,
    moe,
    transformer,
)
from smdistributed_modelparallel_tpu.nn.huggingface import (  # noqa: E402
    laguna,
    lfm2_moe,
)

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "lfm2_moe.py"),
    "lfm2_moe_for_tests")


@pytest.fixture(autouse=True)
def one_device_mesh():
    """Every test starts on a mesh of one device (``test_mellum.py`` says
    why)."""
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
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, ids


# ----------------------------------------------- the short convolution

def loop_conv(x, in_kernel, taps, out_kernel):
    """The mixer position by position and tap by tap, in numpy."""
    x, in_kernel, taps, out_kernel = (
        np.asarray(a, np.float64) for a in (x, in_kernel, taps, out_kernel))
    B, T, D = x.shape
    K = taps.shape[0]
    out = np.zeros((B, T, D))
    for b in range(B):
        streams = np.einsum("td,dsc->tsc", x[b], in_kernel)
        gate_in, gate_out, u = streams[:, 0], streams[:, 1], streams[:, 2]
        v = gate_in * u
        for t in range(T):
            c = np.zeros(D)
            for j in range(K):
                back = K - 1 - j
                if t - back >= 0:            # zeros before position 0
                    c += taps[j] * v[t - back]
            out[b, t] = (gate_out[t] * c) @ out_kernel
    return out


def conv_layer(**fields):
    return conv.DistributedShortConv(hidden_size=16, **fields)


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_is_the_written_out_loop(taps):
    layer = conv_layer(kernel_size=taps, initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (2, 9, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    assert {k: v.shape for k, v in params.items()} == {
        "in_proj/kernel": (16, 3, 16), "conv/kernel": (taps, 16),
        "out_proj/kernel": (16, 16)}
    want = loop_conv(x, params["in_proj/kernel"], params["conv/kernel"],
                     params["out_proj/kernel"])
    np.testing.assert_allclose(
        np.asarray(layer.apply({"params": params}, x)), want, atol=1e-4)


def test_short_conv_is_causal_and_reads_its_taps_alone():
    """Position t reads positions t - 2 .. t: a change at position 4 moves
    outputs 4, 5, 6 and no other; position 0 sees zeros before it."""
    layer = conv_layer(initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (1, 10, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    a = layer.apply({"params": params}, x)
    b = layer.apply({"params": params}, x.at[:, 4].add(1.0))
    moved = np.asarray(jnp.any(jnp.abs(a - b) > 1e-6, axis=-1))[0]
    assert moved.tolist() == [t in (4, 5, 6) for t in range(10)]
    # the first output is the last tap's alone
    alone = layer.apply({"params": params}, x[:, :1])
    np.testing.assert_allclose(np.asarray(a[:, :1]), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)


def test_short_conv_gradients_against_the_loops():
    """Autodiff of the layer against finite differences of the loop."""
    layer = conv_layer(initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (1, 6, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    probe = np.asarray(jax.random.normal(jax.random.key(2), (1, 6, 16)),
                       np.float64)

    grads = jax.grad(lambda p, x: jnp.sum(
        layer.apply({"params": p}, x) * probe), argnums=(0, 1))(params, x)

    def plain(p, x):
        return float(np.sum(loop_conv(
            x, p["in_proj/kernel"], p["conv/kernel"], p["out_proj/kernel"])
            * probe))

    rng = np.random.default_rng(0)
    host = {k: np.asarray(v, np.float64) for k, v in params.items()}
    for name, grad in [*grads[0].items(), ("x", grads[1])]:
        for _ in range(4):
            index = tuple(rng.integers(0, n) for n in grad.shape)
            step = np.zeros(grad.shape)
            step[index] = 1e-4
            up = dict(host) if name != "x" else host
            xs = [np.asarray(x, np.float64)] * 2
            if name == "x":
                xs = [xs[0] + step, xs[1] - step]
                high, low = plain(host, xs[0]), plain(host, xs[1])
            else:
                high = plain(dict(up, **{name: host[name] + step}), xs[0])
                low = plain(dict(up, **{name: host[name] - step}), xs[0])
            np.testing.assert_allclose(
                float(grad[index]), (high - low) / 2e-4, rtol=2e-3,
                atol=2e-4, err_msg=f"{name}{index}")


def test_short_conv_under_tp_2_is_tp_1():
    """The host mesh at tp 2: channels of the three streams and the taps
    split, the output projection's partial products summed."""
    import smdistributed_modelparallel_tpu as smp

    layer = conv_layer(initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (2, 8, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    want = layer.apply({"params": params}, x)
    want_grad = jax.grad(lambda p: jnp.sum(jnp.square(
        layer.apply({"params": p}, x))))(params)
    smp.reset()
    smp.init({"tensor_parallel_degree": 2, "ddp": True, "microbatches": 1},
             devices=jax.devices()[:2])
    boxed = layer.init(jax.random.key(1), x)["params"]
    specs = {k: v.names for k, v in boxed.items()}
    assert specs == {"in_proj/kernel": (None, None, "tp"),
                     "conv/kernel": (None, "tp"),
                     "out_proj/kernel": ("tp", None)}
    from jax.sharding import NamedSharding, PartitionSpec as P

    from smdistributed_modelparallel_tpu.backend.state import state

    placed = {k: jax.device_put(params[k], NamedSharding(
        state.mesh, P(*specs[k]))) for k in params}
    with jax.set_mesh(state.mesh):
        got = jax.jit(lambda p, x: layer.apply({"params": p}, x))(placed, x)
        got_grad = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
            layer.apply({"params": p}, x)))))(placed)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    for name in want_grad:
        scale = float(jnp.max(jnp.abs(want_grad[name])))
        np.testing.assert_allclose(
            np.asarray(got_grad[name]) / scale,
            np.asarray(want_grad[name]) / scale, atol=1e-5, err_msg=name)


def test_conv_core_bytes_count_eleven_tensors():
    assert conv.conv_core_bytes(100, 16, 2) == {
        "fwd": 4 * 100 * 16 * 2, "bwd": 7 * 100 * 16 * 2}


def test_conv_ops_carry_their_scopes_forward_and_backward():
    from smdistributed_modelparallel_tpu.utils import hlo_audit, profiling

    layer = conv_layer()
    x = jnp.ones((1, 8, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        layer.apply({"params": p}, x))))).lower(params).as_text(
            debug_info=True)
    for part in ("in_proj", "core", "out_proj"):
        scope = f"smp/conv/{part}"
        assert scope in profiling.SCOPES
        lines = [line for line in text.split("\n") if scope in line]
        assert any("transpose(" in line for line in lines), scope
        assert any("transpose(" not in line for line in lines), scope
    op_name = "jit(f)/jvp(smp/layer/conv/smp/conv/core)/mul"
    assert hlo_audit.scopes_of(op_name) == ("smp/layer/conv", "smp/conv/core")


def test_conv_kind_refuses_decode():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    layer = transformer.DistributedTransformerLayer(
        num_attention_heads=2, attention_head_size=8, hidden_size=16,
        intermediate_size=32, conv_mixer=3, decode=True)
    with pytest.raises(SMPValidationError, match="decode"):
        layer.init(jax.random.key(0), jnp.ones((1, 4, 16)))


# --------------------------------------------------- the routing law

def routed_layer(**fields):
    return moe.DistributedDroplessMoE(
        hidden_size=16, intermediate_size=8, num_experts=8, top_k=2,
        **fields)


def plain_routed(x, params, bias, sigmoid=True):
    """The equations of the issue on [N, D] rows, experts one by one."""
    x = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    logits = x @ np.asarray(params["router/kernel"], np.float64)
    scores = 1 / (1 + np.exp(-logits))
    order = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :2]
    chosen = np.take_along_axis(scores, order, axis=-1)
    weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    gate_up = np.asarray(params["experts/gate_up/kernel"], np.float64)
    down = np.asarray(params["experts/down/kernel"], np.float64)
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        for k in range(2):
            e = order[n, k]
            gate, up = x[n] @ gate_up[e, :, 0], x[n] @ gate_up[e, :, 1]
            out[n] += weights[n, k] * ((gate / (1 + np.exp(-gate)) * up)
                                       @ down[e])
    return out, order


def test_sigmoid_law_with_a_bias_is_the_equations(monkeypatch):
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    layer = routed_layer(score="sigmoid", selection_bias=True,
                         initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (2, 12, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    assert params["router/selection_bias"].shape == (8,)
    assert float(jnp.max(jnp.abs(params["router/selection_bias"]))) == 0.0
    bias = np.zeros(8)
    bias[5] = 10.0                       # expert 5 is chosen by every token
    bias[2] = -10.0                      # and expert 2 by none
    for b in (np.zeros(8), bias):
        with_bias = dict(params, **{
            "router/selection_bias": jnp.asarray(b, jnp.float32)})
        want, order = plain_routed(x, params, b)
        got = layer.apply({"params": with_bias}, x)
        np.testing.assert_allclose(
            np.asarray(got).reshape(-1, 16), want, atol=2e-4)
    assert (order == 5).any(axis=-1).all() and not (order == 2).any()


def test_bias_moves_the_selection_and_not_the_weights(monkeypatch):
    """Under a bias that is the same for every expert the selection and
    the weights stay as they were: it is added to the scores the top-k
    reads and to nothing the output reads."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    layer = routed_layer(score="sigmoid", selection_bias=True,
                         initializer_range=0.5)
    x = jax.random.normal(jax.random.key(0), (1, 12, 16))
    params = layer.init(jax.random.key(1), x)["params"]
    shifted = dict(params, **{
        "router/selection_bias": jnp.full((8,), 3.0, jnp.float32)})
    np.testing.assert_array_equal(
        np.asarray(layer.apply({"params": params}, x)),
        np.asarray(layer.apply({"params": shifted}, x)))


def test_softmax_law_is_untouched_and_the_law_is_checked(monkeypatch):
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    x = jax.random.normal(jax.random.key(0), (1, 12, 16))
    plain = routed_layer(initializer_range=0.5)
    params = plain.init(jax.random.key(1), x)["params"]
    assert set(params) == {"router/kernel", "experts/gate_up/kernel",
                           "experts/down/kernel"}
    sigmoid = routed_layer(score="sigmoid", initializer_range=0.5)
    assert set(sigmoid.init(jax.random.key(1), x)["params"]) == set(params)
    assert float(jnp.max(jnp.abs(
        plain.apply({"params": params}, x)
        - sigmoid.apply({"params": params}, x)))) > 1e-4
    with pytest.raises(SMPValidationError, match="neither"):
        routed_layer(score="tanh").init(jax.random.key(1), x)


def test_bias_gets_no_gradient_and_no_update_through_the_optimizer():
    """Three AdamW steps (weight decay and all) through ``@smp.step`` and
    ``DistributedOptimizer``: every selection bias stays as loaded to the
    bit, its first moment stays zero, and its neighbours move."""
    import optax

    import smdistributed_modelparallel_tpu as smp

    cfg = lfm2tiny.config()
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(
            optax.adamw(1e-2, weight_decay=0.1), model)
        step = builder.train_step(smp)
        ids = jax.random.randint(jax.random.key(2), (4, 32), 0, 64)
        step(model, ids)               # the init pass: parameters exist
        w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(3))
        model.load_state_dict(builder.flat_from_hf(cfg, w))
        del w                          # the step gives the buffers up
        before = {k: np.asarray(v) for k, v in flat_of(model.params).items()}
        biases = [k for k in before if k.endswith("router/selection_bias")]
        assert len(biases) == 2 and all(
            np.abs(before[k]).max() > 0 for k in biases)
        for _ in range(3):
            step(model, ids)
            optimizer.step()
        after = {k: np.asarray(v) for k, v in flat_of(model.params).items()}
        for key in biases:
            np.testing.assert_array_equal(after[key], before[key])
            router = key.replace("selection_bias", "kernel")
            assert np.abs(after[router] - before[router]).max() > 1e-3
        moments = flat_of(optimizer.opt_state)
        held = [k for k in moments if k.endswith("router/selection_bias")]
        assert len(held) == 4          # mu and nu of the two leaves
        assert all(float(jnp.max(jnp.abs(moments[k]))) == 0.0 for k in held)
    finally:
        smp.reset()


def test_router_law_gauge_says_which_law():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    x = jnp.ones((1, 8, 16))
    for fields, law in ((dict(), 0),
                        (dict(score="sigmoid", selection_bias=True), 1)):
        layer = routed_layer(**fields)
        layer.init(jax.random.key(0), x)
        series = telemetry.report()["metrics"]["smp_moe_router_law"]["series"]
        assert {s["labels"]["layer"]: s["value"] for s in series}[""] == law


# ------------------------------------------------- the plan and the stack

def test_plan_names_each_kind_by_its_mixer():
    cfg = lfm2tiny.config()
    pattern, kinds = lfm2_weights.plan(cfg)
    assert pattern == ("lead_dense_conv", "full", "conv", "conv", "conv")
    assert transformer.pattern_segments(pattern) == [
        (1, [("lead_dense_conv", 1)]), (1, [("full", 1)]),
        (1, [("conv", 3)])]
    lead, full, mixed = (kinds[k] for k in ("lead_dense_conv", "full",
                                            "conv"))
    assert lead == {"conv_mixer": 3, "intermediate_size": 48,
                    "num_experts": 0}
    assert full["qk_norm"] is True and "conv_mixer" not in full
    assert (full["num_attention_heads"], full["num_key_value_heads"],
            full["window_size"], full["rotary_dim"],
            full["rotary_emb_base"]) == (4, 1, None, 8, 1000000.0)
    assert mixed["conv_mixer"] == 3 and "num_attention_heads" not in mixed
    for kw in (full, mixed):
        assert (kw["num_experts"], kw["moe_top_k"], kw["moe_held"]) == \
            (16, 4, (4, 4))
        assert (kw["moe_score"], kw["moe_selection_bias"],
                kw["moe_norm_topk"], kw["moe_routed_scaling"],
                kw["moe_shared_intermediate_size"]) == (
                    "sigmoid", True, True, 1.0, 0)
    kw = lfm2_moe.config_to_smp(lfm2_weights.hf_view(cfg))
    assert kw["tie_input_output_embedding"] and kw["final_layernorm"]
    assert kw["layernorm_epsilon"] == 1e-5 and kw["layernorm_type"] == "rms"
    assert kw["attention_head_size"] == 8 and kw["num_layers"] == 5


def test_published_plan_at_full_depth():
    """The catalog's row seen whole: two leading conv layers with the dense
    MLP, then attention at every index 2 mod 4."""
    import json

    with open(os.path.join(_REPO, lfm2tiny.CONFIG)) as f:
        cfg = json.load(f)
    types = ["full_attention" if i % 4 == 2 else "conv" for i in range(40)]
    whole = dict(cfg, layer_types=types, num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=8,
                 num_experts=64, vocab_size=65536)
    whole.pop("head_dim")
    pattern, kinds = lfm2_moe.layer_plan(whole)
    assert pattern[:7] == ("lead_dense_conv", "lead_dense_conv", "full",
                           "conv", "conv", "conv", "full")
    assert pattern.count("full") == 10 and pattern.count("conv") == 28
    assert transformer.pattern_segments(pattern) == [
        (1, [("lead_dense_conv", 2)]), (9, [("full", 1), ("conv", 3)]),
        (1, [("full", 1)]), (1, [("conv", 1)])]
    assert kinds["full"]["rotary_dim"] == 64 and \
        kinds["full"]["moe_held"] is None
    assert kinds["lead_dense_conv"]["intermediate_size"] == 11776
    kw = lfm2_moe.config_to_smp(whole)
    assert kw["attention_head_size"] == 64 and kw["vocab_size"] == 65536


def test_plan_refuses_what_the_family_does_not_have():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    view = lfm2_weights.hf_view(lfm2tiny.config())
    with pytest.raises(SMPValidationError, match="conv_bias"):
        lfm2_moe.layer_plan(dict(view, conv_bias=True))
    with pytest.raises(SMPValidationError, match="neither"):
        lfm2_moe.layer_plan(dict(view, layer_types=["sliding_attention"] * 5))


def test_conv_mixers_gauge_counts_the_layers_by_kind():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = lfm2tiny.config()
    module = builder.module(cfg)
    jax.eval_shape(module.init, jax.random.key(0),
                   jnp.zeros((1, 8), jnp.int32))
    series = telemetry.report()["metrics"]["smp_conv_mixers"]["series"]
    assert {s["labels"]["kind"]: s["value"] for s in series} == {
        "lead_dense_conv": 1, "conv": 3}


# ------------------------------------- each kind of layer, and the model

KINDS = {
    "lead_dense_conv": dict(layer_types=["conv"], num_dense_layers=1),
    "full": dict(layer_types=["full_attention"], num_dense_layers=0),
    "conv": dict(layer_types=["conv"], num_dense_layers=0),
    "five_layers": {},
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_layer_kind_forward_and_gradients(kind, monkeypatch):
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    cfg = lfm2tiny.config(**KINDS[kind])
    module, params, w, ids = model_and_reference(cfg)
    if kind != "five_layers":
        assert lfm2_weights.plan(cfg)[0] == (kind,)
    probe = jax.random.normal(jax.random.key(5), (2, ids.shape[1],
                                                  cfg["vocab_size"]))

    def program(params):
        return jnp.sum(module.apply({"params": params}, ids) * probe)

    def plain(w):
        return jnp.sum(reference.forward(cfg, w, ids)[0] * probe)

    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, ids)),
        np.asarray(reference.forward(cfg, w, ids)[0]), atol=1e-3)
    got = builder.hf_from_flat(cfg, flat_of(jax.grad(program)(params)))
    want = jax.grad(plain)(w)
    assert set(got) == set(want) == set(lfm2_weights.spec_for(cfg))
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(got[name]) / scale, np.asarray(want[name]) / scale,
            atol=5e-4, err_msg=name)
        if name.endswith("expert_bias"):
            assert float(jnp.max(jnp.abs(got[name]))) == 0.0
        else:
            assert float(jnp.max(jnp.abs(want[name]))) > 0, name


@pytest.mark.parametrize("checkpointing", [False, True],
                         ids=["kept", "checkpointed"])
def test_five_layer_model_trains_three_steps_as_the_reference_does(
        checkpointing):
    import optax

    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.nn.moe import record_moe_stats

    cfg = lfm2tiny.config(
        module={"activation_checkpointing": checkpointing})
    lr, steps = 1e-3, 3
    batches = jax.random.randint(jax.random.key(2), (steps, 4, 32), 0, 64)
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(optax.adamw(lr), model)
        step = builder.train_step(smp)
        step(model, batches[0])        # the init pass: parameters exist
        make = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))
        w = make(np.uint32(0))
        # a second copy: the step gives the loaded buffers up
        model.load_state_dict(builder.flat_from_hf(cfg, make(np.uint32(0))))
        losses, rows = [], []
        for ids in batches:
            out = step(model, ids)
            optimizer.step()
            loss, stats = out.stack()
            losses.append(float(jnp.mean(loss)))
            summary = record_moe_stats(stats)
            assert summary["dropped"] == 0
            rows.append(summary["local"])
        want, first_grad, change, loads = reference.follow_steps(
            *reference.hashable(cfg), dict(w), batches, np.uint32(0), lr,
            "float32", steps)
        np.testing.assert_allclose(losses, np.asarray(want), rtol=2e-5)
        assert rows[0] == int(jnp.sum(loads)) and loads.shape == (4, 4)
        assert len(summary["max_over_mean"]) == 4      # four routed layers
        got = builder.hf_from_flat(cfg, flat_of(model.params))
        w = make(np.uint32(0))         # the reference gave its copy up too
        for name, norm in change.items():
            moved = float(jnp.sqrt(jnp.sum(jnp.square(got[name] - w[name]))))
            if name.endswith("expert_bias"):
                # (the reference's norm for it is its constant distance
                # from the leaf as ``weights.make_leaf`` makes it)
                assert moved == 0.0 and float(first_grad[name]) == 0.0
                continue
            assert moved == pytest.approx(float(norm), rel=2e-2, abs=1e-6), \
                name
    finally:
        smp.reset()


# ----------------------------------------------------- the shares add up

def test_the_eight_chips_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The guide's test of a chip's share, on one routed layer whose mixer
    is the convolution (counted once: its channels are a width and every
    chip's tensor-parallel part of it sums to the whole by the
    projection's linearity, which ``test_short_conv_under_tp_2_is_tp_1``
    holds): each share's held experts' output under the one router and the
    one bias, with the norms and the mixer counted once, sums to the uncut
    reference's layer, and every assignment lands on exactly one share."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    n, D, K, F, E = 8, 32, 4, 16, 16
    cfg = lfm2tiny.config(layer_types=["conv"], num_dense_layers=0,
                          num_experts=E, experts_held_first=0)
    w = jax.jit(lambda s: lfm2_weights.make_weights(cfg, s))(np.uint32(4))
    lw = {k[len("model.layers.conv."):]: v[0] for k, v in w.items()
          if k.startswith("model.layers.conv.")}
    x = jax.random.normal(jax.random.key(0), (2, 24, D))
    run, = reference.layer_runs(cfg)
    want, loads = reference.layer(cfg, x, lw, run, "float32")

    eps = cfg["norm_eps"]
    mixer = conv.DistributedShortConv(hidden_size=D)
    mixed = mixer.apply({"params": {
        k[len("conv/"):]: v for k, v in lfm2_moe.conv_from_hf(
            lw["conv.in_proj.weight"], lw["conv.conv.weight"],
            lw["conv.out_proj.weight"]).items()}},
        shared.rms_norm(x, lw["operator_norm.weight"], eps))
    h = x + mixed
    normed = shared.rms_norm(h, lw["ffn_norm.weight"], eps)
    m = "feed_forward."
    routed, landed, held = jnp.zeros_like(x), 0, E // n
    for s in range(n):
        first = held * s
        layer = moe.DistributedDroplessMoE(
            hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
            held=(first, held), score="sigmoid", selection_bias=True)
        part = laguna.experts_from_hf(
            lw[m + "experts.w1.weight"][first:first + held],
            lw[m + "experts.w3.weight"][first:first + held],
            lw[m + "experts.w2.weight"][first:first + held], xp=jnp)
        part = {k[len("output/"):]: v for k, v in part.items()}
        part["router/kernel"] = lw[m + "gate.weight"].T
        part["router/selection_bias"] = lw[m + "expert_bias"]
        shapes = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]
        assert set(flat_of(shapes)) == set(part)
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


# ---------------------------------------------------------- the translator

def test_translator_there_and_back():
    cfg = lfm2tiny.config()
    view = lfm2_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = lfm2_moe.translate_state_dict_to_hf(flat, view)
    assert sd["model.layers.0.conv.in_proj.weight"].shape == (3 * 32, 32)
    assert sd["model.layers.0.conv.conv.weight"].shape == (32, 1, 3)
    assert sd["model.layers.0.conv.out_proj.weight"].shape == (32, 32)
    assert sd["model.layers.0.feed_forward.w1.weight"].shape == (48, 32)
    assert sd["model.layers.0.feed_forward.w2.weight"].shape == (32, 48)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (4 * 8, 32)
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (8, 32)
    assert sd["model.layers.1.self_attn.out_proj.weight"].shape == (32, 32)
    assert sd["model.layers.1.self_attn.q_layernorm.weight"].shape == (8,)
    assert sd["model.layers.3.feed_forward.gate.weight"].shape == (16, 32)
    assert sd["model.layers.3.feed_forward.expert_bias"].shape == (16,)
    assert sd["model.embedding_norm.weight"].shape == (32,)
    assert "lm_head.weight" not in sd
    assert {k.split(".", 3)[3] for k in sd if k.startswith(
        "model.layers.2.")} >= {"operator_norm.weight", "ffn_norm.weight"}
    # the held experts keep their published indices 4 .. 7
    assert "model.layers.2.feed_forward.experts.4.w3.weight" in sd
    assert "model.layers.2.feed_forward.experts.3.w3.weight" not in sd
    assert "model.layers.2.feed_forward.experts.8.w3.weight" not in sd
    # the streams of in_proj are B, C, x in that order: rows D .. 2D - 1
    # are the second gate's
    np.testing.assert_array_equal(
        sd["model.layers.0.conv.in_proj.weight"][32:64],
        flat["transformer/seq_layers_0_lead_dense_conv/layer/conv/in_proj/"
             "kernel"][0, :, 1].T)
    back = lfm2_moe.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_lfm2_moe_is_a_registered_family():
    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("Lfm2MoeForCausalLM")
    assert family.name == "lfm2moe"
    assert huggingface.family_for("lfm2_moe") is family
    assert family.config_to_smp is lfm2_moe.config_to_smp
    assert huggingface.family_for("mellum").name == "mellum"
