"""Laguna's layers in the program, at small sizes on the CPU, seeded random
weights, against the plain reference (``benchmark/reference/laguna.py``):
grouped KV heads with per-head gates, YaRN and partial rotary, the stack
built from a static per-layer pattern, the dropless expert layer's share
of the experts (the shares add up; nothing is dropped under imbalance),
the five-layer model through ``DistributedModel`` + ``@smp.step``, the
Hugging Face translator there and back. And a multi-head model through the
changed attention layer lowers to the text it lowered to before."""

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
from benchmark import laguna_weights, loader  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import moe, transformer  # noqa: E402
from smdistributed_modelparallel_tpu.nn.huggingface import laguna  # noqa: E402
from smdistributed_modelparallel_tpu.nn.moe import (  # noqa: E402
    DistributedDroplessMoE,
    record_moe_stats,
)

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "laguna_moe.py"),
    "laguna_moe_for_tests")


def flat_of(tree):
    from smdistributed_modelparallel_tpu.module_manager import path_key

    return {path_key(path): leaf for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}


def unflatten(flat, like):
    from smdistributed_modelparallel_tpu.module_manager import path_key

    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    return jax.tree_util.tree_unflatten(
        treedef, [flat[path_key(path)] for path, _ in paths])


# ------------------------------------------------------------- the pattern

@pytest.mark.parametrize("pattern,segments", [
    (("a",) * 4, [(1, [("a", 4)])]),
    (("d", "w", "w", "w", "f"),
     [(1, [("d", 1)]), (1, [("w", 3)]), (1, [("f", 1)])]),
    (("d",) + ("w", "w", "w", "f") * 2 + ("w", "w", "w"),
     [(1, [("d", 1)]), (2, [("w", 3), ("f", 1)]), (1, [("w", 3)])]),
    (("f", "w") * 3, [(3, [("f", 1), ("w", 1)])]),
])
def test_pattern_segments(pattern, segments):
    assert transformer.pattern_segments(pattern) == segments
    where = transformer.pattern_layer_paths(pattern)
    assert len(where) == len(set(where)) == len(pattern)
    for kind, (path, _) in zip(pattern, where):
        assert path.endswith(f"_{kind}/layer")


def test_pattern_needs_an_entry_for_each_layer():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    kw = laguna.config_to_smp(laguna_weights.hf_view(lagunatiny.config()))
    kw["num_layers"] = 4
    with pytest.raises(SMPValidationError, match="num_layers entries"):
        transformer.DistributedTransformerLMHead(**kw).init(
            jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


# ------------------------------------------------------------------ rotary

def written_yarn(d, theta, factor, orig, beta_fast, beta_slow):
    """The formula as the issue writes it, with Python loops."""
    def dim_of(turns):
        return d * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low, high = max(np.floor(dim_of(beta_fast)), 0), \
        min(np.ceil(dim_of(beta_slow)), d - 1)
    out = []
    for i in range(d // 2):
        plain = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain / factor * ramp + plain * (1 - ramp))
    return np.array(out)


def test_yarn_frequencies_against_the_written_formula():
    got = transformer.yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    want = written_yarn(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    # high frequencies untouched, the lowest divided by the factor
    assert got[0] == 1.0 and got[-1] == pytest.approx(want[-1])
    assert want[-1] == pytest.approx(500000.0 ** (-62 / 64) / 128)
    cos, _ = reference.rotary_tables(16, 128, {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.4852030263919618,
        "partial_rotary_factor": 0.5})
    assert cos.shape == (16, 64)
    np.testing.assert_allclose(
        np.asarray(cos[3, :32]), 1.4852030263919618 * np.cos(3 * want),
        rtol=2e-5)


@pytest.mark.parametrize("rope", [
    {"rope_theta": 10000, "rope_type": "default", "partial_rotary_factor": 1},
    {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
     "original_max_position_embeddings": 16, "beta_fast": 32, "beta_slow": 1,
     "attention_factor": None, "partial_rotary_factor": 0.5},
], ids=["whole_head_plain", "half_head_yarn"])
def test_rotary_of_the_program_is_the_references(rope):
    hd, T = 16, 24
    q = jax.random.normal(jax.random.key(0), (2, T, 3, hd))
    k = jax.random.normal(jax.random.key(1), (2, T, 1, hd))
    d = int(hd * rope["partial_rotary_factor"])
    yarn = None
    if rope["rope_type"] == "yarn":
        yarn = (rope["factor"], rope["original_max_position_embeddings"],
                32.0, 1.0, 0.1 * np.log(rope["factor"]) + 1.0)
    got_q, got_k = transformer.apply_rotary(
        q, k, d, base=float(rope["rope_theta"]), neox_style=True, yarn=yarn)
    cos, sin = reference.rotary_tables(T, hd, rope)
    np.testing.assert_allclose(
        np.asarray(got_q), np.asarray(reference.rotate(q, cos, sin)),
        atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got_k), np.asarray(reference.rotate(k, cos, sin)),
        atol=2e-5)
    # the dims past the rotary part pass through
    np.testing.assert_array_equal(np.asarray(got_q[..., d:]),
                                  np.asarray(q[..., d:]))


# ------------------------------------- each kind of layer, and the model

def model_and_reference(cfg, seed=0, T=24):
    """The program's module with seeded weights, the same weights under the
    reference's names, and ids."""
    module = builder.module(cfg)
    ids = jax.random.randint(jax.random.key(seed), (2, T), 0,
                             cfg["vocab_size"])
    shapes = jax.eval_shape(module.init, jax.random.key(0), ids)["params"]
    w = jax.jit(lambda s: laguna_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, ids


KINDS = {
    "lead_dense": dict(layer_types=["full_attention"],
                       mlp_layer_types=["dense"],
                       num_attention_heads_per_layer=[4]),
    "full": dict(layer_types=["full_attention"], mlp_layer_types=["sparse"],
                 num_attention_heads_per_layer=[4]),
    "window": dict(layer_types=["sliding_attention"],
                   mlp_layer_types=["sparse"],
                   num_attention_heads_per_layer=[6]),
    "five_layers": {},
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_layer_kind_forward_and_gradients(kind):
    cfg = lagunatiny.config(**KINDS[kind])
    module, params, w, ids = model_and_reference(cfg)
    if kind != "five_layers":
        assert laguna_weights.plan(cfg)[0] == (kind,)
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


def test_window_really_limits_what_a_query_sees():
    """Changing a token more than a window back changes nothing at the
    last position of a window-only model, and something in a full one."""
    for layer_type, moved in (("sliding_attention", False),
                              ("full_attention", True)):
        cfg = lagunatiny.config(
            layer_types=[layer_type], mlp_layer_types=["sparse"],
            num_attention_heads_per_layer=[6])
        module, params, _, ids = model_and_reference(cfg)
        other = ids.at[:, 3].set((ids[:, 3] + 1) % cfg["vocab_size"])
        a = module.apply({"params": params}, ids)[:, -1]
        b = module.apply({"params": params}, other)[:, -1]
        assert bool(jnp.any(jnp.abs(a - b) > 1e-6)) is moved


def test_five_layer_model_trains_through_smp_step():
    import optax

    import smdistributed_modelparallel_tpu as smp

    cfg = lagunatiny.config()
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(optax.adamw(1e-3), model)
        step = builder.train_step(smp)
        ids = jax.random.randint(jax.random.key(2), (4, 32), 0, 64)
        losses = []
        for _ in range(3):
            out = step(model, ids)
            optimizer.step()
            loss, stats = out.stack()
            losses.append(float(jnp.mean(loss)))
        # the first step's loss is the reference's on the same weights
        # (parameters change only at optimizer.step()): make them again.
        smp.reset()
        smp.init({"microbatches": 2})
        model = smp.DistributedModel(builder.module(cfg))
        out = builder.train_step(smp)(model, ids)
        w = builder.hf_from_flat(cfg, flat_of(model.params))
        logits, loads = reference.forward(cfg, w, ids)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        want = -jnp.mean(jnp.take_along_axis(
            logp, ids[:, 1:, None], axis=-1))
        loss, stats = out.stack()
        assert float(jnp.mean(loss)) == pytest.approx(float(want), abs=2e-4)
        assert losses[2] < losses[0]
        summary = record_moe_stats(stats)
        assert summary["dropped"] == 0
        assert summary["local"] == int(jnp.sum(loads))
        assert len(summary["max_over_mean"]) == 4      # four expert layers
        # every expert layer's path is the key its counters come back under
        assert set(summary["max_over_mean"]) == {
            f"{layer}#{i}" for layer in stats for i in range(
                int(np.prod(stats[layer].shape[1:-1])))}
        assert set(stats) <= set(moe._TRACED_CHUNK_ROWS)
        assert set(stats) <= set(_wgrad_engaged())
        assert not any(_wgrad_engaged()[layer] for layer in stats)
        assert 0 < summary["wgrad_visited_share"] <= 1
        report = smp.telemetry.report()["metrics"]
        assert report["smp_moe_local_assignments"]["series"][0]["value"] \
            == summary["local"]
        assert report["smp_moe_dropped_assignments"]["series"][0]["value"] == 0
        assert len(report["smp_moe_expert_load_max_over_mean"]["series"]) == 4
    finally:
        smp.reset()


def test_two_periods_and_a_tail_run_as_one_stack():
    """Ten layers: lead, two whole periods scanned together, a tail."""
    types = (["full_attention"] + ["sliding_attention"] * 3) * 3
    cfg = lagunatiny.config(
        layer_types=types[:10], mlp_layer_types=["dense"] + ["sparse"] * 9,
        gating_types=["per_head"] * 10,
        num_attention_heads_per_layer=([4, 6, 6, 6] * 3)[:10])
    module, params, w, ids = model_and_reference(cfg)
    assert any("period" in key for key in flat_of(params))
    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, ids)),
        np.asarray(reference.forward(cfg, w, ids)[0]), atol=3e-4)


# ------------------------------------------- the expert layer's guarantees

D, F, E, K = 32, 16, 16, 4


@pytest.fixture(autouse=True)
def chunks_of_eight_rows(monkeypatch):
    """Several chunks of sorted rows at these sizes."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)


def expert_layer(held):
    return DistributedDroplessMoE(
        hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
        held=held, shared_intermediate_size=F, routed_scaling=2.5,
        initializer_range=0.5)


def reference_layer(params, x, first, count):
    cfg = {"num_experts_per_tok": K, "moe_routed_scaling_factor": 2.5,
           "experts_held_first": first}
    gate_up = params["experts/gate_up/kernel"]
    lw = {
        "mlp.gate.weight": params["router/kernel"].T,
        "mlp.experts.gate_proj.weight": gate_up[:, :, 0].swapaxes(1, 2),
        "mlp.experts.up_proj.weight": gate_up[:, :, 1].swapaxes(1, 2),
        "mlp.experts.down_proj.weight":
            params["experts/down/kernel"].swapaxes(1, 2),
        "mlp.shared_expert.gate_proj.weight":
            params["shared"]["gate/kernel"].T,
        "mlp.shared_expert.up_proj.weight": params["shared"]["fc/kernel"].T,
        "mlp.shared_expert.down_proj.weight":
            params["shared"]["proj/kernel"].T,
    }
    # ``params`` of the whole layer hold every expert: keep the share's.
    lw = {k: v[first:first + count]
          if ".experts." in k and v.shape[0] != count else v
          for k, v in lw.items()}
    return reference.expert_ffn(cfg, x, lw, "float32")


def test_the_shares_add_up():
    """16 experts over 4 shares: the routed parts of all shares, with the
    shared expert counted once, are the uncut layer."""
    x = jax.random.normal(jax.random.key(0), (2, 24, D))
    whole = expert_layer(None)
    params = whole.init(jax.random.key(1), x)["params"]
    uncut = whole.apply({"params": params}, x)
    want, loads = reference_layer(params, x, 0, E)
    np.testing.assert_allclose(np.asarray(uncut), np.asarray(want),
                               atol=2e-4)
    shared_only = reference.gated_mlp(
        x, params["shared"]["gate/kernel"].T, params["shared"]["fc/kernel"].T,
        params["shared"]["proj/kernel"].T, "float32")
    total, landed = jnp.zeros_like(uncut), 0
    for share in range(4):
        first = 4 * share
        part = dict(params)
        for key in ("experts/gate_up/kernel", "experts/down/kernel"):
            part[key] = params[key][first:first + 4]
        out, mut = expert_layer((first, 4)).apply(
            {"params": part}, x, mutable=["intermediates"])
        stats = mut["intermediates"]["moe_stats"][0]
        np.testing.assert_array_equal(
            np.asarray(stats[:4]), np.asarray(loads[first:first + 4]))
        assert int(stats[4]) == 0
        landed += int(jnp.sum(stats[:4]))
        total = total + (out - shared_only)
    assert landed == 2 * 24 * K            # every assignment landed once
    np.testing.assert_allclose(np.asarray(total + shared_only),
                               np.asarray(uncut), atol=3e-4)


def test_no_drops_when_every_token_goes_to_one_held_expert(monkeypatch):
    """A router that puts every token's first choice on one held expert:
    that expert's load is every token, 24 chunks of rows run, nothing is
    dropped, values and gradients still follow the reference."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 4)
    x = jnp.abs(jax.random.normal(jax.random.key(0), (2, 48, D))) + 0.1
    layer = expert_layer((4, 4))
    params = dict(layer.init(jax.random.key(1), x)["params"])
    params["router/kernel"] = params["router/kernel"].at[:, 5].set(3.0)

    def program(params, x):
        return layer.apply({"params": params}, x, mutable=["intermediates"])

    out, mut = program(params, x)
    stats = np.asarray(mut["intermediates"]["moe_stats"][0])
    assert stats[1] == 2 * 48 and stats[4] == 0
    assert _wgrad_engaged()[""] == 0         # four-row chunks: the products
    want, loads = reference_layer(params, x, 4, 4)
    np.testing.assert_array_equal(stats[:4], np.asarray(loads))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=3e-4)
    probe = jax.random.normal(jax.random.key(3), out.shape)
    got = jax.grad(lambda p, x: jnp.sum(program(p, x)[0] * probe),
                   argnums=(0, 1))(params, x)
    ref = jax.grad(lambda p, x: jnp.sum(
        reference_layer(p, x, 4, 4)[0] * probe), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-6
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=3e-4)


def _wgrad_engaged():
    """``{layer: 0 | 1}`` of ``smp_moe_wgrad_kernel_engaged``."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    series = telemetry.report()["metrics"]["smp_moe_wgrad_kernel_engaged"]
    return {s["labels"]["layer"]: s["value"] for s in series["series"]}


def _dense_held_experts(x, w_gate_up, w_down, weights, tokens, offsets,
                        activation="silu"):
    """``held_experts_output`` one expert at a time in plain products:
    ``offsets`` are numbers, so each expert's rows are a static slice."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate_up.shape[0]):
        rows = slice(int(offsets[e]), int(offsets[e + 1]))
        picked = x[tokens[rows]]
        gate, up = jnp.split(picked @ w_gate_up[e], 2, axis=-1)
        y = (transformer._activation(activation)(gate) * up) @ w_down[e]
        out = out.at[tokens[rows]].add(y * weights[rows][:, None])
    return out


def _three_chunks_of_sorted_rows(D, F, held, idle=None):
    """640 tokens at two of ``held`` experts each, sorted into 512-row
    chunks: 1,280 rows in three chunks of a 1,536-row buffer, so experts
    lie across the chunk boundaries, the last chunk ends in rows past the
    groups and every token is in two groups (``idle``: a held expert no
    token chooses). Returns the routing, the operands of
    ``held_experts_output`` and a probe for its output."""
    tokens_n, top_k, rows = 640, 2, 512
    keys = jax.random.split(jax.random.key(D + held), 6)
    scores = jax.random.uniform(keys[0], (tokens_n, held))
    if idle is not None:
        scores = scores.at[:, idle].set(2.0)         # sorts last
    top_idx = jnp.argsort(scores, axis=-1)[:, :top_k]
    top_weight = jax.nn.softmax(
        jax.random.normal(keys[1], (tokens_n, top_k)), axis=-1)
    tokens, weights, offsets, _, _ = moe.route_to_held(
        top_idx, top_weight, 0, held, rows)
    assert int(offsets[-1]) == 1280 and tokens.shape == (1536,)
    # an expert's rows lie across each chunk boundary
    assert not set(np.asarray(offsets).tolist()) & {512, 1024}
    x = jax.random.normal(keys[2], (tokens_n, D))
    w_gate_up = jax.random.normal(keys[3], (held, D, 2 * F)) * 0.05
    w_down = jax.random.normal(keys[4], (held, F, D)) * 0.05
    probe = jax.random.normal(keys[5], (tokens_n, D))
    return rows, tokens, weights, offsets, x, w_gate_up, w_down, probe


@pytest.mark.parametrize("D,F,held", [(384, 128, 4), (256, 128, 8)],
                         ids=["laguna_3_to_1", "eight_held"])
def test_weight_gradients_summed_in_the_kernel_are_the_products(
        D, F, held, monkeypatch):
    """The kernel forced through interpret mode at tile-sized shapes: three
    512-row chunks of 1,280 sorted rows, so experts lie across the chunk
    boundaries and the last chunk ends in rows past the groups. The
    gradients of ``held_experts_output`` for the rows, both weight tensors
    and the combine weights are those of the product path (the kernel
    standing aside) and of a dense expert-by-expert reference."""
    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.ops import pallas_grouped_wgrad as gw

    rows, tokens, weights, offsets, x, w_gate_up, w_down, probe = \
        _three_chunks_of_sorted_rows(D, F, held)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda x, a, b, w: jnp.sum(fn(x, a, b, w) * probe),
            argnums=(0, 1, 2, 3)))(x, w_gate_up, w_down, weights)

    program = lambda x, a, b, w: moe.held_experts_output(   # noqa: E731
        x, a, b, w, tokens, offsets, "silu", rows)
    smp.reset()
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    try:
        assert not moe._wgrad_kernel_engages(rows, w_gate_up, w_down)
        products = grads(program)
        monkeypatch.setattr(gw, "FORCE_INTERPRET", True)
        assert moe._wgrad_kernel_engages(rows, w_gate_up, w_down)
        assert not moe._wgrad_kernel_engages(8, w_gate_up, w_down)
        calls = []
        real = gw.grouped_wgrad
        monkeypatch.setattr(
            gw, "grouped_wgrad",
            lambda *a, **k: calls.append(a[0].shape) or real(*a, **k))
        kernel = grads(program)
        assert calls == [(rows, D), (rows, F)]       # traced once a tensor
    finally:
        smp.reset()
    dense = grads(lambda x, a, b, w: _dense_held_experts(
        x, a, b, w, tokens, np.asarray(offsets)))
    for got, same, want in zip(kernel, products, dense):
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(same) / scale, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=2e-5)


def _held_gradients(operands, activation, kernels, monkeypatch,
                    dtype=jnp.float32):
    """The four gradients of ``sum(held_experts_output * probe)`` (rows,
    both weight tensors, combine weights) with the rows and weights in
    ``dtype``, the two Pallas kernels forced through interpret mode
    (``kernels``) or standing aside, beside those of the dense
    expert-by-expert reference in float32 on the same values."""
    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.ops import pallas_grouped_wgrad as gw
    from smdistributed_modelparallel_tpu.ops import pallas_row_scatter_add as rs

    rows, tokens, weights, offsets, x, w_gate_up, w_down, probe = operands
    x, w_gate_up, w_down = (v.astype(dtype) for v in (x, w_gate_up, w_down))

    def grads(fn, *args):
        return jax.jit(jax.grad(
            lambda x, a, b, w: jnp.sum(fn(x, a, b, w) * probe),
            argnums=(0, 1, 2, 3)))(*args)

    smp.reset()
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    try:
        monkeypatch.setattr(gw, "FORCE_INTERPRET", kernels)
        monkeypatch.setattr(rs, "FORCE_INTERPRET", kernels)
        assert moe._wgrad_kernel_engages(rows, w_gate_up, w_down) == kernels
        assert moe._combine_kernel_engages(x, rows) == kernels
        got = grads(
            lambda x, a, b, w: moe.held_experts_output(
                x, a, b, w, tokens, offsets, activation, rows),
            x, w_gate_up, w_down, weights)
    finally:
        smp.reset()
    want = grads(
        lambda x, a, b, w: _dense_held_experts(
            x, a, b, w, tokens, np.asarray(offsets), activation),
        *(v.astype(jnp.float32) for v in (x, w_gate_up, w_down)), weights)
    return got, want


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("activation", ["silu", "gelu_new"])
@pytest.mark.parametrize("kernels", [True, False],
                         ids=["kernels", "products"])
def test_the_written_out_backward_chunk_gives_the_four_gradients(
        kernels, activation, dtype, monkeypatch):
    """``_chunk_grads`` forms no second product: the rows' gradient goes
    through ``u = g @ w_down^T`` and the combine weights' is ``sum_f h_act
    * u`` where autodiff had ``sum_d y * g``. The four gradients against
    ``jax.grad`` of the dense reference on the three 512-row chunks, with
    the kernels forced through interpret mode and standing aside, under
    the routed cells' activation and GPT-2's: in float32 to rounding, in
    bfloat16 no further from the float32 run than autodiff's chain was
    (0.53% of the gradient's norm at worst over these cases for both, read
    on the parent of PR 43)."""
    got, want = _held_gradients(
        _three_chunks_of_sorted_rows(384, 128, 4), activation, kernels,
        monkeypatch, jnp.dtype(dtype))
    for g, w in zip(got, want):
        assert g.shape == w.shape
        gap = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
        assert gap < (2e-5 if dtype == "float32" else 6e-3), gap


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["kernels", "products"])
def test_a_held_expert_without_a_row_gets_no_gradient(kernels, monkeypatch):
    """Expert 1 of four is chosen by no token: its group is empty in every
    chunk, its blocks of both weight gradients are exactly zero and the
    other gradients are the dense reference's."""
    operands = _three_chunks_of_sorted_rows(384, 128, 4, idle=1)
    offsets = np.asarray(operands[3])
    assert offsets[1] == offsets[2] and offsets[-1] == 1280
    got, want = _held_gradients(operands, "silu", kernels, monkeypatch)
    assert not np.asarray(got[1][1]).any() and not np.asarray(got[2][1]).any()
    for g, w in zip(got, want):
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)


@pytest.mark.parametrize("kernels", [True, False],
                         ids=["kernels", "products"])
def test_rows_past_the_groups_reach_no_gradient(kernels, monkeypatch):
    """The rows past the groups point to a token no assignment names (one
    of 640 more, none routed), whose ``x`` and ``g`` are not finite, and every grouped product leaves NaN
    in the rows past its groups (on the chip: whatever the memory held).
    The chain masks them going in, in the middle and coming out: the four
    gradients are finite and the dense reference's."""
    rows, tokens, weights, offsets, x, w_gate_up, w_down, probe = \
        _three_chunks_of_sorted_rows(384, 128, 4)
    ghosts = x.shape[0]          # as many again: the token tiles still divide
    tokens = jnp.where(jnp.arange(tokens.shape[0]) < offsets[-1], tokens,
                       ghosts + 7)
    x = jnp.concatenate([x, jnp.full(x.shape, jnp.nan)])
    probe = jnp.concatenate([probe, jnp.full(probe.shape, jnp.inf)])
    real = jax.lax.ragged_dot

    def ragged_dot(lhs, rhs, group_sizes, **kwargs):
        out = real(lhs, rhs, group_sizes, **kwargs)
        return jnp.where(moe._valid_rows(lhs.shape[0], group_sizes), out,
                         jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", ragged_dot)
    got, want = _held_gradients(
        (rows, tokens, weights, offsets, x, w_gate_up, w_down, probe),
        "silu", kernels, monkeypatch)
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        scale = float(jnp.max(jnp.abs(w)))
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-5)
    assert not np.asarray(got[0][ghosts:]).any()


@pytest.mark.parametrize("D,F,held", [(384, 128, 4), (256, 128, 8)],
                         ids=["laguna_3_to_1", "eight_held"])
def test_rows_summed_back_in_the_kernel_are_the_scatter_adds(
        D, F, held, monkeypatch):
    """The row scatter-add kernel forced through interpret mode on the same
    three 512-row chunks of 1,280 sorted rows over 640 tokens (two token
    tiles of 320; experts across the chunk boundaries, the last chunk ends
    in rows past the groups, every token in two groups): the output of
    ``held_experts_output`` and its gradients for the rows, both weight
    tensors and the combine weights are those of XLA's scatter-add (the
    kernel standing aside) and of a dense expert-by-expert reference. On
    a mesh of two devices it stands aside though forced."""
    import smdistributed_modelparallel_tpu as smp
    from smdistributed_modelparallel_tpu.ops import pallas_row_scatter_add as rs

    rows, tokens, weights, offsets, x, w_gate_up, w_down, probe = \
        _three_chunks_of_sorted_rows(D, F, held)

    def out_and_grads(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, a, b, w: jnp.sum(fn(x, a, b, w) * probe),
            argnums=(0, 1, 2, 3)))(x, w_gate_up, w_down, weights)

    program = lambda x, a, b, w: moe.held_experts_output(   # noqa: E731
        x, a, b, w, tokens, offsets, "silu", rows)
    smp.reset()
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    try:
        assert not moe._combine_kernel_engages(x, rows)
        scatter = out_and_grads(program)
        monkeypatch.setattr(rs, "FORCE_INTERPRET", True)
        assert moe._combine_kernel_engages(x, rows)
        assert not moe._combine_kernel_engages(x, 8)     # the shrunk chunks
        assert not moe._combine_kernel_engages(x[:, :100], rows)
        calls = []
        real = rs.row_scatter_add
        monkeypatch.setattr(
            rs, "row_scatter_add",
            lambda *a, **k: calls.append(a[1].shape) or real(*a, **k))
        kernel = out_and_grads(program)
        assert calls == [(rows, D), (rows, D)]   # traced once a pass
        smp.reset()
        smp.init({"microbatches": 1, "ddp": True},
                 devices=jax.devices()[:2])
        assert not moe._combine_kernel_engages(x, rows)
    finally:
        smp.reset()
    dense = out_and_grads(lambda x, a, b, w: _dense_held_experts(
        x, a, b, w, tokens, np.asarray(offsets)))
    for got, same, want in zip(jax.tree_util.tree_leaves(kernel),
                               jax.tree_util.tree_leaves(scatter),
                               jax.tree_util.tree_leaves(dense)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-6
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(same) / scale, atol=2e-6)
        np.testing.assert_allclose(np.asarray(got) / scale,
                                   np.asarray(want) / scale, atol=2e-5)


def test_visited_share_of_planted_loads():
    """Eight-row chunks over four held experts: loads 8, 0, 4, 4 fill two
    chunks that hold one and two experts (3 of 8 pairs); loads 3, 3, 3, 3
    fill two chunks that hold three and two (5 of 8). The gauge is the two
    calls together; a layer that was not traced here adds nothing."""
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    assert moe._experts_visited(np.array([8, 0, 4, 4]), 8) == (3, 8)
    assert moe._experts_visited(np.array([3, 3, 3, 3]), 8) == (5, 8)
    assert moe._experts_visited(np.array([0, 0, 0, 0]), 8) == (0, 0)
    assert moe._experts_visited(np.array([0, 40, 0, 0]), 8) == (5, 20)
    stats = {"planted": np.array([[[8, 0, 4, 4, 0]], [[3, 3, 3, 3, 0]]]),
             "never_traced": np.array([[[1, 1, 1, 1, 0]]])}
    try:
        moe._TRACED_CHUNK_ROWS["planted"] = 8
        summary = record_moe_stats(stats)
    finally:
        del moe._TRACED_CHUNK_ROWS["planted"]
    assert summary["wgrad_visited_share"] == 0.5
    assert summary["local"] == 32
    series = telemetry.report()["metrics"][
        "smp_moe_wgrad_experts_visited_share"]["series"]
    assert [s["value"] for s in series] == [0.5]


def test_dropped_counts_what_a_smaller_buffer_would_lose(monkeypatch):
    """The counter is live: with the row buffer planted too small (16 rows
    for 96 assignments to one held expert) it reads the 80 that have no
    row; under the layer's own bound it reads 0."""
    top_idx = jnp.tile(jnp.array([[5, 0, 1, 2]]), (96, 1))
    top_weight = jnp.full((96, K), 0.25)
    *_, loads, dropped = moe.route_to_held(top_idx, top_weight, 4, 4, 8)
    assert loads.tolist() == [0, 96, 0, 0] and int(dropped) == 0
    monkeypatch.setattr(moe, "_row_buffer", lambda *a: 16)
    tokens, _, _, loads, dropped = moe.route_to_held(
        top_idx, top_weight, 4, 4, 8)
    assert tokens.shape == (16,)
    assert loads.tolist() == [0, 96, 0, 0] and int(dropped) == 80


def test_expert_layer_refuses_what_it_cannot_hold():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    x = jnp.zeros((1, 8, D))
    with pytest.raises(SMPValidationError, match="must lie inside"):
        expert_layer((14, 4)).init(jax.random.key(0), x)


# ---------------------------------------------------------- the translator

def test_translator_there_and_back():
    cfg = lagunatiny.config()
    view = laguna_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = laguna.translate_state_dict_to_hf(flat, view)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (48, 32)
    assert sd["model.layers.1.self_attn.q_proj.weight"].shape == (6 * 8, 32)
    assert sd["model.layers.1.self_attn.k_proj.weight"].shape == (2 * 8, 32)
    assert sd["model.layers.4.self_attn.g_proj.weight"].shape == (4, 32)
    assert sd["model.layers.2.mlp.gate.weight"].shape == (16, 32)
    # the held experts keep their published indices 4 .. 7
    assert "model.layers.2.mlp.experts.4.up_proj.weight" in sd
    assert "model.layers.2.mlp.experts.3.up_proj.weight" not in sd
    assert "model.layers.2.mlp.experts.8.up_proj.weight" not in sd
    back = laguna.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_laguna_is_a_registered_family():
    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("LagunaForCausalLM")
    assert family.name == "laguna"
    assert huggingface.family_for("laguna") is family


# ------------------------------------- what was there lowers as it did

# sha256 of the StableHLO text that jax.grad of a two-layer multi-head
# DistributedTransformerLMHead lowered to on the parent commit of the PR
# that gave the attention layer KV groups, gates and per-kind rotary
# (5a0602f), under the matmul precision conftest.py pins and a mesh of one
# device.
_LOWERED_BEFORE = {
    "gpt2": "b64e5aedc8de93134ed99427d3e8f4a2d264bcb4087fbf9885c9acb868a07af6",
    "neox": "958b9f3e5521b8da552ab4e1062e5dea903154e58dc9f44ae279e51232fb8eab",
}
_FAMILY_KWARGS = {
    "gpt2": {},
    "neox": dict(rotary_dim=8, gpt_neox_type_rotary=True,
                 parallel_attn_output=True, use_positional_embedding=False,
                 tie_input_output_embedding=False, final_layernorm=True,
                 window_size=8),
}


@pytest.fixture(autouse=True)
def one_device_mesh():
    """Every test starts on a mesh of one device: ``smp.reset()`` keeps the
    mesh of the last ``smp.init``, so the lowered text depends on what an
    earlier test left behind (its sharding constraints), and a tp mesh left
    by an earlier file in this worker boxes the parameters that the
    helpers here name flat (``.../value``)."""
    import smdistributed_modelparallel_tpu as smp

    smp.reset()
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    yield
    smp.reset()


@pytest.mark.parametrize("family", list(_LOWERED_BEFORE))
def test_multi_head_model_lowers_as_before(family, one_device_mesh):
    module = transformer.DistributedTransformerLMHead(
        num_layers=2, num_attention_heads=2, attention_head_size=16,
        hidden_size=32, intermediate_size=64, vocab_size=64, num_positions=32,
        attention_dropout_prob=0.0, hidden_dropout_prob=0.0,
        embedding_dropout_prob=0.0, causal_mask_size=32, pre_layernorm=True,
        post_layernorm=False, **_FAMILY_KWARGS[family])
    ids = jnp.zeros((2, 32), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init(jax.random.key(0), ids))["params"]

    def loss(p, ids):
        return jnp.sum(module.apply({"params": p}, ids).astype(jnp.float32))

    text = jax.jit(jax.grad(loss)).lower(params, ids).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == _LOWERED_BEFORE[family]
