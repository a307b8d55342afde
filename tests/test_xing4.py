"""Xing4.0's layers in the program, at small sizes on the CPU, seeded random
weights, against the plain reference (``benchmark/reference/xing4.py``) and
against loops written out here: latent attention (forward, gradient, the one
rotary key every head shares, the softmax scale with YaRN's ``mscale``, tp 2
on the host mesh equal to tp 1), the flash kernels in interpret mode with
value heads of their own size against ``ops/attention.py``'s plain path,
the hyper-connection against a token-by-token loop (``H_res`` doubly
stochastic, one stream the plain residual to the bit), each kind of layer
and the five-layer four-stream model through ``DistributedModel`` +
``@smp.step`` with and without ``activation_checkpointing``, the eight
chips' shares of a routed layer adding up to the uncut layer, the Hugging
Face translator there and back."""

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

import xing4tiny  # noqa: E402
from benchmark import loader, weights, xing4_weights  # noqa: E402
from benchmark.reference import laguna as shared  # noqa: E402
from benchmark.reference import xing4 as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import (  # noqa: E402
    hyper_connection,
    latent_attention,
    moe,
    transformer,
)
from smdistributed_modelparallel_tpu.nn.huggingface import (  # noqa: E402
    laguna,
    xing4,
)

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "xing4_moe.py"),
    "xing4_moe_for_tests")


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
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, ids


# ------------------------------------------------------ latent attention

YARN = (64.0, 4096, 32.0, 1.0, 1.0)
SCALE = 24 ** -0.5 * (0.1 * np.log(64.0) + 1.0) ** 2


def latent_layer(**fields):
    return latent_attention.DistributedLatentAttentionLayer(**dict(dict(
        num_attention_heads=4, hidden_size=32, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, softmax_scale=SCALE, rotary_yarn=YARN,
        initializer_range=0.3), **fields))


def loop_latent(x, p, scale=SCALE, yarn=YARN, eps=1e-6):
    """The equations head by head and query by query, in numpy (rotary on
    halves, as the module keeps its rope columns)."""
    p = {k: np.asarray(v, np.float64) for k, v in flat_of(p).items()}
    x = np.asarray(x, np.float64)
    B, T, _ = x.shape
    dn, dr, rkv = 16, 8, 16

    def rms(v, w):
        return v / np.sqrt(np.mean(v * v, axis=-1, keepdims=True) + eps) * w

    freqs = transformer.yarn_inv_freq(dr, 10000.0, *yarn[:4])
    angles = np.arange(T)[:, None] * freqs[None, :]
    cos, sin = np.cos(angles) * yarn[4], np.sin(angles) * yarn[4]

    def rope(v):                                   # [T, dr]
        a, b = v[:, :dr // 2], v[:, dr // 2:]
        return np.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    out = np.zeros_like(x)
    for b in range(B):
        c_q = rms(x[b] @ p["q_down/kernel"], p["q_norm/scale"])
        latent = x[b] @ p["kv_down/kernel"]
        c_kv = rms(latent[:, :rkv], p["kv_norm/scale"])
        k_pe = rope(latent[:, rkv:])               # one for every head
        for h in range(4):
            q = c_q @ p["q_up/kernel"][:, h]
            kv = c_kv @ p["kv_up/kernel"][:, h]
            q = np.concatenate([q[:, :dn], rope(q[:, dn:])], -1)
            k = np.concatenate([kv[:, :dn], k_pe], -1)
            for t in range(T):
                s = scale * (k[:t + 1] @ q[t])
                w = np.exp(s - s.max())
                o = (w / w.sum()) @ kv[:t + 1, dn:]
                out[b, t] += o @ p["dense/kernel"][h]
    return out


def test_latent_attention_is_the_written_out_loop():
    layer = latent_layer()
    x = jax.random.normal(jax.random.key(0), (2, 9, 32))
    params = layer.init(jax.random.key(1), x)["params"]
    assert {k: v.shape for k, v in flat_of(params).items()} == {
        "q_down/kernel": (32, 24), "q_norm/scale": (24,),
        "q_up/kernel": (24, 4, 24), "kv_down/kernel": (32, 24),
        "kv_norm/scale": (16,), "kv_up/kernel": (16, 4, 32),
        "dense/kernel": (4, 16, 32)}
    np.testing.assert_allclose(
        np.asarray(layer.apply({"params": params}, x)),
        loop_latent(x, params), atol=2e-4)


def test_latent_attention_scale_carries_mscale_and_the_table_does_not():
    """The softmax scale is the layer's field whole (192^-1/2 m^2 at full
    size); YaRN's fifth entry multiplies cos and sin. A wrong place for
    either moves the output."""
    x = jax.random.normal(jax.random.key(0), (1, 7, 32))
    layer = latent_layer()
    params = layer.init(jax.random.key(1), x)["params"]
    base = np.asarray(layer.apply({"params": params}, x))
    plain_scale = latent_layer(softmax_scale=24 ** -0.5)
    moved = np.asarray(plain_scale.apply({"params": params}, x))
    np.testing.assert_allclose(
        moved, loop_latent(x, params, scale=24 ** -0.5), atol=2e-4)
    assert np.abs(moved - base).max() > 1e-3
    table = latent_layer(rotary_yarn=YARN[:4] + (1.4,))
    np.testing.assert_allclose(
        np.asarray(table.apply({"params": params}, x)),
        loop_latent(x, params, yarn=YARN[:4] + (1.4,)), atol=2e-4)
    # the translator's numbers at the published keys
    latent, rotary = xing4.latent_fields(xing4tiny.config(
        qk_nope_head_dim=128, qk_rope_head_dim=64))
    assert latent["softmax_scale"] == pytest.approx(0.14468, abs=1e-5)
    assert rotary["rotary_yarn"] == (64.0, 4096, 32.0, 1.0, 1.0)


def test_every_head_reads_the_one_rotary_key():
    """A change to ``kv_down``'s rope columns moves every head's output;
    a change to one head's ``kv_up`` that head's alone."""
    x = jax.random.normal(jax.random.key(0), (1, 6, 32))
    layer = latent_layer()
    params = flat_of(layer.init(jax.random.key(1), x)["params"])
    like = layer.init(jax.random.key(1), x)["params"]

    def heads_out(p):
        """Each head's part of the output: ``dense`` of the others zeroed."""
        outs = []
        for h in range(4):
            only = p["dense/kernel"] * (jnp.arange(4) == h)[:, None, None]
            outs.append(layer.apply({"params": unflatten(
                dict(p, **{"dense/kernel": only}), like)}, x))
        return np.asarray(jnp.stack(outs))

    base = heads_out(params)
    rope = dict(params, **{"kv_down/kernel": params["kv_down/kernel"].at[
        :, 16:].add(0.5)})
    assert (np.abs(heads_out(rope) - base).max(axis=(1, 2, 3)) > 1e-4).all()
    one = dict(params, **{"kv_up/kernel": params["kv_up/kernel"].at[
        :, 2].add(0.5)})
    moved = np.abs(heads_out(one) - base).max(axis=(1, 2, 3))
    assert moved[2] > 1e-4 and (moved[[0, 1, 3]] == 0).all()


def test_latent_attention_gradients_against_the_loop():
    layer = latent_layer()
    x = jax.random.normal(jax.random.key(0), (1, 5, 32))
    params = layer.init(jax.random.key(1), x)["params"]
    probe = np.asarray(jax.random.normal(jax.random.key(2), (1, 5, 32)))
    grad = flat_of(jax.grad(lambda p: jnp.sum(
        layer.apply({"params": p}, x) * probe))(params))
    flat = {k: np.asarray(v, np.float64)
            for k, v in flat_of(params).items()}
    rng = np.random.default_rng(0)
    for name, value in flat.items():
        for _ in range(2):
            index = tuple(rng.integers(0, n) for n in value.shape)
            step = np.zeros_like(value)
            step[index] = 1e-4
            high = np.sum(loop_latent(x, unflatten(
                dict(flat, **{name: value + step}), params)) * probe)
            low = np.sum(loop_latent(x, unflatten(
                dict(flat, **{name: value - step}), params)) * probe)
            np.testing.assert_allclose(
                float(grad[name][index]), (high - low) / 2e-4, rtol=5e-3,
                atol=5e-4, err_msg=f"{name}{index}")


def test_latent_attention_under_tp_2_is_tp_1():
    """The host mesh at tp 2: heads of ``q_up``, ``kv_up`` and ``dense``
    split, the down-projections and the latents' norms replicated."""
    import smdistributed_modelparallel_tpu as smp

    layer = latent_layer()
    x = jax.random.normal(jax.random.key(0), (2, 8, 32))
    params = layer.init(jax.random.key(1), x)["params"]
    loss = lambda p, x: jnp.sum(jnp.square(            # noqa: E731
        layer.apply({"params": p}, x)))
    want, want_grad = layer.apply({"params": params}, x), jax.grad(loss)(
        params, x)
    smp.reset()
    smp.init({"tensor_parallel_degree": 2, "ddp": True, "microbatches": 1},
             devices=jax.devices()[:2])
    from smdistributed_modelparallel_tpu.module_manager import path_key

    boxed = jax.tree_util.tree_flatten_with_path(
        layer.init(jax.random.key(1), x)["params"],
        is_leaf=lambda v: hasattr(v, "names"))[0]
    specs = {path_key(path): getattr(v, "names", (None,) * np.ndim(v))
             for path, v in boxed}
    assert specs == {
        "q_down/kernel": (None, None), "q_norm/scale": (None,),
        "q_up/kernel": (None, "tp", None), "kv_down/kernel": (None, None),
        "kv_norm/scale": (None,), "kv_up/kernel": (None, "tp", None),
        "dense/kernel": ("tp", None, None)}
    from jax.sharding import NamedSharding, PartitionSpec as P

    from smdistributed_modelparallel_tpu.backend.state import state

    placed = unflatten({k: jax.device_put(v, NamedSharding(
        state.mesh, P(*specs[k]))) for k, v in flat_of(params).items()},
        params)
    with jax.set_mesh(state.mesh):
        got = jax.jit(lambda p, x: layer.apply({"params": p}, x))(placed, x)
        got_grad = jax.jit(jax.grad(loss))(placed, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    for name, value in flat_of(want_grad).items():
        scale = float(jnp.max(jnp.abs(value)))
        np.testing.assert_allclose(
            np.asarray(flat_of(got_grad)[name]) / scale,
            np.asarray(value) / scale, atol=1e-5, err_msg=name)


def test_latent_ops_carry_their_scopes_and_the_kind_refuses_decode():
    from smdistributed_modelparallel_tpu.utils import profiling
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    layer = latent_layer()
    x = jnp.ones((1, 8, 32))
    params = layer.init(jax.random.key(1), x)["params"]
    text = jax.jit(jax.grad(lambda p: jnp.sum(jnp.square(
        layer.apply({"params": p}, x))))).lower(params).as_text(
            debug_info=True)
    for part in ("q_down", "q_up", "kv_down", "kv_up", "rope", "out"):
        scope = f"smp/latent/{part}"
        assert scope in profiling.SCOPES
        lines = [line for line in text.split("\n") if scope in line]
        assert any("transpose(" in line for line in lines), scope
        assert any("transpose(" not in line for line in lines), scope
    latent, _ = xing4.latent_fields(xing4tiny.config())
    block = transformer.DistributedTransformerLayer(
        num_attention_heads=4, attention_head_size=24, hidden_size=32,
        intermediate_size=48, latent_attention=latent, decode=True)
    with pytest.raises(SMPValidationError, match="decode"):
        block.init(jax.random.key(0), jnp.ones((1, 4, 32)))


# ------------------------------- the flash kernels at unequal head sizes

def plain_attention(q, k, v, **kw):
    from smdistributed_modelparallel_tpu.ops import attention

    return attention.attention_core(q, k, v, use_pallas=False, **kw)


FLASH_SHAPES = {
    # (heads, kv heads, keys' size, values' size, window)
    "latent_24_16": (2, 2, 24, 16, None),
    # as the published 192 / 128: the keys run at 256 lanes, the values 128
    "lanes_differ": (1, 1, 136, 24, None),
    "grouped_kv": (4, 2, 24, 16, None),
    "grouped_kv_lanes_differ": (2, 1, 136, 24, None),
    "wider_values": (2, 2, 16, 24, None),
    "window": (2, 1, 24, 16, 48),
}


@pytest.mark.parametrize("shape", [
    "latent_24_16", "lanes_differ",
    pytest.param("grouped_kv", marks=pytest.mark.slow),
    pytest.param("grouped_kv_lanes_differ", marks=pytest.mark.slow),
    pytest.param("wider_values", marks=pytest.mark.slow),
    pytest.param("window", marks=pytest.mark.slow)])
def test_flash_kernels_take_value_heads_of_their_own_size(shape):
    """Interpret mode: forward and the three gradients against the plain
    path, whose einsums take any sizes."""
    from smdistributed_modelparallel_tpu.ops import pallas_attention

    H, Hkv, dqk, dv, window = FLASH_SHAPES[shape]
    keys = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(keys[0], (1, 160, H, dqk))
    k = jax.random.normal(keys[1], (1, 160, Hkv, dqk))
    v = jax.random.normal(keys[2], (1, 160, Hkv, dv))
    probe = jax.random.normal(keys[3], (1, 160, H, dv))
    scale = 0.3

    def flash(q, k, v):
        return pallas_attention.flash_attention(
            q, k, v, None, None, None, scale, True, window, 0.0, 128, 128,
            True)

    def plain(q, k, v):
        return plain_attention(q, k, v, causal=True, window=window,
                               scale=scale, mask_value=-1e9)

    out = flash(q, k, v)
    assert out.shape == (1, 160, H, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(plain(q, k, v)),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * probe), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * probe), (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   err_msg=name)


def test_flash_kernels_pad_each_size_to_its_own_lanes():
    from smdistributed_modelparallel_tpu.ops import pallas_attention as pa
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    assert [pa._pad_width(n) for n in (64, 128, 192, 256, 80)] == [
        128, 128, 256, 256, 128]
    q = jnp.zeros((1, 256, 4, 192), jnp.bfloat16)
    v = jnp.zeros((1, 256, 4, 128), jnp.bfloat16)
    qt, kt, vt, dims = pa._prep(q, q, v, 128, 128)
    assert (qt.shape, kt.shape, vt.shape) == (
        (4, 256, 256), (4, 256, 256), (4, 256, 128))
    assert dims[4:6] == (192, 256) and dims[8:] == (128, 128)
    with pytest.raises(ValueError, match="one size"):
        pa._prep(q, v, v, 128, 128)
    jax.eval_shape(lambda q, v: pa.flash_attention(
        q, q, v, None, None, None, 1.0, True, None, 0.0, 128, 128, True),
        q, v)
    series = telemetry.report()["metrics"]["smp_flash_v_head_dim"]["series"]
    assert series[0]["value"] == 128


# --------------------------------------------------- the hyper-connection

def connection(**fields):
    return hyper_connection.DistributedHyperConnection(**dict(dict(
        streams=4, hidden_size=16, initializer_range=0.5), **fields))


def seeded_connection(x, seed=1):
    """A connection's parameters away from their start: ``alpha`` large
    enough that the coefficients follow the token."""
    layer = connection()
    params = dict(layer.init(jax.random.key(seed), x)["params"])
    keys = jax.random.split(jax.random.key(seed + 1), 3)
    params["alpha"] = jnp.asarray([0.7, -0.5, 0.2])
    params["bias"] = params["bias"] + 0.3 * jax.random.normal(keys[0], (24,))
    params["norm/scale"] = 1 + 0.2 * jax.random.normal(keys[1], (4, 16))
    return layer, params


def loop_connection(x, y, p, iters=20, eps=1e-6, clamp=(-30.0, 30.0)):
    """One sub-layer's connection token by token, in numpy: ``(u, X',
    H_res)`` for a sub-layer whose output is ``y``."""
    x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    B, T, n, D = x.shape
    u, new = np.zeros((B, T, D)), np.zeros_like(x)
    h_res = np.zeros((B, T, n, n))
    sig = lambda a: 1 / (1 + np.exp(-a))                 # noqa: E731
    for b in range(B):
        for t in range(T):
            X = x[b, t]
            z = X / np.sqrt(np.mean(X * X) + 1e-6) * p["norm/scale"]
            raw = np.einsum("nd,ndc->c", z, p["phi"])
            pre = sig(p["alpha"][0] * raw[:n] + p["bias"][:n])
            post = 2 * sig(p["alpha"][1] * raw[n:2 * n] + p["bias"][n:2 * n])
            M = np.exp(np.clip(
                p["alpha"][2] * raw[2 * n:] + p["bias"][2 * n:], *clamp
            ).reshape(n, n))
            for _ in range(iters):
                M = M / (M.sum(axis=0, keepdims=True) + eps)
                M = M / (M.sum(axis=1, keepdims=True) + eps)
            u[b, t] = pre @ X
            new[b, t] = M @ X + post[:, None] * y[b, t][None, :]
            h_res[b, t] = M
    return u, new, h_res


def test_hyper_connection_is_the_token_by_token_loop():
    x = jax.random.normal(jax.random.key(0), (2, 5, 4, 16))
    y = jax.random.normal(jax.random.key(3), (2, 5, 16))
    layer, params = seeded_connection(x)
    assert {k: v.shape for k, v in params.items()} == {
        "norm/scale": (4, 16), "phi": (4, 16, 24), "alpha": (3,),
        "bias": (24,)}
    u, h_post, h_res = layer.apply({"params": params}, x)
    new = hyper_connection.post_res(x, y, h_post, h_res)
    want_u, want_new, want_res = loop_connection(x, y, params)
    np.testing.assert_allclose(np.asarray(u), want_u, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new), want_new, atol=2e-5)
    got_res = np.moveaxis(np.asarray(h_res), (0, 1), (2, 3))
    np.testing.assert_allclose(got_res, want_res, atol=1e-6)
    # rows are the last to be divided; the columns follow as the rounds
    # converge, which is slow on logits this wide
    np.testing.assert_allclose(got_res.sum(axis=-1), 1.0, atol=1e-5)
    assert got_res.min() > 0 and np.ptp(got_res[..., 0, 0]) > 1e-3
    # doubly stochastic to 1e-5 after 20 rounds on logits of the same width
    # with no diagonal under them (a diagonal of 6 is almost a permutation,
    # where the rounds converge slowly: 1e-3 in 20), still the token's own
    params["bias"] = params["bias"].at[8:].add(
        -6.0 * jnp.eye(4).reshape(-1))
    mild = np.moveaxis(np.asarray(
        layer.apply({"params": params}, x)[2]), (0, 1), (2, 3))
    np.testing.assert_allclose(mild.sum(axis=-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(mild.sum(axis=-2), 1.0, atol=1e-5)
    assert np.ptp(mild[..., 0, 1]) > 1e-2


def test_hyper_connection_starts_on_the_mean_and_almost_unmixed():
    x = jax.random.normal(jax.random.key(0), (1, 3, 4, 16))
    layer = connection(initializer_range=0.02)
    params = layer.init(jax.random.key(1), x)["params"]
    u, h_post, h_res = layer.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(u), np.asarray(x.mean(axis=2)),
                               atol=2e-2)
    np.testing.assert_allclose(np.asarray(h_post), 1.0, atol=2e-2)
    eye = np.asarray(h_res)[:, :, 0, 0]
    np.testing.assert_allclose(eye, np.eye(4), atol=1e-2)


def test_hyper_connection_gradients_are_the_plain_formulas():
    """The two mixes' written-out transposes against autodiff of the
    formulas (``custom_vjp`` off), through the coefficients too."""
    x = jax.random.normal(jax.random.key(0), (2, 4, 4, 16))
    w = jax.random.normal(jax.random.key(4), (16, 16)) * 0.3
    probe = jax.random.normal(jax.random.key(5), (2, 4, 4, 16))
    layer, params = seeded_connection(x)

    def loss(params, x, plain):
        u, h_post, h_res = layer.apply({"params": params}, x)
        if plain:
            u = sum(h_pre_of(params, x)[i][..., None] * x[:, :, i]
                    for i in range(4))
        y = jnp.tanh(u @ w)
        if plain:
            new = jnp.stack([
                sum(h_res[i, j][..., None] * x[:, :, j] for j in range(4))
                + h_post[i][..., None] * y for i in range(4)], axis=2)
        else:
            new = hyper_connection.post_res(x, y, h_post, h_res)
        return jnp.sum(new * probe)

    def h_pre_of(params, x):
        # the pre coefficients again, from the module's own formulas
        r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=(2, 3)) + 1e-6)
        raw = r[None] * jnp.einsum(
            "btnd,ndc->cbt", x, params["norm/scale"][..., None]
            * params["phi"])
        return jax.nn.sigmoid(
            params["alpha"][0] * raw[:4] + params["bias"][:4, None, None])

    got = jax.grad(loss, (0, 1))(params, x, False)
    want = jax.grad(loss, (0, 1))(params, x, True)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-9
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale, atol=2e-5)


def block(**fields):
    return transformer.DistributedTransformerLayer(**dict(dict(
        num_attention_heads=2, attention_head_size=8, hidden_size=16,
        intermediate_size=32, pre_layernorm=True, post_layernorm=False,
        layernorm_type="rms", causal_mask_size=16,
        attention_dropout_prob=0.0, hidden_dropout_prob=0.0), **fields))


def test_one_stream_is_the_plain_residual_to_the_bit():
    """``hyper_connection`` with one stream makes no module and no op: the
    block's parameters and output are those of a block without it."""
    x = jax.random.normal(jax.random.key(0), (2, 6, 16))
    plain = block()
    params = plain.init(jax.random.key(1), x)["params"]
    one = block(hyper_connection={"streams": 1})
    assert set(flat_of(one.init(jax.random.key(1), x)["params"])) == set(
        flat_of(params))
    np.testing.assert_array_equal(
        np.asarray(one.apply({"params": params}, x)),
        np.asarray(plain.apply({"params": params}, x)))
    assert str(jax.make_jaxpr(lambda p, x: one.apply({"params": p}, x))(
        params, x)) == str(jax.make_jaxpr(
            lambda p, x: plain.apply({"params": p}, x))(params, x))
    # and written out: x + attn(norm(x)), then + mlp(norm(.))
    assert "hyper_connection" not in " ".join(flat_of(params))


def test_four_streams_refuse_what_has_no_stream_form():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    x = jnp.ones((1, 4, 4, 16))
    for fields in ({"post_layernorm": True}, {"parallel_attn_output": True}):
        layer = block(hyper_connection={"streams": 4}, **fields)
        with pytest.raises(SMPValidationError, match="pre-norm"):
            layer.init(jax.random.key(0), x)
    stack = transformer.DistributedTransformerLMHead(
        num_layers=2, num_attention_heads=2, attention_head_size=8,
        hidden_size=16, intermediate_size=32, vocab_size=32,
        hyper_connection={"streams": 4})
    assert stack.pipeline_spec() is None


def test_mhc_bytes_and_the_streams_in_and_out():
    assert hyper_connection.mhc_bytes(100, 4, 16, 2) == {
        "fwd": 14 * 100 * 16 * 2, "bwd": 19 * 100 * 16 * 2}
    x = jax.random.normal(jax.random.key(0), (2, 3, 16))
    streams = hyper_connection.expand_streams(x, 4)
    assert streams.shape == (2, 3, 4, 16)
    np.testing.assert_array_equal(np.asarray(streams[:, :, 2]), np.asarray(x))
    np.testing.assert_allclose(
        np.asarray(hyper_connection.collapse_streams(streams)),
        4 * np.asarray(x), rtol=1e-6)


def test_mhc_ops_carry_their_scopes_forward_and_backward():
    from smdistributed_modelparallel_tpu.utils import profiling

    x = jnp.ones((1, 8, 4, 16))
    layer, params = seeded_connection(x)

    def loss(p):
        u, h_post, h_res = layer.apply({"params": p}, x)
        return jnp.sum(jnp.square(
            hyper_connection.post_res(x, jnp.tanh(u), h_post, h_res)))

    text = jax.jit(jax.grad(loss)).lower(params).as_text(debug_info=True)
    for part in ("coeff", "sinkhorn", "pre", "post_res"):
        scope = f"smp/mhc/{part}"
        assert scope in profiling.SCOPES
        lines = [line for line in text.split("\n") if scope in line]
        assert any("transpose(" in line for line in lines), scope
        assert any("transpose(" not in line for line in lines), scope


# ----------------------------------------------------- the family's plan

def test_plan_names_the_lead_and_the_routed_kind():
    cfg = xing4tiny.config()
    pattern, kinds = xing4_weights.plan(cfg)
    assert pattern == ("lead_dense",) + ("full",) * 4
    assert set(kinds) == {"lead_dense", "full"}
    lead, full = kinds["lead_dense"], kinds["full"]
    assert lead["num_experts"] == 0 and lead["intermediate_size"] == 48
    assert full["num_experts"] == 16 and full["moe_held"] == (4, 4)
    assert (full["moe_score"], full["moe_selection_bias"],
            full["moe_routed_scaling"], full["moe_top_k"]) == (
                "sigmoid", True, 2.0, 4)
    assert full["moe_shared_intermediate_size"] == 16
    assert full["intermediate_size"] == 16 and full["moe_dropless"]
    for kw in kinds.values():
        assert kw["latent_attention"]["qk_rope_head_dim"] == 8
        assert kw["latent_attention"]["v_head_dim"] == 16
        assert kw["rotary_yarn"][:2] == (64.0, 4096)
    smp_kwargs = xing4.config_to_smp(xing4_weights.hf_view(cfg))
    assert smp_kwargs["hyper_connection"] == {
        "streams": 4, "sinkhorn_iters": 20, "eps": 1e-6,
        "clamp": (-30.0, 30.0)}
    assert smp_kwargs["tie_input_output_embedding"] is False
    assert smp_kwargs["attention_head_size"] == 24


def test_published_plan_at_full_depth():
    import json

    with open(os.path.join(_REPO, xing4tiny.CONFIG)) as f:
        cfg = json.load(f)
    published = dict(cfg, **cfg["published"])
    published.pop("layer_types")
    pattern, kinds = xing4.layer_plan(published)
    assert pattern == ("lead_dense",) * 2 + ("full",) * 38
    assert kinds["full"]["num_experts"] == 64
    assert kinds["full"]["moe_held"] is None
    assert kinds["full"]["num_attention_heads"] == 32


def test_plan_refuses_what_the_family_does_not_run():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    view = xing4_weights.hf_view(xing4tiny.config())
    for change, word in (
            ({"n_group": 8}, "group-limited"), ({"topk_group": 4}, "group"),
            ({"scoring_func": "softmax"}, "sigmoid"),
            ({"n_shared_experts": 2}, "shared"),
            ({"q_lora_rank": None}, "latent"),
            ({"rope_scaling": {"type": "linear", "factor": 2}}, "YaRN")):
        with pytest.raises(SMPValidationError, match=word):
            xing4.layer_plan(dict(view, **change))
    with pytest.raises(SMPValidationError, match="attention_bias"):
        xing4.config_to_smp(dict(view, attention_bias=True))


def test_gauges_count_the_streams_and_the_latent_layers():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = xing4tiny.config()
    module = builder.module(cfg)
    jax.eval_shape(module.init, jax.random.key(0),
                   jnp.zeros((1, 8), jnp.int32))
    metrics = telemetry.report()["metrics"]
    by_kind = lambda name: {                                 # noqa: E731
        s["labels"]["kind"]: s["value"]
        for s in metrics[name]["series"]}
    assert by_kind("smp_mhc_streams") == {"lead_dense": 4, "full": 4}
    assert by_kind("smp_attn_latent_layers") == {"lead_dense": 1, "full": 4}
    a_call = {s["labels"]["pass"]: s["value"]
              for s in metrics["smp_mhc_bytes"]["series"]}
    assert a_call == hyper_connection.mhc_bytes(8, 4, 32, 4)


# ----------------------------------------- layers, the model, the shares

KINDS = {
    "lead_dense": {"layer_types": ["full_attention"],
                   "mlp_layer_types": ["dense"]},
    "full": {"layer_types": ["full_attention"],
             "mlp_layer_types": ["sparse"], "first_k_dense_replace": 0},
    "five_layers": {},
}


@pytest.mark.parametrize("kind", [
    pytest.param("lead_dense", marks=pytest.mark.slow),
    pytest.param("full", marks=pytest.mark.slow), "five_layers"])
def test_layer_kind_forward_and_gradients(kind, monkeypatch):
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    cfg = xing4tiny.config(**KINDS[kind])
    module, params, w, ids = model_and_reference(cfg)
    if kind != "five_layers":
        assert xing4_weights.plan(cfg)[0] == (kind,)
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
    assert set(got) == set(want) == set(xing4_weights.spec_for(cfg))
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(got[name]) / scale, np.asarray(want[name]) / scale,
            atol=5e-4, err_msg=name)
        if name.endswith("e_score_correction_bias"):
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

    cfg = xing4tiny.config(
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
        make = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))
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
        spec = xing4_weights.spec_for(cfg)
        for name, norm in change.items():
            moved = float(jnp.sqrt(jnp.sum(jnp.square(got[name] - w[name]))))
            if name.endswith("e_score_correction_bias"):
                assert moved == 0.0 and float(first_grad[name]) == 0.0
                continue
            assert moved > 0, name
            # as the driver reads it: against the leaf ``make_leaf`` makes
            # (for alpha and bias that is the change plus their constant)
            raw = weights.make_leaf(np.uint32(0), name, *spec[name])
            assert float(jnp.sqrt(jnp.sum(jnp.square(
                got[name] - raw)))) == pytest.approx(
                    float(norm), rel=2e-2, abs=1e-6), name
    finally:
        smp.reset()


def test_the_eight_chips_shares_add_up_to_the_uncut_layer(monkeypatch):
    """The guide's test of a chip's share, on one routed layer: each
    share's held experts' output under the one router and the one bias,
    with the connections, the norms, latent attention (its heads' parts
    sum by the output projection's linearity, which
    ``test_latent_attention_under_tp_2_is_tp_1`` holds) and the shared
    expert counted once, sums to the uncut reference's layer, and every
    assignment lands on exactly one share."""
    monkeypatch.setattr(moe, "ROWS_PER_CHUNK", 8)
    n, D, K, F, E = 8, 32, 4, 16, 16
    cfg = xing4tiny.config(
        layer_types=["full_attention"], mlp_layer_types=["sparse"],
        first_k_dense_replace=0, n_routed_experts=E, experts_held_first=0)
    w = jax.jit(lambda s: xing4_weights.make_weights(cfg, s))(np.uint32(4))
    lw = {k[len("model.layers.full."):]: v[0] for k, v in w.items()
          if k.startswith("model.layers.full.")}
    X = jax.random.normal(jax.random.key(0), (2, 24, 4, D))
    run, = reference.layer_runs(cfg)
    want, loads = reference.layer(cfg, X, lw, run, "float32")

    # everything up to the routed experts, once, by the reference
    X_mid, _ = reference.connected(
        cfg, X, lw, "attn_hc", "input_layernorm.weight",
        lambda z: (reference.attention(cfg, z, lw, "float32"),), "float32")
    pre, post, res = reference.coefficients(cfg, X_mid, lw, "ffn_hc",
                                            "float32")
    u = sum(pre[..., i, None] * X_mid[:, :, i] for i in range(4))
    normed = shared.rms_norm(u, lw["post_attention_layernorm.weight"],
                             cfg["rms_norm_eps"])
    m = "mlp."
    shared_out = shared.gated_mlp(
        normed, lw[m + "shared_experts.gate_proj.weight"],
        lw[m + "shared_experts.up_proj.weight"],
        lw[m + "shared_experts.down_proj.weight"], "float32")
    routed, landed, held = jnp.zeros_like(normed), 0, E // n
    for s in range(n):
        first = held * s
        layer = moe.DistributedDroplessMoE(
            hidden_size=D, intermediate_size=F, num_experts=E, top_k=K,
            held=(first, held), score="sigmoid", selection_bias=True,
            routed_scaling=2.0)
        part = laguna.experts_from_hf(
            lw[m + "experts.gate_proj.weight"][first:first + held],
            lw[m + "experts.up_proj.weight"][first:first + held],
            lw[m + "experts.down_proj.weight"][first:first + held], xp=jnp)
        part = {k[len("output/"):]: v for k, v in part.items()}
        part["router/kernel"] = lw[m + "gate.weight"].T
        part["router/selection_bias"] = lw[m + "gate.e_score_correction_bias"]
        shapes = jax.eval_shape(layer.init, jax.random.key(0),
                                normed)["params"]
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
    y = routed + shared_out
    got = jnp.stack([
        sum(res[..., i, j, None] * X_mid[:, :, j] for j in range(4))
        + post[..., i, None] * y for i in range(4)], axis=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-4)


# ---------------------------------------------------------- the translator

def test_translator_there_and_back():
    cfg = xing4tiny.config()
    view = xing4_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = xing4.translate_state_dict_to_hf(flat, view)
    a = "model.layers.0.self_attn."
    assert sd[a + "q_a_proj.weight"].shape == (24, 32)
    assert sd[a + "q_a_layernorm.weight"].shape == (24,)
    assert sd[a + "q_b_proj.weight"].shape == (4 * 24, 24)
    assert sd[a + "kv_a_proj_with_mqa.weight"].shape == (16 + 8, 32)
    assert sd[a + "kv_a_layernorm.weight"].shape == (16,)
    assert sd[a + "kv_b_proj.weight"].shape == (4 * 32, 16)
    assert sd[a + "o_proj.weight"].shape == (32, 4 * 16)
    assert sd["model.layers.0.mlp.gate_proj.weight"].shape == (48, 32)
    assert sd["model.layers.0.attn_hc.phi.weight"].shape == (24, 4 * 32)
    assert sd["model.layers.0.ffn_hc.norm.weight"].shape == (4 * 32,)
    assert sd["model.layers.3.ffn_hc.alpha"].shape == (3,)
    assert sd["model.layers.3.attn_hc.bias"].shape == (24,)
    assert sd["model.layers.3.mlp.gate.weight"].shape == (16, 32)
    assert sd["model.layers.3.mlp.gate.e_score_correction_bias"].shape == (
        16,)
    assert sd["model.layers.3.mlp.shared_experts.up_proj.weight"].shape == (
        16, 32)
    assert sd["lm_head.weight"].shape == (64, 32)
    # the held experts keep their published indices 4 .. 7
    assert "model.layers.2.mlp.experts.4.up_proj.weight" in sd
    assert "model.layers.2.mlp.experts.3.up_proj.weight" not in sd
    assert "model.layers.2.mlp.experts.8.up_proj.weight" not in sd
    # rope columns: the program's halves are the class's pairs, evens first
    q_up = flat["transformer/seq_layers_0_lead_dense/layer/attention/"
                "q_up/kernel"][0]                     # [r_q, H, nope + rope]
    hf = sd[a + "q_b_proj.weight"].T.reshape(24, 4, 24)
    np.testing.assert_array_equal(hf[..., :16], q_up[..., :16])
    np.testing.assert_array_equal(hf[..., 16::2], q_up[..., 16:20])
    np.testing.assert_array_equal(hf[..., 17::2], q_up[..., 20:])
    back = xing4.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_pairs_in_the_reference_are_halves_in_the_program():
    """The reference rotates interleaved pairs on the class's columns, the
    program halves on the translator's permuted ones: one attention."""
    cfg = xing4tiny.config(**KINDS["lead_dense"])
    _, params, w, ids = model_and_reference(cfg)
    lw = {k[len("model.layers.lead_dense."):]: v[0] for k, v in w.items()
          if k.startswith("model.layers.lead_dense.")}
    x = jax.random.normal(jax.random.key(3), (2, 12, 32))
    latent, rotary = xing4.latent_fields(cfg)
    layer = latent_attention.DistributedLatentAttentionLayer(
        num_attention_heads=4, hidden_size=32, **latent, **rotary)
    prefix = "transformer/seq_layers_0_lead_dense/layer/attention/"
    part = {k[len(prefix):]: v[0] for k, v in flat_of(params).items()
            if k.startswith(prefix) and "hyper_connection" not in k
            and "layernorm" not in k}
    shapes = jax.eval_shape(layer.init, jax.random.key(0), x)["params"]
    np.testing.assert_allclose(
        np.asarray(layer.apply({"params": unflatten(part, shapes)}, x)),
        np.asarray(reference.attention(cfg, x, lw, "float32")), atol=2e-5)


def test_xing4_is_a_registered_family():
    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("Xing4ForCausalLM")
    assert family.name == "xing40"
    assert huggingface.family_for("xing4_0") is family
    assert family.config_to_smp is xing4.config_to_smp
    assert huggingface.family_for("lfm2_moe").name == "lfm2moe"
