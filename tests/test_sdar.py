"""SDAR-MoE trained by block diffusion, in the program, at small sizes on
the CPU, seeded random weights: the two-copy pass against the definition
block by block (the noisy half's logits of block k are those of the model
run block-causally on [x0 before k ; xt of k] alone), the model and its
gradients against the plain reference (``benchmark/reference/sdar.py``),
the model through ``DistributedModel`` + ``@smp.step`` for three steps,
rotary at positions given per token, the head over the positions it is
asked for, the loss and its counters by hand, the attention's scope, the
Hugging Face translator there and back. (The eight chips' shares adding up
to the uncut layer is a case of ``tests/test_mellum.py``'s test.)"""

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

import sdartiny  # noqa: E402
from benchmark import loader, sdar_weights  # noqa: E402
from benchmark.reference import sdar as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import diffusion, transformer  # noqa: E402
from smdistributed_modelparallel_tpu.nn.huggingface import sdar  # noqa: E402
from smdistributed_modelparallel_tpu.ops import attention as ops_attention  # noqa: E402

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "sdar_moe.py"),
    "sdar_moe_for_tests")
MASK = sdartiny.TINY["mask_token_id"]


@pytest.fixture(autouse=True)
def one_device_mesh():
    """Every test starts on a mesh of one device (see
    ``tests/test_mellum.py``)."""
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


def noisy_batch(seed, batch=2, L=24, block=4):
    """``(clean, noisy, rates)`` as the benchmark draws them."""
    made = sdar_weights.diffusion_batches(
        np.uint32(seed), 1, batch, L, MASK, 8, block, 1e-3, MASK)
    return made["clean"][0], made["noisy"][0], made["rates"][0]


def model_and_reference(cfg, seed=0, L=24):
    """The program's module with seeded weights, the same weights under the
    reference's names, and a batch with its noise."""
    module = builder.module(cfg)
    clean, noisy, rates = noisy_batch(seed, L=L, block=cfg["block_length"])
    stream = diffusion.two_copy_stream(clean, noisy)
    shapes = jax.eval_shape(module.init, jax.random.key(0), stream)["params"]
    w = jax.jit(lambda s: sdar_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, (clean, noisy, rates)


# ------------------------------------------------------------- the plan

def test_plan_is_one_kind_under_the_block_diffusion_mask():
    cfg = sdartiny.config()
    pattern, kinds = sdar_weights.plan(cfg)
    assert pattern == ("full", "full") and list(kinds) == ["full"]
    kw = kinds["full"]
    assert kw["qk_norm"] is True and kw["block_diffusion"] == 4
    assert kw["window_size"] is None and kw["rotary_yarn"] is None
    assert (kw["num_attention_heads"], kw["num_key_value_heads"]) == (4, 1)
    assert kw["rotary_dim"] == 8 and kw["rotary_emb_base"] == 1000000.0
    assert kw["moe_shared_intermediate_size"] == 0 and kw["moe_norm_topk"]
    assert (kw["num_experts"], kw["moe_top_k"], kw["moe_held"]) == \
        (16, 4, (4, 4))
    kwargs = sdar.config_to_smp(sdar_weights.hf_view(cfg))
    assert kwargs["head_positions"] == 0.5
    assert not kwargs["tie_input_output_embedding"]


def test_published_plan_at_full_depth():
    import json

    with open(os.path.join(_REPO, sdartiny.CONFIG)) as f:
        cfg = json.load(f)
    whole = dict(cfg, num_attention_heads=32, num_key_value_heads=4,
                 num_experts=128)
    whole.pop("block_length")
    pattern, kinds = sdar.layer_plan(whole)
    assert pattern == ("full",) * 48
    assert transformer.pattern_segments(pattern) == [(1, [("full", 48)])]
    kw = kinds["full"]
    assert kw["block_diffusion"] == sdar.BLOCK_LENGTH == 4
    assert (kw["num_attention_heads"], kw["num_key_value_heads"],
            kw["num_experts"], kw["moe_top_k"], kw["intermediate_size"]) == (
                32, 4, 128, 8, 768)


def test_plan_refuses_what_the_family_does_not_have():
    from smdistributed_modelparallel_tpu.utils.exceptions import (
        SMPValidationError,
    )

    view = sdar_weights.hf_view(sdartiny.config())
    with pytest.raises(SMPValidationError, match="mlp_only_layers"):
        sdar.layer_plan(dict(view, mlp_only_layers=[0]))
    with pytest.raises(SMPValidationError, match="use_sliding_window"):
        sdar.layer_plan(dict(view, use_sliding_window=True))
    with pytest.raises(SMPValidationError, match="rope_scaling"):
        sdar.layer_plan(dict(view, rope_scaling={"type": "yarn"}))
    with pytest.raises(SMPValidationError, match="attention_bias"):
        sdar.config_to_smp(dict(view, attention_bias=True))
    with pytest.raises(SMPValidationError, match="grouped KV heads"):
        sdar.layer_plan(dict(view, num_key_value_heads=4))


# ----------------------------------------------------------- the mask

@pytest.mark.parametrize("half,block", [(8, 4), (12, 2), (12, 12), (9, 3)])
def test_mask_is_the_definition(half, block):
    got = np.asarray(ops_attention.block_diffusion_mask(2 * half, block))
    want = np.zeros((2 * half, 2 * half), bool)
    for i in range(2 * half):
        for j in range(2 * half):
            bi, bj = (i % half) // block, (j % half) // block
            if i < half and j < half:
                want[i, j] = bi == bj
            elif i < half:
                want[i, j] = bj < bi
            elif j >= half:
                want[i, j] = bj <= bi
    np.testing.assert_array_equal(got, want)
    idx = jnp.arange(2 * half)
    np.testing.assert_array_equal(
        np.asarray(reference.live(idx, idx, half, block)), want)
    # about a quarter of the pairs are live: L^2 + L B of (2 L)^2
    assert want.sum() == half * half + half * block


def test_attention_core_refuses_a_stream_that_is_not_two_copies():
    q = jnp.zeros((1, 20, 2, 8))
    with pytest.raises(ValueError, match="two copies of whole blocks"):
        ops_attention.attention_core(q, q, q, block_diffusion=4)
    with pytest.raises(ValueError, match="no window"):
        ops_attention.attention_core(
            q[:, :16], q[:, :16], q[:, :16], block_diffusion=4, window=4)


# ----------------------------- the two-copy pass, block by block

def test_noisy_logits_of_a_block_are_the_model_on_its_prefix_alone():
    """Block k's noisy logits = the same weights run on [x0 before k ; xt
    of k] alone, (k + 1) B tokens at positions 0 .., under the plain
    block-causal mask (a token sees the blocks up to its own, so the last
    block, the noisy one, sees the clean text before it and itself both
    ways, and the clean text never sees it)."""
    cfg = sdartiny.config()
    B = cfg["block_length"]
    module, params, _, (clean, noisy, _) = model_and_reference(cfg, L=16)
    two_copy = module.apply(
        {"params": params}, diffusion.two_copy_stream(clean, noisy))
    assert two_copy.shape == (2, 16, cfg["vocab_size"])

    kwargs = sdar.config_to_smp(sdar_weights.hf_view(cfg))
    kwargs.update(
        head_positions=None, causal_mask_size=None,
        layer_kinds={k: {f: v for f, v in kw.items()
                         if f != "block_diffusion"}
                     for k, kw in kwargs["layer_kinds"].items()})
    plain = transformer.DistributedTransformerLMHead(**kwargs)
    for k in range(16 // B):
        T = (k + 1) * B
        ids = jnp.concatenate(
            [clean[:, :k * B], noisy[:, k * B:T]], axis=1)
        blocks = jnp.arange(T) // B
        mask = (blocks[None, :] <= blocks[:, None])[None, None]
        alone = plain.apply({"params": params}, ids, attention_mask=mask)
        np.testing.assert_allclose(
            np.asarray(two_copy[:, k * B:T]), np.asarray(alone[:, -B:]),
            atol=2e-4, err_msg=f"block {k}")


def test_the_clean_half_never_sees_the_noise():
    """Another draw of the noise moves the noisy half's states and leaves
    the clean half's alone (read before the head, which drops them)."""
    cfg = sdartiny.config()
    module, params, _, (clean, noisy, _) = model_and_reference(cfg)
    other = noisy_batch(99)[1]
    other = jnp.where(other == MASK, MASK, clean)    # the same text
    assert bool(jnp.any(other != noisy))

    def states(noisy):
        kwargs = sdar.config_to_smp(sdar_weights.hf_view(cfg))
        whole = transformer.DistributedTransformerLMHead(
            **dict(kwargs, head_positions=None))
        return whole.apply(
            {"params": params}, diffusion.two_copy_stream(clean, noisy))

    a, b = states(noisy), states(other)
    L = clean.shape[1]
    np.testing.assert_array_equal(np.asarray(a[:, L:]), np.asarray(b[:, L:]))
    assert float(jnp.max(jnp.abs(a[:, :L] - b[:, :L]))) > 1e-3


# ------------------------------- the model against the plain reference

def test_model_forward_and_gradients_are_the_references():
    cfg = sdartiny.config()
    module, params, w, (clean, noisy, rates) = model_and_reference(cfg)
    stream = diffusion.two_copy_stream(clean, noisy)

    def program(params):
        logits = module.apply({"params": params}, stream)
        return diffusion.masked_diffusion_loss(
            logits, clean, noisy, rates, MASK)[0]

    def plain(w):
        total, _ = reference.diffusion_loss_sum(
            cfg, w, clean, noisy, rates, MASK, "float32")
        return total / clean.size

    np.testing.assert_allclose(
        np.asarray(module.apply({"params": params}, stream)),
        np.asarray(reference.forward(cfg, w, clean, noisy)[0]), atol=2e-4)
    np.testing.assert_allclose(
        float(program(params)), float(plain(w)), rtol=1e-5)
    got = builder.hf_from_flat(cfg, flat_of(jax.grad(program)(params)))
    want = jax.grad(plain)(w)
    assert set(got) == set(want)
    for name in want:
        scale = float(jnp.max(jnp.abs(want[name]))) + 1e-6
        np.testing.assert_allclose(
            np.asarray(got[name]) / scale, np.asarray(want[name]) / scale,
            atol=3e-4, err_msg=name)
    assert float(jnp.max(jnp.abs(
        want["model.layers.full.self_attn.k_norm.weight"]))) > 0


def test_two_layer_model_trains_three_steps_as_the_reference_does():
    import optax

    import smdistributed_modelparallel_tpu as smp

    cfg = sdartiny.config()
    lr, steps = 1e-3, 3
    mix = dict(sdartiny.TINY_MIX, batch_pool=steps, seq=32,
               noise={"kind": "linear_per_block", "eps": 1e-3})
    batches = sdar_weights.make_batches(cfg, mix, np.uint32(5))
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(optax.adamw(lr), model)
        step = builder.train_step(smp)
        step(model, batches[0])        # the init pass: parameters exist
        w = builder.hf_from_flat(cfg, flat_of(model.params))
        losses, rows, masked = [], [], 0
        for i in range(steps):
            out = step(model, batches[i])
            optimizer.step()
            loss, stats, counts = out.stack()
            losses.append(float(jnp.mean(loss)))
            summary = smp.nn.record_moe_stats(stats)
            assert summary["dropped"] == 0
            rows.append(summary["local"])
            said = smp.nn.record_diffusion_stats(counts)
            assert said["data_tokens"] == 4 * 32
            assert said["loss_tokens"] == int(
                jnp.sum(batches[i]["noisy"] == MASK))
            masked += said["loss_tokens"]
        assert 0.2 < masked / (steps * 4 * 32) < 0.8
        want, _, _, loads, _ = reference.follow_steps(
            *reference.hashable(cfg), dict(w), batches, np.uint32(0), lr,
            "float32", steps)
        np.testing.assert_allclose(losses, np.asarray(want), atol=3e-4)
        assert rows[0] == int(jnp.sum(loads))
        assert len(summary["max_over_mean"]) == 2      # two expert layers
    finally:
        smp.reset()


# --------------------------------- rotary at positions, head on request

def test_rotary_takes_a_position_for_each_token():
    q = jax.random.normal(jax.random.key(0), (2, 12, 3, 8))
    k = jax.random.normal(jax.random.key(1), (2, 12, 1, 8))
    rot = lambda *a, **kw: transformer.apply_rotary(   # noqa: E731
        *a, rotary_dim=8, base=1e6, neox_style=True, **kw)
    counted = rot(q, k)
    given = rot(q, k, positions=jnp.arange(12))
    for a, b in zip(counted, given):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a two-copy stream: each half rotates as a sequence of its own
    twice = rot(q, k, positions=jnp.arange(12) % 6)
    for whole, x in zip(twice, (q, k)):
        for half in (slice(0, 6), slice(6, 12)):
            alone = rot(x[:, half], x[:, half])[0]
            np.testing.assert_allclose(
                np.asarray(whole[:, half]), np.asarray(alone), atol=1e-6)
    # one row of positions a sequence
    per_row = jnp.stack([jnp.arange(12), jnp.arange(12) + 5])
    shifted = rot(q, k, positions=per_row)
    by_offset = rot(q, k, offset=jnp.asarray([0, 5]))
    np.testing.assert_allclose(
        np.asarray(shifted[0]), np.asarray(by_offset[0]), atol=1e-6)


@pytest.mark.parametrize("asked,got", [(None, 16), (0.5, 8), (0.375, 6)])
def test_head_makes_logits_for_the_positions_it_is_asked_for(asked, got):
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    cfg = sdartiny.config()
    kwargs = sdar.config_to_smp(sdar_weights.hf_view(cfg))
    module = transformer.DistributedTransformerLMHead(
        **dict(kwargs, head_positions=asked))
    ids = jnp.zeros((2, 16), jnp.int32)
    params = module.init(jax.random.key(0), ids)["params"]
    telemetry.reset()
    logits = module.apply({"params": params}, ids)
    assert logits.shape == (2, got, cfg["vocab_size"])
    whole = transformer.DistributedTransformerLMHead(
        **dict(kwargs, head_positions=None)).apply({"params": params}, ids)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(whole[:, :got]), atol=1e-5)
    series = telemetry.report()["metrics"].get(
        "smp_lm_head_positions", {"series": []})["series"]
    by = {s["labels"]["which"]: s["value"] for s in series}
    assert by == ({} if asked is None else {"computed": got, "input": 16})


# ------------------------------------------- the objective, by hand

def test_noise_is_drawn_per_block_and_only_masks():
    clean, noisy, rates = noisy_batch(1, batch=64, L=256)
    assert rates.shape == (64, 64)
    assert float(rates.min()) >= 1e-3 and float(rates.max()) <= 1.0
    changed = noisy != clean
    np.testing.assert_array_equal(
        np.asarray(noisy[changed]), np.full(int(changed.sum()), MASK))
    assert int(clean.max()) < MASK
    # a block is masked about as often as its rate says
    per_block = changed.reshape(64, 64, 4).mean(axis=-1)
    assert abs(float(per_block.mean()) - float(rates.mean())) < 0.01
    assert float(jnp.corrcoef(per_block.ravel(), rates.ravel())[0, 1]) > 0.8
    assert abs(float(rates.mean()) - 0.5) < 0.02


def test_loss_is_the_weighted_sum_over_masked_positions():
    clean = jnp.asarray([[1, 2, 3, 4, 5, 6, 7, 8]])
    noisy = jnp.asarray([[1, MASK, 3, MASK, MASK, 6, 7, 8]])
    rates = jnp.asarray([[0.5, 0.25]])
    logits = jax.random.normal(jax.random.key(0), (1, 8, 64))
    loss, counts = diffusion.masked_diffusion_loss(
        logits, clean, noisy, rates, MASK)
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    by_hand = -(logp[0, 1, 2] / 0.5 + logp[0, 3, 4] / 0.5
                + logp[0, 4, 5] / 0.25) / 8
    np.testing.assert_allclose(float(loss), by_hand, rtol=1e-5)
    assert int(counts["loss_tokens"]) == 3
    assert int(counts["data_tokens"]) == 8
    stream = diffusion.two_copy_stream(clean, noisy)
    np.testing.assert_array_equal(
        np.asarray(stream), np.concatenate([noisy, clean], axis=1))


# ------------------------------------------------------------ the scope

def test_attention_ops_carry_the_patterns_scope_forward_and_backward():
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    cfg = sdartiny.config(layer_types=["full_attention"])
    module, params, _, (clean, noisy, _) = model_and_reference(cfg, L=8)
    stream = diffusion.two_copy_stream(clean, noisy)

    def loss(p):
        return jnp.sum(module.apply({"params": p}, stream))

    import re

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    under = {n for n in names if "smp/attn/block_diffusion" in n}
    assert any("transpose(" in n for n in under)
    assert any("transpose(" not in n for n in under)
    assert not any("smp/attn/full" in n or "smp/attn/window" in n
                   for n in names)
    # Since PR 39 the attention's parts carry scopes of their own inside
    # the pattern's, and the stack one round the layers: the innermost is
    # a part's, the pattern's scope stays between the layer's and it.
    assert all(hlo_audit.scope_of(n) in (
        "smp/attn/qkv", "smp/attn/qk_norm", "smp/attn/core", "smp/attn/out")
        for n in under)
    assert any(hlo_audit.scopes_of(n) == (
        "smp/model/stack", "smp/layer/full", "smp/attn/block_diffusion",
        "smp/attn/core") for n in under)


# ---------------------------------------------------------- the translator

def test_translator_there_and_back():
    cfg = sdartiny.config()
    view = sdar_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = sdar.translate_state_dict_to_hf(flat, view)
    assert sd["model.layers.0.self_attn.q_proj.weight"].shape == (4 * 8, 32)
    assert sd["model.layers.0.self_attn.k_proj.weight"].shape == (8, 32)
    assert sd["model.layers.1.self_attn.q_norm.weight"].shape == (8,)
    assert sd["model.layers.1.mlp.gate.weight"].shape == (16, 32)
    assert not any("g_proj" in k or "shared_expert" in k for k in sd)
    assert "model.layers.1.mlp.experts.4.up_proj.weight" in sd
    assert "model.layers.1.mlp.experts.3.up_proj.weight" not in sd
    back = sdar.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_sdar_is_a_registered_family():
    import types

    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("SDARMoeForCausalLM")
    assert family.name == "sdarmoe"
    assert huggingface.family_for(
        types.SimpleNamespace(model_type="sdar_moe")) is family
    assert family.config_to_smp is sdar.config_to_smp
    assert huggingface.family_for("mellum").name == "mellum"


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(_REPO, "benchmark", "reference", "sdar.py")) as f:
        source = f.read()
    imports = [line for line in source.splitlines()
               if line.startswith(("import ", "from "))]
    assert imports and not any(
        "smdistributed_modelparallel_tpu" in line for line in imports)
