"""The plain reference against the package's model at a tiny size: the same
seeded weights through ``builders/gpt2_zoo`` and through
``reference/decoder`` give the same logits and the same loss."""

import numpy as np
import pytest

import benchtiny  # noqa: F401
from benchmark import loader, weights

CFG = dict(model_type="gpt2", n_embd=64, n_head=4, n_layer=3,
           n_positions=48, vocab_size=160, layer_norm_epsilon=1e-5,
           n_inner=None, initializer_range=0.02)


@pytest.fixture(scope="module")
def made():
    import jax

    w = jax.jit(lambda s: weights.make_weights(CFG, s))(
        weights.seed_word(2 ** 31 + 3))
    ids = weights.token_batches(
        weights.seed_word(4), 1, 2, 48, CFG["vocab_size"])[0]
    return w, ids


def builder():
    import os

    return loader.load_module(
        os.path.join(benchtiny.ROOT, "benchmark", "builders", "gpt2_zoo.py"),
        "gpt2_zoo_for_test")


def test_weights_are_a_function_of_the_seed():
    import jax

    make = jax.jit(lambda s: weights.make_weights(CFG, s))
    a, b = make(weights.seed_word(11)), make(weights.seed_word(11))
    c = make(weights.seed_word(12))
    assert weights.seed_word(2 ** 32 + 11) == weights.seed_word(11)
    for name in a:
        assert np.array_equal(a[name], b[name])
        assert not np.array_equal(a[name], c[name])
    assert a["h.attn.c_attn.weight"].shape == (3, 64, 192)


def test_token_batches_rows_all_differ():
    ids = np.asarray(weights.token_batches(
        weights.seed_word(3), 4, 8, 32, 50257))
    rows = ids.reshape(-1, 32)
    assert len({tuple(r) for r in rows}) == len(rows)
    assert ids.min() >= 0 and ids.max() < 50257


def test_reference_logits_match_the_packages_model(made):
    from benchmark.reference import decoder

    w, ids = made
    b = builder()
    module = b.module(CFG)
    got = module.apply({"params": b.tree_from_hf(CFG, w)}, ids)
    want = decoder.forward(CFG, w, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_translator_round_trips(made):
    w, _ = made
    b = builder()
    flat = b.flat_from_hf(CFG, w)
    assert set(flat) == set(b.HF_TO_PATH.values())
    back = b.hf_from_flat(CFG, flat)
    assert set(back) == set(w) and all(back[k] is w[k] for k in w)


def test_reference_loss_and_gradient_agree_with_plain_autodiff(made):
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder, train

    w, ids = made
    loss, grads = jax.jit(
        lambda w, ids: train.loss_and_grads(CFG, w, ids, "float32"))(w, ids)

    def whole(w):
        logits = decoder.forward(CFG, w, ids)
        logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
        picked = jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1)
        return -jnp.mean(picked)

    want, want_g = jax.jit(jax.value_and_grad(whole))(w)
    assert float(loss) == pytest.approx(float(want), rel=1e-5)
    for name in grads:
        np.testing.assert_allclose(
            np.asarray(grads[name]), np.asarray(want_g[name]),
            atol=1e-6, rtol=1e-3)


def test_adamw_matches_optax(made):
    import jax
    import optax

    from benchmark.reference import train

    w, _ = made
    grads = jax.tree_util.tree_map(lambda x: 0.01 * x + 0.001, w)
    zeros = jax.tree_util.tree_map(lambda x: 0 * x, w)
    new, mu, nu = train.adamw(w, grads, zeros, zeros, 1, 1e-3)
    tx = optax.adamw(1e-3)
    updates, _ = tx.update(grads, tx.init(w), w)
    want = optax.apply_updates(w, updates)
    for name in w:
        np.testing.assert_allclose(np.asarray(new[name]),
                                   np.asarray(want[name]), rtol=1e-6,
                                   atol=1e-8)


@pytest.mark.parametrize("precision,low,high", [
    ("bfloat16", 1e-4, 0.1), ("float8", 0.01, 1.0)])
def test_lower_precisions_move_the_logits(made, precision, low, high):
    from benchmark.reference import decoder

    w, ids = made
    exact = np.asarray(decoder.forward(CFG, w, ids))
    rounded = np.asarray(decoder.forward(CFG, w, ids, precision))
    gap = np.abs(exact - rounded).max()
    assert low < gap < high


NEOX = dict(model_type="gpt_neox", hidden_size=64, num_attention_heads=4,
            num_hidden_layers=3, intermediate_size=256, vocab_size=160,
            max_position_embeddings=48, rotary_pct=0.25,
            rotary_emb_base=10000, use_parallel_residual=True,
            tie_word_embeddings=False, hidden_act="gelu",
            layer_norm_eps=1e-5, initializer_range=0.02)


def neox_builder():
    import os

    return loader.load_module(
        os.path.join(benchtiny.ROOT, "benchmark", "builders", "neox_tp.py"),
        "neox_tp_for_test")


@pytest.fixture(scope="module")
def neox_made():
    import jax

    w = jax.jit(lambda s: weights.make_weights(NEOX, s))(
        weights.seed_word(5))
    ids = weights.token_batches(
        weights.seed_word(6), 1, 2, 48, NEOX["vocab_size"])[0]
    return w, ids


def test_neox_translation_matches_the_repos_own_translator(neox_made):
    import types

    from smdistributed_modelparallel_tpu.nn.huggingface import gptneox

    w, _ = neox_made
    b = neox_builder()
    flat = b.flat_from_hf(NEOX, w)
    per_layer = {}
    for name, value in w.items():
        value = np.asarray(value)
        if name.startswith("gpt_neox.layers."):
            rest = name[len("gpt_neox.layers."):]
            for i in range(NEOX["num_hidden_layers"]):
                per_layer[f"gpt_neox.layers.{i}.{rest}"] = value[i]
        else:
            per_layer[name] = value
    want = gptneox.translate_hf_state_dict(
        per_layer, types.SimpleNamespace(**NEOX))
    assert set(flat) == set(want)
    for key in want:
        np.testing.assert_array_equal(np.asarray(flat[key]), want[key])
    back = b.hf_from_flat(NEOX, flat)
    for name in w:
        np.testing.assert_array_equal(np.asarray(back[name]),
                                      np.asarray(w[name]))


def test_neox_reference_logits_match_the_packages_model(neox_made):
    from benchmark.reference import decoder

    w, ids = neox_made
    b = neox_builder()
    module = b.module(NEOX)
    import jax
    from flax.core import meta

    from smdistributed_modelparallel_tpu.module_manager import path_key

    flat = b.flat_from_hf(NEOX, w)
    shapes = meta.unbox(jax.eval_shape(
        module.init, jax.random.key(0), ids)["params"])
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    assert {path_key(p) for p, _ in paths} == set(flat)
    tree = jax.tree_util.tree_unflatten(
        treedef, [flat[path_key(p)] for p, _ in paths])
    got = module.apply({"params": tree}, ids)
    want = decoder.forward(NEOX, w, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-5)
