"""Where the untied LM head's vocabulary lives on a pp x tp mesh.

``DistributedTransformerLMHead`` splits ``lm_head``'s vocabulary dim, and the
logits it produces, over tp and pp where the vocabulary divides by both, over
tp where only that divides, and leaves the head whole otherwise
(``nn/transformer._lm_head_vocab_split``). Unsplit, every chip of the
four-chip cell computed the whole ``[tokens, V]`` product, logits and loss and
carried the whole kernel through the optimizer (PR 30). Read here from the
step as XLA compiled it for the CPU mesh, and held against the same module
run with no mesh at all.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import smdistributed_modelparallel_tpu as smp
from benchmark.builders import neox_tp

D, T, LAYERS, MICROBATCHES = 32, 32, 2, 2

# (pp, tp, vocabulary, train, shards the vocabulary ends up in)
_CASES = {
    "pp2_tp2": (2, 2, 1024, True, 4),
    "pp1_tp2": (1, 2, 1024, True, 2),
    "pp2_tp1": (2, 1, 1024, True, 2),
    "fill_drain_forward_only": (2, 2, 1024, False, 4),
    "vocab_divides_tp_alone": (2, 2, 1022, True, 2),
    "vocab_divides_neither": (2, 2, 1023, True, 1),
}


def _neox(vocab):
    """A small GPT-NeoX in the benchmark's configuration keys."""
    return {
        "hidden_size": D, "num_hidden_layers": LAYERS,
        "num_attention_heads": 4, "intermediate_size": 4 * D,
        "vocab_size": vocab, "max_position_embeddings": T,
        "rotary_pct": 0.25, "rotary_emb_base": 10000,
        "use_parallel_residual": True, "layer_norm_eps": 1e-5,
        "hidden_act": "gelu", "tie_word_embeddings": False,
        "initializer_range": 0.02,
    }


def _cell_loss(logits, ids):
    """``benchmark/builders/neox_tp.train_step``'s loss, line for line."""
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def _forward_only_step():
    @smp.step
    def step(model, ids):
        logits = model(ids)
        return _cell_loss(logits, ids), logits

    return step


def _gauge():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    series = telemetry.report()["metrics"]["smp_lm_head_vocab_shards"]
    (only,) = series["series"]
    return only["value"]


def _compiled_text(step):
    (runner,) = step._cache.values()
    compiled = runner.holder.get("compiled")
    if compiled is None:
        pytest.skip("AOT step executable unavailable on this backend")
    return compiled.as_text()


def _without_a_mesh(cfg, params, ids, train):
    """The unsplit model: the same module applied outside ``smp`` (no mesh,
    so no layer is partitioned), microbatch by microbatch; the step's
    gradients are the mean over microbatches."""
    smp.reset()
    module = neox_tp.module(cfg)

    def loss_fn(p, mb):
        logits = module.apply({"params": p}, mb)
        return _cell_loss(logits, mb), logits

    mbs = ids.reshape(MICROBATCHES, -1, ids.shape[-1])
    (loss, logits), grads = jax.vmap(
        jax.value_and_grad(loss_fn, has_aux=True), in_axes=(None, 0)
    )(params, mbs)
    grads = jax.tree_util.tree_map(lambda g: g.mean(0), grads)
    return np.asarray(loss), np.asarray(logits), grads if train else None


@pytest.mark.parametrize("case", list(_CASES))
def test_untied_head_splits_its_vocabulary_over_tp_and_pp(case):
    pp, tp, vocab, train, shards = _CASES[case]
    if jax.device_count() < pp * tp:
        pytest.skip("needs the CPU mesh")
    cfg = _neox(vocab)
    smp.reset()
    smp.init({"pipeline_parallel_degree": pp, "tensor_parallel_degree": tp,
              "ddp": True, "microbatches": MICROBATCHES},
             devices=jax.devices()[:pp * tp])
    model = smp.DistributedModel(neox_tp.module(cfg))
    ids = jax.random.randint(jax.random.key(0), (MICROBATCHES, T), 0, vocab)
    if train:
        step = neox_tp.train_step(smp)       # the cell's own step function
        loss, logits = step(model, ids).stack(), None
    else:
        step = _forward_only_step()
        loss, logits = step(model, ids).stack()
    text = _compiled_text(step)
    grads = jax.device_get(model.grads) if train else None
    params = jax.device_get(model.params)
    kernel = model.params["lm_head"]["kernel"]
    assert _gauge() == shards

    # A device holds V / shards columns of the kernel and of the logits;
    # nothing of the step is wider than that, and nothing gathers it back.
    local = vocab // shards
    assert kernel.addressable_shards[0].data.shape == (D, local)
    shapes = set(re.findall(r"\b\w+\[([\d,]+)\]", text))
    last_dims = {int(s.rsplit(",", 1)[-1]) for s in shapes}
    assert f"{D},{local}" in shapes                     # the kernel
    assert f"1,{T - 1},{local}" in shapes               # the logits
    wider = {vocab // k for k in (1, 2) if vocab // k > local}
    assert not wider & last_dims
    regathered = [
        line for line in text.splitlines()
        if re.search(r" (all-gather|all-to-all)(-start)?\(", line)
        and re.search(rf"\w+\[[\d,]*\b{local}\]", line)
    ]
    assert regathered == []

    want_loss, want_logits, want_grads = _without_a_mesh(
        cfg, params, np.asarray(ids), train
    )
    np.testing.assert_allclose(np.asarray(loss), want_loss,
                               rtol=1e-4, atol=1e-5)
    if logits is not None:
        np.testing.assert_allclose(
            np.asarray(logits), want_logits, rtol=1e-3, atol=2e-5
        )
    if train:
        assert jax.tree_util.tree_structure(grads) == \
            jax.tree_util.tree_structure(want_grads)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                a, b, rtol=1e-3, atol=2e-5
            ),
            grads, want_grads,
        )


def test_optimizer_state_sharding_keeps_the_vocabulary_axes():
    """``shard_optimizer_state`` adds rdp to a free dim of each moment
    through the dimension-wise merge of the providers' specs: the head's
    moments keep tp x pp on the vocabulary and take rdp on the hidden dim,
    and a step and an update run on that layout."""
    import optax

    from smdistributed_modelparallel_tpu.module_manager import path_key

    if jax.device_count() < 8:
        pytest.skip("needs the 8-device CPU mesh")
    cfg = _neox(1024)
    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "tensor_parallel_degree": 2,
              "ddp": True, "microbatches": MICROBATCHES,
              "shard_optimizer_state": True})
    model = smp.DistributedModel(neox_tp.module(cfg))
    optimizer = smp.DistributedOptimizer(optax.adamw(1e-3), model)
    step = neox_tp.train_step(smp)
    ids = jax.random.randint(jax.random.key(0), (2 * MICROBATCHES, T), 0, 1024)
    before = float(step(model, ids).reduce_mean())
    optimizer.step()
    after = float(step(model, ids).reduce_mean())
    assert after < before

    kernel = model.params["lm_head"]["kernel"]
    assert model.module_manager.spec_for("lm_head/kernel", kernel) \
        == P(None, ("tp", "pp"))
    assert kernel.sharding.spec == P(None, ("tp", "pp"))
    moments = {
        path_key(path): leaf.sharding.spec
        for path, leaf in jax.tree_util.tree_flatten_with_path(
            optimizer.opt_state)[0]
        if path_key(path).endswith("lm_head/kernel")
    }
    assert len(moments) == 2
    assert set(moments.values()) == {P("rdp", ("tp", "pp"))}
