"""Ouro's mechanisms in the program, at small sizes on the CPU, seeded
random weights, against the plain reference (``benchmark/reference/ouro.py``)
and against forms written out here: the looped stack against four tied
copies unrolled (states, and the shared weights' gradient as the copies'
sum), one pass as the stack that was there, the branch norm's placement, the
head and the exit gate on every pass's state cut into pieces, the exit
distribution and the gate-weighted loss with its entropy term and their
gradient through the gate, the model through ``DistributedModel`` +
``@smp.step`` with and without ``activation_checkpointing`` for three AdamW
steps, the two refusals, the gauges, the Hugging Face translator there and
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

import ourotiny  # noqa: E402
from benchmark import loader, ouro_weights  # noqa: E402
from benchmark.reference import ouro as reference  # noqa: E402
from smdistributed_modelparallel_tpu.nn import (  # noqa: E402
    exit_gate,
    transformer,
)
from smdistributed_modelparallel_tpu.nn.huggingface import ouro  # noqa: E402
from smdistributed_modelparallel_tpu.utils.exceptions import (  # noqa: E402
    SMPValidationError,
)

builder = loader.load_module(
    os.path.join(_REPO, "benchmark", "builders", "ouro_looped.py"),
    "ouro_looped_for_tests")

STACK = dict(
    num_layers=2, num_attention_heads=2, attention_head_size=16,
    hidden_size=32, intermediate_size=48, attention_dropout_prob=0.0,
    hidden_dropout_prob=0.0, layernorm_type="rms", layernorm_epsilon=1e-6,
    pre_layernorm=True, post_layernorm=False, activation="silu",
    gated_mlp=True, use_mlp_bias=False, use_qkv_bias=False,
    use_attn_dense_bias=False, rotary_dim=16, rotary_emb_base=1e6,
    gpt_neox_type_rotary=True, causal_mask_size=64, initializer_range=0.2)


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
    w = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))(
        np.uint32(seed + 11))
    params = unflatten(builder.flat_from_hf(cfg, w), shapes)
    return module, params, w, ids


def rms(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


# ------------------------------------------------------- the looped stack

def looped_and_single(**fields):
    looped = transformer.DistributedTransformer(
        **STACK, branch_layernorm=True, loop_steps=4, **fields)
    single = transformer.DistributedTransformer(
        **STACK, branch_layernorm=True, **fields)
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    params = looped.init(jax.random.key(0), x)["params"]
    # every norm's scale off 1, so that one left out shows
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.key(2), p.shape)
        if p.ndim <= 2 else p, params)
    return looped, single, params, x


def unrolled(single, copies, norm_scale, x):
    """Four copies of the stack, each with parameters of its own, the norm
    after each."""
    states = []
    for params in copies:
        x = rms(single.apply({"params": params}, x), norm_scale)
        states.append(x)
    return jnp.stack(states)


@pytest.mark.parametrize("checkpointing", [False, True],
                         ids=["kept", "checkpointed"])
def test_looped_stack_is_four_tied_copies_unrolled(checkpointing):
    looped, single, params, x = looped_and_single(
        activation_checkpointing=checkpointing)
    assert set(params) == {"seq_layers", "loop_norm"}
    layers = {"seq_layers": params["seq_layers"]}
    states = looped.apply({"params": params}, x)
    assert states.shape == (4, 2, 12, 32)
    want = unrolled(single, [layers] * 4, params["loop_norm"]["scale"], x)
    np.testing.assert_allclose(states, want, atol=2e-5)
    # a pass changes the state: the passes are not one pass four times
    assert float(jnp.max(jnp.abs(states[1] - states[0]))) > 0.1


def test_shared_weights_gradient_is_the_copies_gradients_summed():
    looped, single, params, x = looped_and_single(
        activation_checkpointing=True)
    probe = jax.random.normal(jax.random.key(3), (4, 2, 12, 32))
    layers = {"seq_layers": params["seq_layers"]}
    scale = params["loop_norm"]["scale"]

    got = jax.grad(lambda p: jnp.sum(
        probe * looped.apply({"params": p}, x)))(params)
    by_copy = jax.grad(lambda copies: jnp.sum(
        probe * unrolled(single, copies, scale, x)))([layers] * 4)
    want = jax.tree_util.tree_map(lambda *g: sum(g), *by_copy)
    for (path, a), b in zip(
            jax.tree_util.tree_flatten_with_path(got["seq_layers"])[0],
            jax.tree_util.tree_leaves(want["seq_layers"])):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a / scale, b / scale, atol=2e-5,
                                   err_msg=str(path))
    # each copy's part is its own: the sum is not four times one of them
    first, last = (jax.tree_util.tree_leaves(c)[0] for c in
                   (by_copy[0], by_copy[3]))
    assert float(jnp.max(jnp.abs(first - last))) > 1e-3


def test_one_pass_is_the_stack_that_was_there():
    """``loop_steps`` 1: no norm, no leading axis, the parameter tree of a
    stack built without the field, and the same program."""
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    plain = transformer.DistributedTransformer(**STACK)
    one = transformer.DistributedTransformer(
        **STACK, loop_steps=1, branch_layernorm=False)
    params = plain.init(jax.random.key(0), x)["params"]
    assert set(params) == {"seq_layers"}
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(one.init(jax.random.key(0), x)["params"])
    text = lambda m: jax.jit(                                # noqa: E731
        lambda p, x: m.apply({"params": p}, x)).lower(params, x).as_text()
    assert text(plain) == text(one)
    assert plain.apply({"params": params}, x).shape == (2, 12, 32)
    head = transformer.DistributedTransformerLMHead(
        **STACK, vocab_size=64, num_positions=64,
        embedding_dropout_prob=0.0, final_layernorm=True,
        use_positional_embedding=False, tie_input_output_embedding=False)
    ids = jnp.zeros((2, 12), jnp.int32)
    tree = head.init(jax.random.key(0), ids)["params"]
    assert set(tree) == {"word_embedding", "transformer", "ln_f", "lm_head"}
    assert set(tree["transformer"]) == {"seq_layers"}


# --------------------------------------------------------- the branch norm

def layer_of(**fields):
    kwargs = {k: v for k, v in STACK.items() if k != "num_layers"}
    return transformer.DistributedTransformerLayer(**kwargs, **fields)


def test_branch_norm_stands_on_the_branch_before_the_add():
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    layer = layer_of(branch_layernorm=True)
    params = layer.init(jax.random.key(0), x)["params"]
    assert {k for k in flat_of(params) if "layernorm" in k} == {
        "attention/layernorm/scale", "attention/branch_layernorm/scale",
        "output/layernorm/scale", "output/branch_layernorm/scale"}
    # scales of 0 on both branch norms: both branches vanish, x comes back
    zeroed = unflatten({k: (0 * v if "branch_layernorm" in k else v)
                        for k, v in flat_of(params).items()}, params)
    np.testing.assert_array_equal(layer.apply({"params": zeroed}, x), x)
    # and with the seeded scales the layer is the reference's sandwich
    lw = {k: v[0] for k, v in ouro.layer_to_hf(
        {k: v[None] for k, v in flat_of(params).items()}).items()}
    np.testing.assert_allclose(
        layer.apply({"params": params}, x),
        reference.layer(ourotiny.config(), x, lw, "float32"), atol=2e-5)


@pytest.mark.parametrize("other", ["parallel_attn_output",
                                   "add_cross_attention"])
def test_branch_norm_refuses_what_it_is_not_written_for(other):
    x = jnp.zeros((1, 4, 32))
    with pytest.raises(SMPValidationError, match=other):
        layer_of(branch_layernorm=True, **{other: True}).init(
            jax.random.key(0), x)


def test_layers_without_the_field_are_what_they_were():
    x = jax.random.normal(jax.random.key(1), (2, 12, 32))
    text = lambda m: jax.jit(m.init).lower(                   # noqa: E731
        jax.random.key(0), x).as_text()
    assert text(layer_of()) == text(layer_of(branch_layernorm=False))
    assert "branch" not in str(jax.tree_util.tree_structure(
        layer_of().init(jax.random.key(0), x)))


# ---------------------------------------------- head and gate, every pass

def test_model_gives_every_passes_logits_and_gate_as_the_reference_does():
    cfg = ourotiny.config()
    module, params, w, ids = model_and_reference(cfg)
    logits, gates = module.apply({"params": params}, ids)
    assert logits.shape == (4, 2, 24, 64) and gates.shape == (4, 2, 24)
    assert gates.dtype == jnp.float32
    want_logits, want_gates = reference.forward(cfg, w, ids)
    np.testing.assert_allclose(logits, want_logits, atol=3e-5)
    np.testing.assert_allclose(gates, want_gates, atol=3e-5)
    # the gate stands on its seeded bias, about -1
    assert -1.2 < float(jnp.mean(gates)) < -0.8


@pytest.mark.parametrize("piece", [None, 8, 5],
                         ids=["whole", "three_pieces", "six_pieces_of_4"])
def test_losses_of_a_pass_are_cut_into_pieces_and_put_together(piece):
    """Loss mode against logits mode, where the head takes a pass's
    positions at most ``loop_head_positions`` at a time (equal pieces: 4
    under a limit of 5) and where it takes them whole."""
    seq = 24
    module = transformer.DistributedTransformerLMHead(
        **dict(STACK, hidden_size=8, intermediate_size=8,
               num_attention_heads=1, attention_head_size=8, rotary_dim=8,
               num_layers=1, causal_mask_size=seq),
        vocab_size=64, num_positions=seq,
        embedding_dropout_prob=0.0, final_layernorm=True,
        use_positional_embedding=False, tie_input_output_embedding=False,
        branch_layernorm=True, loop_steps=2, loop_head_positions=piece)
    ids = jax.random.randint(jax.random.key(0), (1, seq), 0, 64)
    params = module.init(jax.random.key(0), ids)["params"]
    targets = exit_gate.next_token_targets(ids)
    losses, gates = jax.jit(lambda p: module.apply(
        {"params": p}, ids, targets=targets))(params)
    logits, gates_2 = jax.jit(lambda p: module.apply(
        {"params": p}, ids))(params)
    assert losses.shape == (2, 1, seq) and losses.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    want = -jnp.take_along_axis(
        logp[:, :, :-1], jnp.broadcast_to(
            ids[None, :, 1:, None], (2, 1, seq - 1, 1)), axis=-1)[..., 0]
    np.testing.assert_allclose(losses[:, :, :-1], want, atol=2e-5)
    assert float(jnp.max(jnp.abs(losses[:, :, -1]))) == 0.0   # ignored
    np.testing.assert_allclose(gates, gates_2, atol=1e-6)


# ------------------------------------------------ the gate-weighted loss

def test_exit_distribution_sums_to_one_and_is_the_definitions_products():
    z = 3.0 * jax.random.normal(jax.random.key(0), (4, 3, 7))
    log_p = exit_gate.exit_log_distribution(z)
    p = np.exp(np.asarray(log_p, np.float64))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    lam = 1 / (1 + np.exp(-np.asarray(z, np.float64)))
    want = np.stack([
        lam[0], lam[1] * (1 - lam[0]), lam[2] * (1 - lam[0]) * (1 - lam[1]),
        (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
    np.testing.assert_allclose(p, want, atol=1e-6)
    np.testing.assert_allclose(reference.exit_distribution(z), want,
                               atol=1e-6)
    # far out the products underflow; the logarithms do not
    far = exit_gate.exit_log_distribution(jnp.full((4, 1, 1), 200.0))
    assert np.all(np.isfinite(np.asarray(far)))
    # the last pass's own logit is not read
    moved = z.at[-1].add(5.0)
    np.testing.assert_array_equal(
        exit_gate.exit_log_distribution(moved), log_p)


def plain_loss(losses, z, beta, valid):
    """The loss's definition in float64 numpy."""
    lam = 1 / (1 + np.exp(-np.asarray(z, np.float64)))
    n = len(lam)
    p = np.stack([(lam[t] if t < n - 1 else 1.0)
                  * np.prod(1 - lam[:t], axis=0) for t in range(n)])
    entropy = -(p * np.log(p)).sum(axis=0)
    per = (p * np.asarray(losses, np.float64)).sum(axis=0) - beta * entropy
    valid = np.asarray(valid, np.float64)
    return (per * valid).sum() / valid.sum(), p, entropy


@pytest.mark.parametrize("beta", [0.0, 0.05, 1.0])
def test_exit_gated_loss_is_its_definition_and_its_counters(beta):
    key = jax.random.key(int(beta * 100))
    losses = 3 + jax.random.normal(key, (4, 2, 9))
    z = jax.random.normal(jax.random.fold_in(key, 1), (4, 2, 9)) - 1.0
    valid = jnp.arange(9)[None, :] < jnp.asarray([[9], [5]])
    loss, stats = exit_gate.exit_gated_loss(losses, z, beta, valid)
    want, p, entropy = plain_loss(losses, z, beta, valid)
    assert loss.dtype == jnp.float32
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    v = np.asarray(valid, np.float64)
    np.testing.assert_allclose(
        stats["exit_share"], (p * v).sum(axis=(1, 2)) / v.sum(), rtol=1e-6)
    np.testing.assert_allclose(
        stats["entropy"], (entropy * v).sum() / v.sum(), rtol=1e-6)
    np.testing.assert_allclose(
        stats["pass_loss"],
        (np.asarray(losses, np.float64) * v).sum(axis=(1, 2)) / v.sum(),
        rtol=1e-6)
    np.testing.assert_allclose(float(jnp.sum(stats["exit_share"])), 1.0,
                               atol=1e-6)
    # with no mask every position counts
    whole, _ = exit_gate.exit_gated_loss(losses, z, beta)
    np.testing.assert_allclose(
        whole, plain_loss(losses, z, beta, np.ones((2, 9)))[0], rtol=1e-6)


def test_loss_gradient_through_the_gate_is_the_references():
    """d loss / d gate logits and d loss / d losses against the reference's
    products and logarithms (autodiff of the plain definition)."""
    losses = 3 + jax.random.normal(jax.random.key(0), (4, 2, 9))
    z = jax.random.normal(jax.random.key(1), (4, 2, 9)) - 1.0

    def written_out(losses, z):
        p = reference.exit_distribution(z)
        entropy = -jnp.sum(p * jnp.log(p), axis=0)
        return jnp.mean(jnp.sum(p * losses, axis=0) - 0.05 * entropy)

    got = jax.grad(lambda a, b: exit_gate.exit_gated_loss(a, b, 0.05)[0],
                   argnums=(0, 1))(losses, z)
    want = jax.grad(written_out, argnums=(0, 1))(losses, z)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-8)
    np.testing.assert_allclose(got[1], want[1], rtol=1e-4, atol=1e-8)
    assert float(jnp.max(jnp.abs(got[1][:-1]))) > 1e-4
    assert float(jnp.max(jnp.abs(got[1][-1]))) == 0.0    # not read
    # the entropy term pulls the gate: without it the gradient differs
    bare = jax.grad(lambda b: exit_gate.exit_gated_loss(losses, b, 0.0)[0])(z)
    assert float(jnp.max(jnp.abs(bare - got[1]))) > 1e-5


def test_next_token_targets_shift_and_ignore_the_last():
    ids = jnp.arange(12).reshape(2, 6)
    targets = exit_gate.next_token_targets(ids)
    np.testing.assert_array_equal(targets[:, :-1], ids[:, 1:])
    np.testing.assert_array_equal(targets[:, -1], [-100, -100])


def test_record_exit_stats_sets_the_gauges():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    stats = {"exit_share": np.array([[0.4, 0.3, 0.2, 0.1],
                                     [0.2, 0.3, 0.2, 0.3]]),
             "entropy": np.array([1.0, 1.2]),
             "pass_loss": np.array([[4.0, 3.0, 2.0, 1.0]] * 2)}
    out = exit_gate.record_exit_stats(stats)
    assert out["exit_share"] == pytest.approx([0.3, 0.3, 0.2, 0.2])
    assert out["entropy"] == pytest.approx(1.1)
    metrics = telemetry.report()["metrics"]
    by_pass = {s["labels"]["pass"]: s["value"]
               for s in metrics["smp_exit_share"]["series"]}
    assert by_pass == pytest.approx(
        {"1": 0.3, "2": 0.3, "3": 0.2, "4": 0.2})
    assert metrics["smp_exit_entropy"]["series"][0]["value"] == \
        pytest.approx(1.1)
    assert {s["labels"]["pass"]: s["value"] for s in
            metrics["smp_exit_pass_loss"]["series"]} == {
                "1": 4.0, "2": 3.0, "3": 2.0, "4": 1.0}


# ------------------------------------------- the model through @smp.step

def test_model_loss_stats_and_gradient_by_leaf_are_the_references():
    cfg = ourotiny.config()
    module, params, w, ids = model_and_reference(cfg)
    targets = exit_gate.next_token_targets(ids)

    def objective(params):
        losses, gates = module.apply({"params": params}, ids, targets=targets)
        return exit_gate.exit_gated_loss(
            losses, gates, cfg["exit_entropy_weight"], targets != -100)

    (loss, stats), grads = jax.value_and_grad(objective, has_aux=True)(params)
    count = ids.shape[0] * (ids.shape[1] - 1)

    def plain(w):
        total, nll, share, entropy = reference.loss_parts(
            cfg, w, ids, "float32")
        return total / count, (nll / count, share / count, entropy / count)

    (want, (nll, share, entropy)), want_grads = jax.value_and_grad(
        plain, has_aux=True)(w)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    np.testing.assert_allclose(stats["pass_loss"], nll, rtol=2e-6)
    np.testing.assert_allclose(stats["exit_share"], share, rtol=2e-5)
    np.testing.assert_allclose(stats["entropy"], entropy, rtol=2e-5)
    got = builder.hf_from_flat(cfg, flat_of(grads))
    assert set(got) == set(want_grads)
    for name, ref in want_grads.items():
        scale = float(jnp.max(jnp.abs(ref)))
        assert scale > 0, name
        np.testing.assert_allclose(np.asarray(got[name]) / scale,
                                   np.asarray(ref) / scale, atol=2e-4,
                                   err_msg=name)


@pytest.mark.parametrize("checkpointing", [False, True],
                         ids=["kept", "checkpointed"])
def test_model_trains_three_steps_as_the_reference_does(checkpointing):
    import optax

    import smdistributed_modelparallel_tpu as smp

    cfg = ourotiny.config(module={"activation_checkpointing": checkpointing})
    lr, steps = 1e-3, 3
    batches = jax.random.randint(jax.random.key(2), (steps, 4, 32), 0, 64)
    smp.reset()
    smp.init({"microbatches": 2})
    try:
        model = smp.DistributedModel(builder.module(cfg))
        optimizer = smp.DistributedOptimizer(optax.adamw(lr), model)
        step = builder.train_step(smp, cfg["exit_entropy_weight"])
        step(model, batches[0])        # the init pass: parameters exist
        make = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))
        # a copy of its own: the step gives the loaded buffers up
        model.load_state_dict(builder.flat_from_hf(cfg, make(np.uint32(0))))
        losses, stats = [], []
        for ids in batches:
            out = step(model, ids)
            optimizer.step()
            loss, counters = out.stack()
            losses.append(float(jnp.mean(loss)))
            stats.append(smp.nn.record_exit_stats(counters))
        want, first_grad, change, want_stats = reference.follow_steps(
            *reference.hashable(cfg), make(np.uint32(0)), batches,
            np.uint32(0), lr, "float32", steps)
        np.testing.assert_allclose(losses, np.asarray(want), rtol=2e-5)
        for got, (nll, share, entropy) in zip(stats, want_stats):
            np.testing.assert_allclose(got["pass_loss"], nll, rtol=2e-5)
            np.testing.assert_allclose(got["exit_share"], share, rtol=2e-4)
            assert got["entropy"] == pytest.approx(float(entropy), rel=2e-4)
        got = builder.hf_from_flat(cfg, flat_of(model.params))
        w = make(np.uint32(0))
        for name, norm in change.items():
            moved = float(jnp.sqrt(jnp.sum(jnp.square(got[name] - w[name]))))
            assert moved > 0 and float(first_grad[name]) > 0, name
            assert moved == pytest.approx(float(norm), rel=2e-2, abs=1e-6), \
                name
    finally:
        smp.reset()


# ------------------------------------------------------------- refusals

def test_loop_refuses_a_pipeline_and_a_decode_cache():
    import smdistributed_modelparallel_tpu as smp

    looped = transformer.DistributedTransformer(**STACK, loop_steps=4)
    with pytest.raises(SMPValidationError, match="decode cache"):
        transformer.DistributedTransformer(
            **STACK, loop_steps=4, decode=True).init(
                jax.random.key(0), jnp.zeros((1, 4, 32)))
    cfg = ourotiny.config()
    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "microbatches": 2})
    try:
        with pytest.raises(SMPValidationError, match="pipeline executor"):
            looped.pipeline_spec()
        with pytest.raises(SMPValidationError, match="pipeline executor"):
            builder.module(cfg).pipeline_spec()
        with pytest.raises(SMPValidationError, match="pipeline executor"):
            looped.init(jax.random.key(0), jnp.zeros((1, 4, 32)))
        # one pass is pipelined as it was
        assert transformer.DistributedTransformer(
            **STACK).pipeline_spec().num_layers == 2
    finally:
        smp.reset()
    # at pp = 1 a looped stack says what a pipeline would cut
    smp.init({"microbatches": 1}, devices=jax.devices()[:1])
    assert looped.pipeline_spec().num_layers == 2


def test_loop_gauges_count_passes_and_layer_passes():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    jax.eval_shape(
        transformer.DistributedTransformer(**STACK, loop_steps=3).init,
        jax.random.key(0), jnp.zeros((1, 4, 32)))
    metrics = telemetry.report()["metrics"]
    assert metrics["smp_loop_passes"]["series"][0]["value"] == 3
    assert metrics["smp_loop_layer_passes"]["series"][0]["value"] == 6


def test_loop_ops_carry_their_scopes():
    cfg = ourotiny.config()
    module, params, _, ids = model_and_reference(cfg)
    targets = exit_gate.next_token_targets(ids)

    def objective(params):
        losses, gates = module.apply({"params": params}, ids, targets=targets)
        return exit_gate.exit_gated_loss(losses, gates, 0.05)[0]

    text = jax.jit(jax.grad(objective)).lower(params).as_text(debug_info=True)
    for scope in ("smp/model/loop", "smp/model/stack", "smp/head/exit_gate",
                  "smp/layer/branch_norm", "smp/head/logits",
                  "smp/head/loss"):
        assert scope in text, scope
    assert "smp/head/norm" not in text      # the stack's own, after a pass


# ----------------------------------------------------------- translator

def test_config_to_smp_names_the_loop_and_the_sandwich():
    kwargs = ouro.config_to_smp(ouro_weights.hf_view(ourotiny.config()))
    assert kwargs["loop_steps"] == 4 and kwargs["branch_layernorm"] is True
    assert (kwargs["pre_layernorm"], kwargs["post_layernorm"]) == (True, False)
    assert kwargs["num_layers"] == 2 and kwargs["layernorm_type"] == "rms"
    assert kwargs["rotary_dim"] == kwargs["attention_head_size"] == 16
    assert kwargs["rotary_emb_base"] == 1e6
    assert kwargs["gpt_neox_type_rotary"] is True
    assert kwargs["tie_input_output_embedding"] is False
    for key, value in (("num_key_value_heads", 1), ("rope_scaling", {"f": 2}),
                       ("use_sliding_window", True),
                       ("attention_bias", True)):
        with pytest.raises(SMPValidationError, match=key):
            ouro.config_to_smp(ourotiny.config(**{key: value}))


def test_translator_there_and_back():
    cfg = ourotiny.config()
    view = ouro_weights.hf_view(cfg)
    module = builder.module(cfg)
    shapes = flat_of(jax.eval_shape(
        module.init, jax.random.key(0),
        jnp.zeros((1, 8), jnp.int32))["params"])
    rng = np.random.default_rng(0)
    flat = {k: rng.normal(size=v.shape).astype(np.float32)
            for k, v in shapes.items()}
    sd = ouro.translate_state_dict_to_hf(flat, view)
    layer = "model.layers.1."
    assert sd[layer + "self_attn.q_proj.weight"].shape == (32, 32)
    assert sd[layer + "self_attn.o_proj.weight"].shape == (32, 32)
    assert sd[layer + "mlp.gate_proj.weight"].shape == (48, 32)
    assert sd[layer + "mlp.down_proj.weight"].shape == (32, 48)
    for norm in ("input_layernorm", "input_layernorm_2",
                 "post_attention_layernorm", "post_attention_layernorm_2"):
        assert sd[f"{layer}{norm}.weight"].shape == (32,)
    assert sd["model.norm.weight"].shape == (32,)
    assert sd["model.early_exit_gate.weight"].shape == (1, 32)
    assert sd["model.early_exit_gate.bias"].shape == (1,)
    assert sd["lm_head.weight"].shape == (64, 32)
    assert len(sd) == 5 + 2 * 11
    # head h of q is rows h * 16 .. of q_proj, columns of the fused kernel
    qkv = flat["transformer/seq_layers/layer/attention/qkv/kernel"]
    np.testing.assert_array_equal(
        sd[layer + "self_attn.k_proj.weight"][16:32], qkv[1, :, 1, 1].T)
    np.testing.assert_array_equal(
        sd[layer + "input_layernorm_2.weight"],
        flat["transformer/seq_layers/layer/attention/branch_layernorm/"
             "scale"][1])
    back = ouro.translate_hf_state_dict(sd, view)
    assert set(back) == set(flat)
    for key in flat:
        np.testing.assert_array_equal(back[key], flat[key])


def test_ouro_is_a_registered_family_resolved_without_transformers():
    from smdistributed_modelparallel_tpu.nn import huggingface

    family = huggingface.family_for("OuroForCausalLM")
    assert family.name == "ouro"
    assert huggingface.family_for("ouro") is family
    assert family.config_to_smp is ouro.config_to_smp
    assert family.translate_from_hf is ouro.translate_hf_state_dict
