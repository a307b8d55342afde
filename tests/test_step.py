"""Step-engine end-to-end tests.

Mirrors the reference's central fixture strategy (``SMPTestBase``,
``test/torch/smp_test_base.py``, SURVEY §4): run the same model with and
without the framework and compare losses/gradients/parameters.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import smdistributed_modelparallel_tpu as smp
from tests.models import MLP, TinyTransformerLM, softmax_xent


def make_data(key, n=16, din=8):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (n, din))
    y = jax.random.randint(k2, (n,), 0, 4)
    return x, y


def baseline_train(module, params, x, y, lr, steps, num_mb=1):
    """Plain-JAX reference: full-batch grad = mean over microbatch grads."""
    tx = optax.sgd(lr)
    opt_state = tx.init(params)

    def loss_fn(p, xb, yb):
        logits = module.apply({"params": p}, xb)
        return jnp.mean(softmax_xent(logits, yb))

    losses = []
    for _ in range(steps):
        # microbatched grad accumulation with mean semantics
        grads = None
        per_mb = x.shape[0] // num_mb
        total = 0.0
        for mb in range(num_mb):
            xb, yb = x[mb * per_mb:(mb + 1) * per_mb], y[mb * per_mb:(mb + 1) * per_mb]
            l, g = jax.value_and_grad(loss_fn)(params, xb, yb)
            total += l / num_mb
            grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
        grads = jax.tree_util.tree_map(lambda v: v / num_mb, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(total))
    return params, losses


@pytest.mark.parametrize("num_mb", [1, 4])
def test_mlp_parity_vs_plain_jax(num_mb):
    smp.init({"microbatches": num_mb})
    module = MLP()
    x, y = make_data(jax.random.key(0))

    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def train_step(model, xb, yb):
        logits = model(xb)
        loss = jnp.mean(softmax_xent(logits, yb))
        model.backward(loss)
        return loss

    # First call materializes params; its grads are w.r.t. those init params.
    out = train_step(model, x, y)
    init_params = jax.device_get(model.params)
    smp_losses = [float(out.reduce_mean())]
    optimizer.step()
    for _ in range(4):
        out = train_step(model, x, y)
        smp_losses.append(float(out.reduce_mean()))
        optimizer.step()

    ref_params, ref_losses = baseline_train(module, init_params, x, y, 0.1, 5, num_mb)
    np.testing.assert_allclose(smp_losses, ref_losses, rtol=2e-4, atol=2e-5)
    sd = model.state_dict()
    for k, ref in _flat(ref_params).items():
        np.testing.assert_allclose(sd[k], ref, rtol=2e-3, atol=2e-4, err_msg=k)


def _flat(params, prefix=""):
    out = {}
    for k, v in params.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flat(v, key + "/"))
        else:
            out[key] = np.asarray(v)
    return out


def test_loss_decreases_transformer():
    smp.init({"microbatches": 2})
    module = TinyTransformerLM()
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.adam(1e-2), model)

    ids = jax.random.randint(jax.random.key(0), (8, 16), 0, 64)

    @smp.step
    def train_step(model, batch):
        logits = model(batch)
        loss = jnp.mean(softmax_xent(logits[:, :-1], batch[:, 1:]))
        model.backward(loss)
        return loss

    losses = []
    for _ in range(10):
        out = train_step(model, ids)
        losses.append(float(out.reduce_mean()))
        optimizer.step()
    assert losses[-1] < losses[0] * 0.7, losses


def test_forward_only_step():
    smp.init({"microbatches": 2})
    module = MLP()
    model = smp.DistributedModel(module)
    x, _ = make_data(jax.random.key(0))

    @smp.step
    def eval_step(model, xb):
        return model(xb)

    out = eval_step(model, x)
    assert out.stack().shape == (2, 8, 4)
    assert out.concat().shape == (16, 4)
    assert model.grads is None


def test_step_output_accessors_and_kwargs():
    smp.init({"microbatches": 2})
    module = MLP()
    model = smp.DistributedModel(module)
    x, y = make_data(jax.random.key(0))

    @smp.step
    def train_step(model, xb, yb=None, scale=1.0):
        logits = model(xb)
        loss = jnp.mean(softmax_xent(logits, yb)) * scale
        model.backward(loss)
        return {"loss": loss, "logits": logits}

    out = train_step(model, x, yb=y, scale=2.0)
    assert set(out.reduce_mean().keys()) == {"loss", "logits"}
    assert out.concat()["logits"].shape == (16, 4)


def test_non_split_inputs_step():
    smp.init({"microbatches": 4})
    module = MLP()
    model = smp.DistributedModel(module)
    x, y = make_data(jax.random.key(0))
    mask = jnp.ones((4,))

    @smp.step(non_split_inputs=["mask"])
    def train_step(model, xb, yb, mask):
        logits = model(xb) * mask
        loss = jnp.mean(softmax_xent(logits, yb))
        model.backward(loss)
        return loss

    out = train_step(model, x, y, mask)
    assert out.stack().shape == (4,)


def test_eval_step_after_train_step():
    """A forward-only step fn on an already-initialized model must not be
    mistaken for a backward step (regression: per-StepFunction discovery)."""
    smp.init({"microbatches": 2})
    module = MLP()
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(0))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    @smp.step
    def eval_step(model, xb):
        return model(xb)

    train_step(model, x, y)
    optimizer.step()
    out = eval_step(model, x)  # must not raise "backward was not called"
    assert out.concat().shape == (16, 4)
    assert model.grads is None


def test_static_bool_kwarg_branching():
    """Python scalars stay static: user code may branch on them."""
    smp.init({"microbatches": 2})
    module = MLP()
    model = smp.DistributedModel(module)
    x, y = make_data(jax.random.key(0))

    @smp.step(non_split_inputs=["flip"])
    def train_step(model, xb, yb, flip):
        logits = model(xb)
        if flip:  # TracerBoolConversionError if flip were traced
            logits = -logits
        loss = jnp.mean(softmax_xent(logits, yb))
        model.backward(loss)
        return loss

    l_true = float(train_step(model, x, y, True).reduce_mean())
    l_false = float(train_step(model, x, y, False).reduce_mean())
    assert l_true != l_false


def test_backward_outside_step_raises():
    smp.init({})
    module = MLP()
    model = smp.DistributedModel(module)
    with pytest.raises(smp.SMPValidationError):
        model.backward(jnp.zeros(()))


def test_optimizer_without_grads_raises():
    smp.init({})
    module = MLP()
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    with pytest.raises(smp.SMPValidationError):
        optimizer.step()


def test_num_parameters_and_state_dict_roundtrip():
    smp.init({})
    module = MLP()
    model = smp.DistributedModel(module)
    x, y = make_data(jax.random.key(0))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    train_step(model, x, y)
    sd = model.state_dict()
    assert model.num_parameters() == sum(v.size for v in sd.values())
    model.load_state_dict(sd)
    sd2 = model.state_dict()
    for k in sd:
        np.testing.assert_array_equal(sd[k], sd2[k])


def test_bf16_step_runs():
    smp.init({"bf16": True, "microbatches": 2})
    module = MLP()
    model = smp.DistributedModel(module)
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(0))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    l0 = float(train_step(model, x, y).reduce_mean())
    optimizer.step()
    # master params stay fp32
    assert all(p.dtype == jnp.float32 for p in model.parameters())
    l1 = float(train_step(model, x, y).reduce_mean())
    optimizer.step()
    assert l1 < l0


@pytest.mark.parametrize("fused", [True, False])
def test_warns_when_updates_never_installed(fused):
    """Repeated train steps without optimizer.step() must warn loudly —
    the update/grads are computed then discarded, so the model silently
    never learns (the failure mode is invisible otherwise). Covers both
    the fused path (pending update dropped) and the standalone path
    (grads overwritten with params untouched)."""
    import logging

    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    smp.init({"microbatches": 1, "fused_optimizer_step": fused})
    model = smp.DistributedModel(MLP())
    smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(0))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    get_logger().addHandler(handler)
    try:
        for _ in range(5):
            train_step(model, x, y)
    finally:
        get_logger().removeHandler(handler)
    assert any("optimizer.step()" in m for m in records), records


def test_no_warning_when_optimizer_steps():
    import logging

    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    smp.init({"microbatches": 1})
    model = smp.DistributedModel(MLP())
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(1))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    get_logger().addHandler(handler)
    try:
        for _ in range(5):
            train_step(model, x, y)
            optimizer.step()
    finally:
        get_logger().removeHandler(handler)
    assert not any("NOT learning" in m for m in records), records


@pytest.mark.parametrize("fused", [True, False])
def test_eval_step_preserves_pending_train_state(fused):
    # An eval-only step between a train step and optimizer.step() must
    # not clobber the train step's pending state: the fused update tuple
    # and the grads-finite overflow flag are consumed by the upcoming
    # optimizer.step().
    smp.init({"microbatches": 1, "fused_optimizer_step": fused})
    model = smp.DistributedModel(MLP())
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(1))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    @smp.step
    def eval_step(model, xb, yb):
        return jnp.mean(softmax_xent(model(xb), yb))

    train_step(model, x, y)
    pending = model._pending_update
    finite = model._grads_finite
    grads = model._grads
    if fused:
        assert pending is not None
    eval_step(model, x, y)
    assert model._pending_update is pending
    assert model._grads_finite is finite
    assert model._grads is grads
    before = np.asarray(jax.tree_util.tree_leaves(model.params)[0])
    optimizer.step()
    after = np.asarray(jax.tree_util.tree_leaves(model.params)[0])
    assert not np.allclose(before, after)


def test_step_recompiles_after_reinit_same_shapes():
    # A compiled-step cache entry must not survive smp.reset()/re-init:
    # without fused_optimizer_step (whose optimizer serial happens to
    # differ), the cache key's shapes/flags collide across topologies and
    # a stale program compiled under the DEAD mesh would silently run —
    # here a pp2 re-init would skip the pipeline schedule entirely.
    import logging

    from smdistributed_modelparallel_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    def lm():
        return TransformerLM(vocab_size=32, max_len=12, d_model=16,
                             n_layers=4, n_heads=2)

    smp.init({"microbatches": 2, "ddp": True,
              "fused_optimizer_step": False})
    ids = jax.random.randint(jax.random.key(0), (4, 12), 0, 32)

    @smp.step
    def train_step(model, batch):
        logits = model(batch)
        loss = jnp.mean(logits.astype(jnp.float32) ** 2)
        model.backward(loss)
        return loss

    model = smp.DistributedModel(lm())
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    train_step(model, ids)
    optimizer.step()

    from smdistributed_modelparallel_tpu.backend.state import state

    gen1 = state.generation
    keys1 = list(train_step._cache)
    assert keys1 and all(k[0] == gen1 for k in keys1), keys1

    smp.reset()
    smp.init({"pipeline_parallel_degree": 2, "microbatches": 2,
              "ddp": True, "fused_optimizer_step": False})
    model2 = smp.DistributedModel(lm())
    assert state.generation == gen1 + 1

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    get_logger().addHandler(handler)
    try:
        train_step(model2, ids)
    finally:
        get_logger().removeHandler(handler)
    assert any("Pipeline partition" in m for m in records), (
        "re-initialized pp topology did not run the pipeline schedule",
        records)
    # The discriminating check: the new entry is keyed to the NEW
    # generation (reverting the generation key would make the old entry's
    # shapes/flags collide and serve the stale dp-mesh program), and the
    # unreachable old-generation entry was evicted, not leaked.
    keys2 = list(train_step._cache)
    assert keys2 and all(k[0] == gen1 + 1 for k in keys2), keys2


def test_no_warning_for_eval_steps_between_updates():
    # A train step followed by several forward-only eval steps before
    # optimizer.step() is a normal eval-loop shape: the unconsumed grads
    # belong to the train step, and the eval steps must not each count
    # toward the forgot-optimizer.step() detector.
    import logging

    from smdistributed_modelparallel_tpu.utils.logger import get_logger

    smp.init({"microbatches": 1})
    model = smp.DistributedModel(MLP())
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)
    x, y = make_data(jax.random.key(1))

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    @smp.step
    def eval_step(model, xb, yb):
        return jnp.mean(softmax_xent(model(xb), yb))

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    handler = Capture()
    get_logger().addHandler(handler)
    try:
        for _ in range(3):
            train_step(model, x, y)
            for _ in range(4):
                eval_step(model, x, y)
            optimizer.step()
    finally:
        get_logger().removeHandler(handler)
    assert not any("NOT learning" in m for m in records), records


# ----------------------------------------------------------------------
# The step engine's host spans cover the whole call
# ----------------------------------------------------------------------

STEP_PHASES = ("step/prepare", "step/lookup", "step/place", "step/dispatch",
               "step/install", "step/bookkeeping")


def _host_phases():
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    series = telemetry.report()["metrics"].get(
        "smp_host_phase_seconds", {}).get("series", [])
    return {s["labels"]["phase"]: (s["count"], s["sum"]) for s in series}


def _spanned_steps(steps, monkeypatch=None, fused=False):
    """``steps`` train steps of a tiny MLP; returns the growth of every
    host phase's (count, seconds) over them and the annotations seen."""
    seen = []
    if monkeypatch is not None:
        class Annotation:
            def __init__(self, name, **stats):
                seen.append((name, stats))

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    cfg = {"microbatches": 2}
    if fused:
        cfg.update(fused_optimizer_step=True, fused_step_donation=True)
    smp.init(cfg)
    x, y = make_data(jax.random.key(0))
    model = smp.DistributedModel(MLP())
    optimizer = smp.DistributedOptimizer(optax.sgd(0.1), model)

    @smp.step
    def train_step(model, xb, yb):
        loss = jnp.mean(softmax_xent(model(xb), yb))
        model.backward(loss)
        return loss

    before = _host_phases()
    first_step = smp.state.step_count
    for _ in range(steps):
        train_step(model, x, y)
        optimizer.step()
    after = _host_phases()
    grown = {
        phase: (count - before.get(phase, (0, 0.0))[0],
                seconds - before.get(phase, (0, 0.0))[1])
        for phase, (count, seconds) in after.items()
    }
    return grown, seen, first_step


@pytest.mark.parametrize("fused", [False, True], ids=["plain", "fused"])
def test_after_two_steps_every_phase_has_count_two(fused):
    grown, _, _ = _spanned_steps(2, fused=fused)
    for phase in ("step", "optimizer/step") + STEP_PHASES:
        assert grown[phase][0] == 2, phase
    # First-call phases ran once: trace inside lookup, lower and compile
    # before the first dispatch.
    for phase in ("step/trace", "step/lower", "step/compile"):
        assert grown[phase][0] == 1, phase
    assert grown.get("step/fetch", (0, 0.0))[0] == 0
    # One step-time histogram a step, not two.
    from smdistributed_modelparallel_tpu.utils.telemetry import telemetry

    metrics = telemetry.report()["metrics"]
    assert "smp_step_time_seconds" in metrics
    assert "smp_step_dispatch_seconds" not in metrics


def test_children_cover_the_parent_and_sum_to_within_it():
    grown, _, _ = _spanned_steps(2)
    parent = grown["step"][1]
    children = sum(grown[p][1] for p in STEP_PHASES
                   + ("step/lower", "step/compile"))
    assert children <= parent
    # What no child covers (set_mesh round the executable, the context
    # managers themselves) is a sliver of a call that compiles.
    assert parent - children < 0.05 * parent


def test_annotations_carry_parent_and_step(monkeypatch):
    _, seen, first = _spanned_steps(2, monkeypatch)
    parents = [(n, s) for n, s in seen if n == "smp_phase/step"]
    assert parents == [("smp_phase/step", {"step": first}),
                       ("smp_phase/step", {"step": first + 1})]
    for phase in STEP_PHASES:
        mine = [s for n, s in seen if n == "smp_phase/" + phase]
        assert mine == [{"step": first, "parent": "smp_phase/step"},
                        {"step": first + 1, "parent": "smp_phase/step"}]
    trace = [s for n, s in seen if n == "smp_phase/step/trace"]
    assert trace == [{"step": first, "parent": "smp_phase/step/lookup"}]
    # optimizer.step() runs after the call returns: a root of its own,
    # tied to the step by the shared number.
    opt = [s for n, s in seen if n == "smp_phase/optimizer/step"]
    assert opt == [{"step": first}, {"step": first + 1}]


def test_no_step_blocks_on_its_outputs(monkeypatch):
    """The engine used to block every 16th step to time a roofline; it
    blocks none now (17 steps cover the old sampling period)."""
    from smdistributed_modelparallel_tpu.utils import profiling

    blocked = []
    real = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: blocked.append(1) or real(x))
    grown, _, _ = _spanned_steps(17)
    assert grown["step"][0] == 17
    assert blocked == []
    assert grown.get("step/fetch", (0, 0.0))[0] == 0
    for gone in ("should_sample_step", "record_step_roofline",
                 "ROOFLINE_SAMPLE_EVERY"):
        assert not hasattr(profiling, gone)


def test_step_time_quantiles_are_derived_when_a_report_is_taken():
    from smdistributed_modelparallel_tpu.utils.telemetry import (
        TelemetryRegistry, LATENCY_BUCKETS,
    )

    reg = TelemetryRegistry()
    hist = reg.histogram("smp_step_time_seconds", buckets=LATENCY_BUCKETS)
    assert "smp_step_time_quantile_seconds" not in reg.report()["metrics"]
    for v in (0.1, 0.1, 0.1, 2.0):
        hist.observe(v)
    assert "smp_step_time_quantile_seconds" not in reg._families
    series = reg.report()["metrics"]["smp_step_time_quantile_seconds"][
        "series"]
    got = {s["labels"]["stat"]: s["value"] for s in series}
    assert set(got) == {"p50", "p90", "p99"}
    assert 0.05 < got["p50"] < 0.15 and 1.5 < got["p99"] < 2.5
