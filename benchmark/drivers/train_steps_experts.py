"""Traffic kind ``train_steps_experts``: ``train_steps`` for a model whose
expert layers hold a share of the experts: the same ``Trainer`` object,
window and comparisons (``drivers/train_steps.py``, ``reference/check.py``),
with what the Laguna family brings of its own:

- the batches: token ids drawn by the mix's ``token_law`` (Zipf-Mandelbrot
  with an offset) from the vocabulary slice the configuration holds, so
  that no single id carries a large share of a batch to the same experts;
- weights held through the window: the learning rate lives in the
  optimizer's state (``adamw_lr_in_state``), the checked steps run at the
  mix's ``lr`` and are compared with the reference, and the window runs
  the same compiled step (forward, backward, the whole AdamW update) at
  rate 0, so the parameters stay where the checked steps left them. A chip's share of the experts is the reason: only held experts add
  to the output, so a router that trains learns to prefer or to avoid them
  (which, depends on what the batches let the model learn), the routed
  rows drift through the window, and the rate follows the rows and not the
  program (``PERF.md``, PR 27). In the deployment every expert is present
  and the load has no such direction. ``correct`` holds the window to it
  (``weights_moved_in_window``), and the ``compared`` line gives the rows
  step by step;
- the family's seeded weights and plain reference
  (``laguna_weights.py``, ``reference/laguna.py``);
- the expert layers' counters: the step returns them beside the loss, the
  driver keeps each step's small device arrays and reads them back after
  the window (``smp.nn.record_moe_stats``): rows routed here, drops, load
  imbalance. The required FLOPs and bytes of the routed experts come from
  that count (``laguna_flops.py``).

Mix parameters: those of ``train_steps`` and ``token_law``
(``{"kind": "zipf_mandelbrot", "offset": n}``).
"""

import collections
import gc
import time
import typing

from benchmark import harness, laguna_flops, laguna_weights, loader, weights
from benchmark.reference import check

base = loader.load_sibling(__file__, "train_steps")


def make_batches(cfg, mix, seed_word):
    import jax

    law = mix["token_law"]
    if law["kind"] != "zipf_mandelbrot":
        raise ValueError(f"unknown token_law {law['kind']!r}")
    return jax.jit(lambda s: laguna_weights.token_batches(
        s, mix["batch_pool"], mix["batch"], mix["seq"], cfg["vocab_size"],
        law["offset"]))(seed_word)


class HeldLr(typing.NamedTuple):
    lr: typing.Any


def adamw_lr_in_state(lr):
    """``train_steps.adamw_born_in_place(lr)`` with the learning rate a
    scalar of the optimizer's state (last entry, ``HeldLr``) instead of a
    constant of the program: update = lr x (-(adam + decay x parameter)),
    bit for bit what ``optax.adamw(lr)`` gives, and one compiled step
    serves every rate."""
    import jax
    import jax.numpy as jnp
    import optax

    unit = base.adamw_born_in_place(1.0)

    def init(params):
        return (*unit.init(params), HeldLr(jnp.asarray(lr, jnp.float32)))

    def update(updates, state, params=None):
        *inner, held = state
        updates, inner = unit.update(updates, tuple(inner), params)
        return (jax.tree_util.tree_map(lambda u: u * held.lr, updates),
                (*inner, held))

    return optax.GradientTransformation(init, update)


class Trainer(base.Trainer):
    """``train_steps.Trainer`` with this family's batches and weights and
    the learning rate in the optimizer's state; a step's output is
    ``(loss, expert layers' counters)``."""

    def __init__(self, run):
        super().__init__(run)
        # In place of the base's optimizer, whose state is not made yet.
        self.optimizer = self.smp.DistributedOptimizer(
            adamw_lr_in_state(self.mix["lr"]), self.model)
        self.batches = make_batches(self.cfg, self.mix, self.seed)
        self.stats = []

    def set_lr(self, lr):
        held = [k for k in self.flat(self.optimizer.opt_state)
                if k.rsplit("/", 1)[-1] == "lr"]
        assert len(held) == 1, held
        self.optimizer.load_state_dict({held[0]: lr})

    def one_step(self):
        import jax.numpy as jnp

        ids = self.batches[self.steps_done % self.mix["batch_pool"]]
        with self.run.span("train_step"):
            out = self.train_step(self.model, ids)
        with self.run.span("optimizer_step"):
            self.optimizer.step()
        self.steps_done += 1
        loss, stats = out.stack()
        self.stats.append(stats)
        return jnp.mean(loss)

    def load_seeded_weights(self):
        import jax
        import jax.numpy as jnp

        shardings = {k: v.sharding
                     for k, v in self.flat(self.model.params).items()}
        made = jax.jit(
            lambda seed: self.builder.flat_from_hf(
                self.cfg, laguna_weights.make_weights(self.cfg, seed)),
            out_shardings=shardings)(self.seed)
        self.model.load_state_dict(made)
        del made
        self.optimizer.load_state_dict({
            k: jnp.zeros_like(v)
            for k, v in self.flat(self.optimizer.opt_state).items()
            if isinstance(v, jax.Array)})
        self.set_lr(self.mix["lr"])
        self.steps_done = 0
        self.stats = []

    def leaf_norms(self, tree, minus_seeded=False):
        import jax
        import jax.numpy as jnp

        cfg = self.cfg

        def norms(tree, seed):
            named = self.builder.hf_from_flat(cfg, self.flat(tree))
            spec = laguna_weights.spec_for(cfg)
            return {
                k: jnp.sqrt(jnp.sum(jnp.square(
                    v - weights.make_leaf(seed, k, *spec[k])
                    if minus_seeded else v)))
                for k, v in named.items()
            }

        return {k: float(v)
                for k, v in jax.jit(norms)(tree, self.seed).items()}

    def moe_summary(self, stats):
        """Read ``stats`` (a list of steps' counters) back and record them
        together: ``{"local", "dropped", "max_over_mean"}`` over those
        steps."""
        import jax.numpy as jnp

        together = {
            path: jnp.concatenate([s[path] for s in stats])
            for path in stats[0]
        }
        return self.smp.nn.record_moe_stats(together)


def follow_with_reference(cfg, mix, seed, steps, precision="float32"):
    """The reference's readings for the first ``steps`` steps."""
    import jax

    from benchmark.reference import laguna as reference

    word = weights.seed_word(seed)
    w = jax.jit(lambda s: laguna_weights.make_weights(cfg, s))(word)
    batches = make_batches(cfg, mix, word)[:steps]
    losses, first_grad, change, loads = reference.follow_steps(
        *reference.hashable(cfg), w, batches, word, mix["lr"], precision,
        steps)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(v) for k, v in first_grad.items()},
        "change": {k: float(v) for k, v in change.items()},
        "first_loads": [[int(n) for n in layer] for layer in loads],
    }


def step_loads(stats):
    """The held experts' loads of one step, [expert layers, held], in
    layer order (paths sort in layer order: ``seq_layers_<n>_...``)."""
    import numpy as np

    rows = []
    for path in sorted(stats):
        leaf = np.asarray(stats[path])
        rows.extend(leaf.reshape(leaf.shape[0], -1, leaf.shape[-1])
                    .sum(axis=0)[:, :-1].tolist())
    return rows


def routed_rows(loads):
    return sum(sum(layer) for layer in loads)


def rows_drift(rows_by_step):
    """Mean routed rows of the window's last quarter of steps over its
    first quarter's: 1.0 when the routed load is stationary."""
    n = max(1, len(rows_by_step) // 4)
    return sum(rows_by_step[-n:]) / max(1, sum(rows_by_step[:n]))


def routing_difference(program_loads, reference_loads):
    """Information, not compared: the share of the reference's assignments
    to held experts that the program's loads differ by (sum of |load
    difference| over layers and experts / the reference's total). A token
    whose k-th and (k+1)-th experts lie within bf16 rounding of each other
    routes differently in the two; each such token moves this by at most
    two assignments."""
    total = sum(sum(layer) for layer in reference_loads)
    moved = sum(abs(a - b) for p, r in zip(program_loads, reference_loads)
                for a, b in zip(p, r))
    return moved / total if total else None


def control(run):
    """The control's readings alone (``control.py``)."""
    mix, cfg = run.cell.traffic, run.cell.config
    steps = mix["check_steps"]
    reference = follow_with_reference(cfg, mix, run.seed, steps)
    low = follow_with_reference(cfg, mix, run.seed, steps, run.control)
    numbers, where = check.train_numbers(low, reference)
    harness.say("control", precision=run.control, numbers=numbers,
                worst_leaves=where, losses=low["losses"],
                reference_losses=reference["losses"],
                routing_difference=routing_difference(
                    low["first_loads"], reference["first_loads"]))


def run(run):
    import jax

    mix, cfg = run.cell.traffic, run.cell.config
    trainer = Trainer(run)
    run.lap("seeded_batches")
    first_loss = float(trainer.one_step())       # init pass, compile
    run.lap("first_call_init_pass_and_compile")
    trainer.load_seeded_weights()
    run.lap("load_seeded_weights")
    program = base.first_steps(trainer, mix["check_steps"])
    program_loads = step_loads(trainer.stats[0])
    run.lap("checked_first_steps")
    compiled = base.compiled_step(trainer.train_step)
    kernels = base.kernels_in(compiled) if compiled is not None else None
    harness.say("setup", init_loss=first_loss, kernels=kernels,
                program_losses=program["losses"], setup_laps=dict(run.laps))

    trainer.set_lr(0.0)                          # the window holds the weights
    dispatch_s, pending = [], collections.deque()
    tokens_per_step = mix["batch"] * mix["seq"]
    steps0 = trainer.steps_done
    with run.window() as t0:
        while time.perf_counter() - t0 < run.seconds:
            t = time.perf_counter()
            pending.append(trainer.one_step())
            dispatch_s.append(time.perf_counter() - t)
            if len(pending) > mix["in_flight"]:
                jax.block_until_ready(pending.popleft())
        jax.block_until_ready(trainer.model.params)
    steps = trainer.steps_done - steps0
    with run.span("loss_readback"):
        last_loss = float(pending[-1])
    moe = trainer.moe_summary(trainer.stats[-steps:])
    rows_by_step = [routed_rows(step_loads(stats))
                    for stats in trainer.stats[-steps:]]
    change = trainer.leaf_norms(trainer.model.params, minus_seeded=True)
    trainer.close()
    del trainer, pending
    # The reference needs the room of the program's state: collect what
    # only reference cycles still hold before it starts.
    gc.collect()
    harness.say("program_freed", bytes_in_use=max(
        (d.memory_stats() or {}).get("bytes_in_use", 0)
        for d in run.devices))

    t = time.perf_counter()
    reference = follow_with_reference(cfg, mix, run.seed, mix["check_steps"])
    reference_s = time.perf_counter() - t
    numbers, where = check.train_numbers(program, reference)
    if run.control:
        low = follow_with_reference(
            cfg, mix, run.seed, mix["check_steps"], run.control)
        harness.say("control", precision=run.control,
                    numbers=check.train_numbers(low, reference)[0])
    numbers["weights_moved_in_window"] = max(
        abs(change[k] - v) for k, v in program["change"].items())
    missing = [k for k in base.FLASH_KERNELS if k not in (kernels or {})]
    numbers["flash_kernels_missing"] = len(missing)
    numbers["moe_dropped_assignments"] = moe["dropped"]
    limits = check.load_limits(run.cell.manifest.dir, run.cell.name)
    correct, rows = check.judge(numbers, limits)
    rows_per_step = moe["local"] / steps
    harness.say(
        "compared", rows=rows, worst_leaves=where,
        reference_losses=reference["losses"], last_loss=last_loss,
        reference_seconds=reference_s, steps_in_window=steps,
        smp_moe_local_assignments=moe["local"],
        moe_rows_per_step=rows_per_step, moe_rows_by_step=rows_by_step,
        moe_rows_last_quarter_over_first=rows_drift(rows_by_step),
        moe_rows_first_checked_step={
            "program": routed_rows(program_loads),
            "reference": routed_rows(reference["first_loads"])},
        moe_load_max_over_mean=moe["max_over_mean"],
        routing_difference=routing_difference(
            program_loads, reference["first_loads"]))

    rate = steps * tokens_per_step / run.window_s / len(run.devices)
    expert_layers = sum(
        layer["sparse"] for layer in laguna_flops.layer_shapes(cfg))
    calls = expert_layers * cfg["smp"]["microbatches"] * steps
    return {
        "correct": correct, "attempted": steps, "failed": 0,
        "end_to_end": {"train.tokens_per_s_per_chip": rate},
        "context": {
            "tokens_per_s_per_chip": rate, "steps": steps,
            "dispatch_s": dispatch_s,
            "flops_per_step": laguna_flops.train_flops_per_step(
                cfg, mix["batch"], mix["seq"], rows_per_step),
            "attention_flops_per_step":
                laguna_flops.train_attention_flops_per_step(
                    cfg, mix["batch"], mix["seq"]),
            "attention_bytes_per_step":
                laguna_flops.train_attention_bytes_per_step(
                    cfg, mix["batch"], mix["seq"]),
            "tokens_per_step": tokens_per_step,
            "collective_bytes_per_step": None,
            "moe": {
                "rows_in_window": moe["local"],
                "rows_per_step": rows_per_step,
                "load_max_over_mean": moe["max_over_mean"],
                "grouped_flops_in_window":
                    laguna_flops.expert_flops_per_row(cfg) * moe["local"],
                "grouped_bytes_in_window":
                    laguna_flops.grouped_matmul_bytes(
                        cfg, moe["local"], calls),
            },
        },
    }
