"""Traffic kind ``train_steps``: optimizer steps back to back for the
window, on a fixed pool of seeded batches.

Set-up builds one object, the compiled ``@smp.step`` with its model and
optimizer, loads the seeded weights, and drives it through its first
``check_steps`` steps by the window's own call (``one_step``); that same
object then runs the window. After the window the program's state is freed
and the plain float32 reference follows the same first steps from the same
seed (``reference/train.py``); ``reference/check.py`` compares.

Mix parameters (``traffic/<mix>.json``): ``batch``, ``seq``, ``lr``,
``batch_pool`` (distinct batches, cycled), ``check_steps``, ``in_flight``
(steps the host may run ahead of the device).
"""

import collections
import time

from benchmark import flops, harness, weights
from benchmark.reference import check

FLASH_KERNELS = ("smp_flash_fwd", "smp_flash_bwd_dq", "smp_flash_bwd_dkv")
ADAM_B1 = 0.9


def kernels_in(compiled):
    """``{kernel name: count}`` of the Mosaic kernels in an executable."""
    import re

    found = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        names = re.findall(r"smp_[a-z0-9_]+", m.group(1)) if m else []
        found[names[-1] if names else "unnamed"] += 1
    return dict(found)


def collectives_in(compiled):
    """Bytes the compiled step's collectives move, summed over ops and
    mesh axes (``hlo_audit.collective_census``: a count from the HLO, so it
    repeats exactly)."""
    from smdistributed_modelparallel_tpu.backend.state import state
    from smdistributed_modelparallel_tpu.utils import hlo_audit

    census = hlo_audit.collective_census(compiled.as_text(), state.mesh)
    return sum(entry["bytes"] for entry in census.values())


def compiled_step(train_step):
    runners = list(train_step._cache.values())
    compiled = [r.holder.get("compiled") for r in runners]
    return compiled[0] if len(compiled) == 1 else None


def adamw_born_in_place(lr):
    """``optax.adamw(lr)`` whose fresh moments are tied to the parameters
    (zeros + 0 x parameter). The update is optax's own, untouched. Plain
    ``zeros_like`` depends on no input, so ``jax.jit(tx.init)`` in
    ``DistributedOptimizer._ensure_state`` would make the whole state on
    one device before it is sharded: at 1.4 B parameters that is 11 GB on
    chip 0 and the first call fails (``PERF.md``, PR 23)."""
    import jax
    import optax

    base = optax.adamw(lr)

    def init(params):
        adam, *rest = base.init(params)
        tie = lambda zeros: jax.tree_util.tree_map(  # noqa: E731
            lambda z, p: z + 0 * p, zeros, params)
        return (adam._replace(mu=tie(adam.mu), nu=tie(adam.nu)), *rest)

    return optax.GradientTransformation(init, base.update)


class Trainer:
    """The one object: model, optimizer and compiled step, fed from the
    batch pool. ``one_step`` is the only way a step is ever run."""

    def __init__(self, run):
        import jax

        import smdistributed_modelparallel_tpu as smp

        cell, mix, cfg = run.cell, run.cell.traffic, run.cell.config
        self.smp, self.run, self.cfg, self.mix = smp, run, cfg, mix
        self.builder = cell.builder()
        run.lap("import_program")
        smp.reset()
        smp.init(dict(cfg["smp"]), devices=list(run.devices))
        run.lap("smp_init")
        self.model = smp.DistributedModel(self.builder.module(cfg))
        self.optimizer = smp.DistributedOptimizer(
            adamw_born_in_place(mix["lr"]), self.model)
        self.train_step = self.builder.train_step(smp)
        run.lap("build_model_and_optimizer")
        self.seed = weights.seed_word(run.seed)
        self.batches = make_batches(cfg, mix, self.seed)
        self.steps_done = 0

    def one_step(self):
        """Dispatch one optimizer step on the next batch of the pool;
        returns its loss (on the device, not read back)."""
        ids = self.batches[self.steps_done % self.mix["batch_pool"]]
        with self.run.span("train_step"):
            out = self.train_step(self.model, ids)
        with self.run.span("optimizer_step"):
            self.optimizer.step()
        self.steps_done += 1
        return out.reduce_mean()

    def flat(self, tree):
        """``{'/'-joined path: leaf}`` of a tree shaped like the params."""
        import jax

        from smdistributed_modelparallel_tpu.module_manager import path_key

        return {path_key(path): leaf for path, leaf
                in jax.tree_util.tree_flatten_with_path(tree)[0]}

    def load_seeded_weights(self):
        """The seeded weights into the model, made in one jitted call with
        each leaf born in its parameter's placement (a shard per device
        where the model is sharded), and a fresh optimizer state (the first
        call initialised both from the program's own init)."""
        import jax
        import jax.numpy as jnp

        shardings = {k: v.sharding
                     for k, v in self.flat(self.model.params).items()}
        made = jax.jit(
            lambda seed: self.builder.flat_from_hf(
                self.cfg, weights.make_weights(self.cfg, seed)),
            out_shardings=shardings)(self.seed)
        self.model.load_state_dict(made)
        del made
        self.optimizer.load_state_dict({
            k: jnp.zeros_like(v)
            for k, v in self.flat(self.optimizer.opt_state).items()
            if isinstance(v, jax.Array)})
        self.steps_done = 0

    def leaf_norms(self, tree, minus_seeded=False):
        """Per-leaf L2 norms under HF names, as floats; with
        ``minus_seeded`` the norms of (leaf - its seeded initial value),
        the initial values made again from the seed inside the program."""
        import jax
        import jax.numpy as jnp

        cfg = self.cfg

        def norms(tree, seed):
            named = self.builder.hf_from_flat(cfg, self.flat(tree))
            spec = weights.spec_for(cfg)
            return {
                k: jnp.sqrt(jnp.sum(jnp.square(
                    v - weights.make_leaf(seed, k, *spec[k])
                    if minus_seeded else v)))
                for k, v in named.items()
            }

        return {k: float(v)
                for k, v in jax.jit(norms)(tree, self.seed).items()}

    def close(self):
        self.smp.shutdown()
        self.model = self.optimizer = self.train_step = self.batches = None


def make_batches(cfg, mix, seed_word):
    import jax

    return jax.jit(lambda s: weights.token_batches(
        s, mix["batch_pool"], mix["batch"], mix["seq"],
        cfg["vocab_size"]))(seed_word)


def reference_shardings(cfg, devices):
    """Where the reference's weights live when the cell has more than one
    chip (float32 state of a model that needs four chips does not fit
    one): each leaf split along its last axis over all the devices, where
    that divides. The reference's code does not change; XLA partitions it.
    ``None`` on one device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    if devices is None or len(devices) == 1:
        return None
    mesh = Mesh(np.array(list(devices)), ("ref",))
    n = len(devices)
    return {
        name: NamedSharding(mesh, PartitionSpec(
            *([None] * (len(shape) - 1)), "ref" if shape[-1] % n == 0
            else None))
        for name, (shape, _, _) in weights.spec_for(cfg).items()
    }


def follow_with_reference(cfg, mix, seed, steps, precision="float32",
                          devices=None):
    """The reference's readings for the first ``steps`` steps."""
    import jax

    from benchmark.reference import train

    word = weights.seed_word(seed)
    w = jax.jit(lambda s: weights.make_weights(cfg, s),
                out_shardings=reference_shardings(cfg, devices))(word)
    batches = make_batches(cfg, mix, word)[:steps]
    losses, first_grad, change = train.follow_steps(
        train.hashable(cfg), w, batches, mix["lr"], precision, steps)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(v) for k, v in first_grad.items()},
        "change": {k: float(v) for k, v in change.items()},
    }


def first_steps(trainer, steps):
    """Drive the trainer through its first ``steps`` steps and read what
    is compared: losses, the first gradient's norms (Adam's first moment
    after one step is (1 - b1) x gradient), the parameters' change."""
    readings = {"losses": []}
    for i in range(steps):
        readings["losses"].append(float(trainer.one_step()))
        if i == 0:
            mu = trainer.leaf_norms(trainer.optimizer.opt_state[0].mu)
            readings["first_grad"] = {
                k: v / (1 - ADAM_B1) for k, v in mu.items()}
    readings["change"] = trainer.leaf_norms(
        trainer.model.params, minus_seeded=True)
    return readings


def control(run):
    """The control's readings alone (``control.py``): the reference in the
    lower precision against the reference, no program and no window."""
    mix, cfg = run.cell.traffic, run.cell.config
    steps = mix["check_steps"]
    reference = follow_with_reference(
        cfg, mix, run.seed, steps, devices=run.devices)
    low = follow_with_reference(
        cfg, mix, run.seed, steps, run.control, devices=run.devices)
    numbers, where = check.train_numbers(low, reference)
    harness.say("control", precision=run.control, numbers=numbers,
                worst_leaves=where, losses=low["losses"],
                reference_losses=reference["losses"])


def run(run):
    import jax

    mix, cfg = run.cell.traffic, run.cell.config
    trainer = Trainer(run)
    run.lap("seeded_batches")
    first_loss = float(trainer.one_step())       # init pass, compile
    run.lap("first_call_init_pass_and_compile")
    trainer.load_seeded_weights()
    run.lap("load_seeded_weights")
    program = first_steps(trainer, mix["check_steps"])
    run.lap("checked_first_steps")
    compiled = compiled_step(trainer.train_step)
    kernels = kernels_in(compiled) if compiled is not None else None
    collective_bytes = collectives_in(compiled) \
        if compiled is not None and len(run.devices) > 1 else None
    harness.say("setup", init_loss=first_loss,
                kernels=kernels, program_losses=program["losses"])

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
    trainer.close()
    del trainer

    t = time.perf_counter()
    reference = follow_with_reference(
        cfg, mix, run.seed, mix["check_steps"], devices=run.devices)
    reference_s = time.perf_counter() - t
    numbers, where = check.train_numbers(program, reference)
    if run.control:
        low = follow_with_reference(
            cfg, mix, run.seed, mix["check_steps"], run.control,
            devices=run.devices)
        harness.say("control", precision=run.control,
                    numbers=check.train_numbers(low, reference)[0])
    numbers["loss_rise_over_window"] = last_loss - program["losses"][0]
    missing = [k for k in FLASH_KERNELS if k not in (kernels or {})]
    numbers["flash_kernels_missing"] = len(missing)
    limits = check.load_limits(run.cell.manifest.dir, run.cell.name)
    correct, rows = check.judge(numbers, limits)
    harness.say("compared", rows=rows, worst_leaves=where,
                reference_losses=reference["losses"], last_loss=last_loss,
                reference_seconds=reference_s, steps_in_window=steps)

    rate = steps * tokens_per_step / run.window_s / len(run.devices)
    return {
        "correct": correct, "attempted": steps, "failed": 0,
        "end_to_end": {"train.tokens_per_s_per_chip": rate},
        "context": {
            "tokens_per_s_per_chip": rate, "steps": steps,
            "dispatch_s": dispatch_s,
            "flops_per_step": flops.train_flops_per_step(
                cfg, mix["batch"], mix["seq"]),
            "attention_flops_per_step": flops.train_attention_flops_per_step(
                cfg, mix["batch"], mix["seq"]),
            "attention_bytes_per_step": flops.train_attention_bytes_per_step(
                cfg, mix["batch"], mix["seq"]),
            "tokens_per_step": tokens_per_step,
            "collective_bytes_per_step": collective_bytes,
        },
    }
