"""Traffic kind ``train_steps_block_diffusion``:
``train_steps_expert_family`` (the same trainer, held weights, window,
counters and comparisons, for the family the configuration names) for a
model trained by block diffusion. What differs is the batch: that driver
draws token ids alone and passes neither a block length nor a mask id to
the family's ``token_batches``; here a batch is the family's own
(``<family>_weights.make_batches(cfg, mix, seed_word)``: clean ids, their
noisy copy, the blocks' rates and the mask id, all from the seed, so the
program and the reference see one draw), and a step returns the
objective's counters beside the expert layers'.

``train.tokens_per_s_per_chip`` counts *data* tokens, ``batch x seq`` a
step: what a user counts of a corpus. The stack runs twice that many
positions (both copies), and the family's FLOPs say so.

One more number is compared here, ``first_grad_sample_gap``, and it is
the one the lower precision fails. Half of the noisy copy holds the mask
id: a quarter of all positions are one vector to every router and pull its
gradient one way, so the norm of the first gradient's worst leaf (the
router's, in nearly every run) reads the rounding of one cancelling sum
and not the precision of the run: bfloat16 read up to 0.017 there and
float8 as little as 0.018 (``PERF.md`` section 2). A norm's gap is the
wrong instrument for it. The program's first gradient (Adam's first moment
after the first checked step) and the reference's are therefore also
compared as vectors, on a fixed sample of every leaf
(``sdar_weights.gradient_sample``): a leaf's gap is |sample - reference's|
/ |reference's|, the number is the median leaf's (the router's sum is one
leaf's matter), and every leaf's reading is printed beside its norm's gap
(``first_gradient``). ``first_grad_norm_gap`` stays, held against a leaf
whose gradient is wrong.

Mix parameters: those of ``train_steps_experts`` and ``noise`` (``{"kind":
"linear_per_block", "eps": e}``: a block's rate is e + (1 - e) u).
"""

import importlib
import statistics

from benchmark import harness, loader, weights
from benchmark.reference import check

family = loader.load_sibling(__file__, "train_steps_expert_family")


def make_batches(cfg, mix, seed_word):
    return importlib.import_module(
        f"benchmark.{cfg['family']}_weights").make_batches(
            cfg, mix, seed_word)


class Trainer(family.experts.Trainer):
    """The family trainer on batches that carry their noise; a step's
    output is ``(loss, expert layers' counters, the objective's
    counters)``."""

    def __init__(self, run):
        super().__init__(run)
        self.counts = []
        self.seeded = False

    def one_step(self):
        import jax.numpy as jnp

        batch = self.batches[self.steps_done % self.mix["batch_pool"]]
        with self.run.span("train_step"):
            out = self.train_step(self.model, batch)
        with self.run.span("optimizer_step"):
            self.optimizer.step()
        self.steps_done += 1
        if self.seeded and self.steps_done == 1:
            # The first checked step: its gradient's sample, for Compared.
            self.run.first_grad_sample = self.first_gradient_sample()
        loss, stats, counts = out.stack()
        self.stats.append(stats)
        self.counts.append(counts)
        return jnp.mean(loss)

    def load_seeded_weights(self):
        super().load_seeded_weights()
        self.counts = []
        self.seeded = True

    def first_gradient_sample(self):
        """``gradient_sample`` of the gradient of the step just taken from
        a fresh optimizer state: Adam's first moment / (1 - b1)."""
        import jax
        import numpy as np

        made = family.experts.laguna_weights

        def sample(mu):
            named = self.builder.hf_from_flat(self.cfg, self.flat(mu))
            return {k: v / (1 - family.experts.base.ADAM_B1)
                    for k, v in made.gradient_sample(named).items()}

        return {k: np.asarray(v) for k, v in
                jax.jit(sample)(self.optimizer.opt_state[0].mu).items()}

    def leaf_norms(self, tree, minus_seeded=False):
        """The base's per-leaf norms, the seeded start made again by the
        family's own ``make_leaf`` (not every leaf of this family is a
        function of the run's seed alone)."""
        import jax
        import jax.numpy as jnp

        cfg, made = self.cfg, family.experts.laguna_weights

        def norms(tree, seed):
            named = self.builder.hf_from_flat(cfg, self.flat(tree))
            return {k: jnp.sqrt(jnp.sum(jnp.square(
                v - made.make_leaf(cfg, seed, k) if minus_seeded else v)))
                for k, v in named.items()}

        return {k: float(v)
                for k, v in jax.jit(norms)(tree, self.seed).items()}

    def moe_summary(self, stats):
        """The expert layers' summary of ``stats`` (the window's steps),
        and, read back with it outside the window, the objective's
        counters of those same steps."""
        import jax.numpy as jnp

        steps = self.counts[-len(stats):]
        counts = self.smp.nn.record_diffusion_stats({
            k: jnp.stack([c[k] for c in steps]) for k in steps[0]})
        harness.say("diffusion", steps=len(steps), **counts,
                    loss_tokens_share=counts["loss_tokens"]
                    / counts["data_tokens"])
        return super().moe_summary(stats)


def follow_with_reference(cfg, mix, seed, steps, precision="float32"):
    """``train_steps_expert_family.follow_with_reference`` for this
    family's reference, which also gives the first gradient's sample."""
    import jax
    import numpy as np

    family_weights = importlib.import_module(
        f"benchmark.{cfg['family']}_weights")
    reference = importlib.import_module(
        f"benchmark.reference.{cfg['family']}")
    word = weights.seed_word(seed)
    w = jax.jit(lambda s: family_weights.make_weights(cfg, s))(word)
    batches = make_batches(cfg, mix, word)[:steps]
    losses, first_grad, change, loads, sample = reference.follow_steps(
        *reference.hashable(cfg), w, batches, word, mix["lr"], precision,
        steps)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(v) for k, v in first_grad.items()},
        "change": {k: float(v) for k, v in change.items()},
        "first_loads": [[int(n) for n in layer] for layer in loads],
        "first_grad_sample": {k: np.asarray(v) for k, v in sample.items()},
    }


def sample_gaps(got, want):
    """Per leaf, |got - want| / |want| of two gradient samples."""
    import numpy as np

    gaps = {}
    for name, ref in want.items():
        ref = np.asarray(ref, np.float64)
        diff = np.asarray(got[name], np.float64) - ref
        gaps[name] = float(
            np.linalg.norm(diff) / max(np.linalg.norm(ref), 1e-30))
    return gaps


class Compared:
    """``reference/check.py`` as the expert driver calls it, with
    ``first_grad_sample_gap`` beside its numbers: the median leaf's gap
    between the program's sample of the first gradient (taken by the
    trainer, kept on the run; the control's is in its readings) and the
    reference's."""

    load_limits = staticmethod(check.load_limits)
    judge = staticmethod(check.judge)

    def __init__(self, run):
        self.run = run

    def train_numbers(self, program, reference):
        numbers, where = check.train_numbers(program, reference)
        got = program.get("first_grad_sample")
        gaps = sample_gaps(
            self.run.first_grad_sample if got is None else got,
            reference["first_grad_sample"])
        numbers["first_grad_sample_gap"] = statistics.median(gaps.values())
        floor = statistics.median(reference["first_grad"].values())
        harness.say("first_gradient", sample_gaps=gaps, norm_gaps={
            k: abs(program["first_grad"][k] - v) / max(v, floor)
            for k, v in reference["first_grad"].items()})
        return numbers, where


def bind(cfg, run=None):
    """The family's trainer, batches and reference in the expert
    driver's names; with a ``run``, its comparisons too."""
    experts = family.bind(cfg)
    experts.make_batches = make_batches
    experts.Trainer = Trainer
    experts.follow_with_reference = follow_with_reference
    if run is not None:
        experts.check = Compared(run)
    return experts


def control(run):
    return bind(run.cell.config, run).control(run)


def run(run):
    return bind(run.cell.config, run).run(run)
