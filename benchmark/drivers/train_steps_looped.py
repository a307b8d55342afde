"""Traffic kind ``train_steps_looped``: ``train_steps`` (the same trainer,
window, counters and comparisons) for a looped model with an exit gate.
That driver names GPT-2's and GPT-NeoX's weights, FLOPs and reference
through tables that may not be edited; this one loads it as
``train_steps_expert_family`` loads its sibling and puts this family's in
their place:

    weights    ->  benchmark/ouro_weights.py   (make_weights, spec_for,
                       make_leaf, token_batches by the mix's ``token_law``)
    flops      ->  benchmark/ouro_flops.py     (every pass counted)
    reference  ->  benchmark/reference/ouro.py (follow_steps, hashable)

A step returns the loss's counters beside the loss (exit shares, entropy,
the passes' losses: ``smp.nn.exit_gated_loss``), and five more numbers are
compared, so that a program that runs fewer passes, shares no weights,
reads no gate or drops the entropy term fails by at least one:
``pass_loss_gap_<t>`` (the first checked step's mean next-token loss after
pass t, program against reference) and ``exit_share_gap`` (the widest gap
of a pass's mean exit probability over the checked steps).

Mix parameters: those of ``train_steps`` and ``token_law`` (``{"kind":
"zipf_mandelbrot", "offset": n}``).
"""

import itertools
import math

from benchmark import harness, loader, ouro_flops, ouro_weights
from benchmark.reference import check

steps = loader.load_sibling(__file__, "train_steps")
steps.weights = ouro_weights
steps.flops = ouro_flops


def make_batches(cfg, mix, seed_word):
    import jax

    law = mix["token_law"]
    if law["kind"] != "zipf_mandelbrot":
        raise ValueError(f"unknown token_law {law['kind']!r}")
    return jax.jit(lambda s: ouro_weights.token_batches(
        s, mix["batch_pool"], mix["batch"], mix["seq"], cfg["vocab_size"],
        law["offset"]))(seed_word)


class Trainer(steps.Trainer):
    """The base trainer on a step function that returns ``(loss, the
    loss's counters)``; the counters stay on the device until asked for."""

    def __init__(self, run):
        super().__init__(run)
        self.train_step = self.builder.train_step(
            self.smp, self.cfg["exit_entropy_weight"])
        self.stats = []

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
        super().load_seeded_weights()
        self.stats = []

    def exit_stats(self, first, count):
        """``record_exit_stats`` of each of ``count`` steps from step
        ``first`` on (a host transfer: outside the window)."""
        return [self.smp.nn.record_exit_stats(s)
                for s in self.stats[first:first + count]]


def first_steps(trainer, count):
    """The base's readings of the first ``count`` steps, and those steps'
    counters."""
    readings = _first_steps(trainer, count)
    readings["exit_stats"] = trainer.exit_stats(0, count)
    harness.say("exit_gate", steps=readings["exit_stats"],
                layer_passes_per_step=ouro_flops.layer_passes_per_step(
                    trainer.cfg, trainer.cfg["smp"]["microbatches"]))
    return readings


def follow_with_reference(cfg, mix, seed, count, precision="float32",
                          devices=None):
    """The reference's readings for the first ``count`` steps."""
    import jax

    from benchmark.reference import ouro as reference

    word = ouro_weights.seed_word(seed)
    w = jax.jit(lambda s: ouro_weights.make_weights(cfg, s))(word)
    batches = make_batches(cfg, mix, word)[:count]
    losses, first_grad, change, stats = reference.follow_steps(
        *reference.hashable(cfg), w, batches, word, mix["lr"], precision,
        count)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(v) for k, v in first_grad.items()},
        "change": {k: float(v) for k, v in change.items()},
        "exit_stats": [{"pass_loss": [float(x) for x in nll],
                        "exit_share": [float(x) for x in share],
                        "entropy": float(entropy)}
                       for nll, share, entropy in stats],
    }


def gaps(got, want):
    """|got - want| pass by pass; a pass one side lacks reads infinity."""
    return [abs(a - b) for a, b in itertools.zip_longest(
        got, want, fillvalue=math.inf)]


class Compared:
    """``reference/check.py`` as ``train_steps`` calls it, with this
    family's five numbers beside its own."""

    load_limits = staticmethod(check.load_limits)
    judge = staticmethod(check.judge)

    @staticmethod
    def train_numbers(program, reference):
        numbers, where = check.train_numbers(program, reference)
        got, want = program["exit_stats"], reference["exit_stats"]
        for t, gap in enumerate(gaps(got[0]["pass_loss"],
                                     want[0]["pass_loss"])):
            numbers[f"pass_loss_gap_{t + 1}"] = gap
        numbers["exit_share_gap"] = max(
            gap for g, w in zip(got, want)
            for gap in gaps(g["exit_share"], w["exit_share"]))
        return numbers, where


_first_steps = steps.first_steps
steps.make_batches = make_batches
steps.Trainer = Trainer
steps.first_steps = first_steps
steps.follow_with_reference = follow_with_reference
steps.check = Compared


control = steps.control
run = steps.run
