"""Traffic kind ``train_steps_expert_family``: ``train_steps_experts``
(the same trainer, batches, held weights, window, counters and comparisons)
for whichever family the configuration names. That driver imports
Laguna's weights, FLOPs and reference by name; this one loads it as it
loads ``train_steps`` and puts in their place the modules the
configuration's ``family`` key names:

    "family": "mellum"  ->  benchmark/mellum_weights.py   (make_weights,
                                spec_for, token_batches)
                            benchmark/mellum_flops.py     (layer_shapes,
                                train_flops_per_step, ...)
                            benchmark/reference/mellum.py (follow_steps,
                                hashable)

so the next family with held experts brings those three files and no
driver. Mix parameters: those of ``train_steps_experts``.
"""

import functools
import importlib

from benchmark import loader, weights

experts = loader.load_sibling(__file__, "train_steps_experts")


def follow_with_reference(reference, family_weights, cfg, mix, seed, steps,
                          precision="float32"):
    """The family's reference's readings for the first ``steps`` steps
    (``train_steps_experts.follow_with_reference``, whose reference is
    Laguna's by name)."""
    import jax

    word = weights.seed_word(seed)
    w = jax.jit(lambda s: family_weights.make_weights(cfg, s))(word)
    batches = experts.make_batches(cfg, mix, word)[:steps]
    losses, first_grad, change, loads = reference.follow_steps(
        *reference.hashable(cfg), w, batches, word, mix["lr"], precision,
        steps)
    return {
        "losses": [float(x) for x in losses],
        "first_grad": {k: float(v) for k, v in first_grad.items()},
        "change": {k: float(v) for k, v in change.items()},
        "first_loads": [[int(n) for n in layer] for layer in loads],
    }


def bind(cfg):
    """Put the family of ``cfg`` where ``train_steps_experts`` names
    Laguna's (this file's own copy of that module, not the Laguna
    cell's)."""
    family = cfg["family"]
    family_weights = importlib.import_module(f"benchmark.{family}_weights")
    reference = importlib.import_module(f"benchmark.reference.{family}")
    experts.laguna_weights = family_weights
    experts.laguna_flops = importlib.import_module(
        f"benchmark.{family}_flops")
    experts.follow_with_reference = functools.partial(
        follow_with_reference, reference, family_weights)
    return experts


def control(run):
    return bind(run.cell.config).control(run)


def run(run):
    return bind(run.cell.config).run(run)
