"""Share of device 0's busy time a pipeline executor's tick loop spends
outside its sub-steps: instructions inside a segment of the loop
(``smp/pipeline/{warmup,steady,cooldown,fill_drain}``) under no
``tick_*``, ``embed`` or ``head`` scope (the rings' reads and writes, the
gradients' accumulation, the schedule's bookkeeping). Nothing without a
pipeline."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")

# The segments of an executor's tick loop (``cooldown`` stands for
# ``cooldown_weight`` too), and what it puts inside one round a sub-step,
# the embedding and the head.
SEGMENTS = ("smp/pipeline/warmup", "smp/pipeline/steady",
            "smp/pipeline/cooldown", "smp/pipeline/fill_drain")
PARTS = ("smp/pipeline/tick_", "smp/pipeline/embed", "smp/pipeline/head")


def read(ctx):
    return _tree.share(ctx, lambda record: sum(
        seconds for path, seconds in record["tree"].items()
        if any(p.startswith(SEGMENTS) for p in path)
        and not any(p.startswith(PARTS) for p in path)))
