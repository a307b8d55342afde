"""Share of device 0's busy time in the model's head: the final norm, the
head's product and the library's loss (``smp/head/{norm,logits,loss}``);
under a pipeline executor everything it runs for a microbatch on the last
stage's output (``smp/pipeline/head``: the same, and the step function's own
loss, which ``step.user_code_time_share`` also reads)."""

from benchmark import loader

_tree = loader.load_sibling(__file__, "_tree")


def read(ctx):
    return _tree.share(ctx, _tree.under("smp/head/", "smp/pipeline/head"))
