"""Rows routed to held experts for each token and expert layer: the
program's ``smp_moe_local_assignments`` a step (``moe.rows_per_step``)
divided by the step's tokens and the configuration's ``sparse`` layers.
``num_experts_per_tok`` x held / published experts when the router is
even: what sets the expert layers' share of a step."""


def read(ctx):
    moe = ctx.get("moe")
    if not moe or not moe.get("rows_per_step"):
        return None
    cfg = ctx["cell"].config
    layers = cfg["mlp_layer_types"][:len(cfg["layer_types"])].count("sparse")
    return moe["rows_per_step"] / ctx["tokens_per_step"] / layers
