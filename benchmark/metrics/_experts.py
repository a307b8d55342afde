"""Device 0's self seconds in the routed experts' own work, shared by
``moe.experts_time_share`` and ``moe.row_time_us``: the ops the program's
op index puts under the scope ``smp/moe/experts`` (``_moe.py``) and the
grouped matrix products themselves. The TPU compiler turns each
``lax.ragged_dot`` into a kernel of its own whose instruction,
``ragged-dot-*``, keeps no ``op_name`` beyond that, so the index gives it
no scope; the expert layer's grouped FFN is the program's only
``ragged_dot`` (``nn/moe.py::_expert_ffn``, inside that scope), so an
instruction of that name with no scope is counted here. ``None`` without
an index or where nothing ran under the scope."""

from benchmark import loader

_moe = loader.load_sibling(__file__, "_moe")


def seconds(ctx):
    under = _moe.seconds_under(ctx, ("smp/moe/experts",))
    if not under:
        return None
    index = _moe._scopes.step_index()
    return under + sum(
        s for name, s in ctx["trace"]["op_self_s"].items()
        if name.startswith("ragged-dot")
        and not (index.get(name) or {}).get("scope"))
