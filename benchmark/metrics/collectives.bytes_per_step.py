"""Bytes the compiled step's collectives move, from the HLO
(``hlo_audit.collective_census``). Nothing on one chip."""


def read(ctx):
    return ctx.get("collective_bytes_per_step")
