"""Positions the LM head made logits for, as a share of the positions the
stack ran: the program's gauge ``smp_lm_head_positions`` (``computed`` over
``input``; set while the head is traced). 50 under block diffusion: the
clean half of the two-copy stream pays for no logits. A program whose head
was not asked for a part gives nothing."""

from benchmark import loader

_scopes = loader.load_sibling(__file__, "_scopes")


def read(ctx):
    by = {s["labels"].get("which"): s["value"]
          for s in _scopes._series("smp_lm_head_positions")}
    if not by.get("input"):
        return None
    return 100.0 * by["computed"] / by["input"]
