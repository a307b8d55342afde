"""Share of the decode batch's slots that produced a token: tokens that
came out of decode steps / (``engine.stats["decode_steps"]`` x
``max_slots``). A request's first token comes from its last prefill chunk
and is not counted."""


def read(ctx):
    if not ctx.get("decode_steps"):
        return None
    return 100.0 * ctx["decode_tokens"] / (
        ctx["decode_steps"] * ctx["max_slots"])
