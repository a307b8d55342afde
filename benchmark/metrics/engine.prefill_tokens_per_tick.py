"""Prompt tokens submitted / ``engine.stats["prefill_chunks"]``: how fast
prompts drain, in tokens a tick (a tick runs at most one chunk)."""


def read(ctx):
    if not ctx.get("prefill_chunks"):
        return None
    return ctx["prompt_tokens"] / ctx["prefill_chunks"]
