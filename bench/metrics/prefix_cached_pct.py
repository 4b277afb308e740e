"""Prefix cache: share of the prompt tokens admitted in the window that
were served from cached pages instead of computed, from the engine's
counters: prefill_tokens_cached / (prefill_tokens_cached +
prefill_tokens)."""


def read(ctx):
    if not ctx.engine.get("prefix_cache"):
        return None
    c = ctx.out.counters
    total = c["prefill_tokens_cached"] + c["prefill_tokens"]
    return 100.0 * c["prefill_tokens_cached"] / total if total else None
