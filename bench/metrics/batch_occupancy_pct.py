"""Scheduler: share of the batch's decode slots that produced a token,
over the window, from the engine's counters: decode_tokens /
(decode_steps * max_batch)."""


def read(ctx):
    c = ctx.out.counters
    if not c["decode_steps"]:
        return None
    return 100.0 * c["decode_tokens"] / (c["decode_steps"]
                                         * ctx.engine["max_batch"])
