"""Engine: host time per decode step over the window, in ms, from the
engine's counters: decode_time_s / decode_steps (decode_time_s ends in a
blocking pull of the window's tokens)."""


def read(ctx):
    c = ctx.out.counters
    if not c["decode_steps"]:
        return None
    return 1e3 * c["decode_time_s"] / c["decode_steps"]
