"""Device: share of the traced interval in which no operation ran on
the device (1 - union of operation intervals / interval), averaged over
the cell's chips, from the device trace."""


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    return 100.0 * t["idle_share"]
