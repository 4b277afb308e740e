"""Kernels: the decode window program's (``serve_scan``) share of its
roofline in the traced interval: the least time the decode work needs
on the cell's chips (the larger of its operations over peak rate and its
bytes over peak bandwidth, over the number of chips; bytes are every
weight once per decode step plus each token's live keys and values,
never gathered padding) over the program's device time (averaged over
the chips)."""
from benchkit import counting


def read(ctx):
    t, w = ctx.trace, ctx.out.work
    if t is None or w is None or ctx.peak is None:
        return None
    busy = t["program_s"].get("serve_scan", 0.0)
    if busy <= 0 or w.decode_steps <= 0:
        return None
    nbytes = w.decode_steps * ctx.shape.weight_bytes + w.decode_kv_bytes
    least = counting.least_time(w.decode_flops, nbytes, ctx.peak) / ctx.chips
    return 100.0 * least / busy
