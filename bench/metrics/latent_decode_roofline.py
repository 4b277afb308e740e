"""Kernels: the decode window program's (``serve_scan``) share of its
roofline in the traced interval, for a model whose routed experts are
read only where a token routes to them (a family work count with
``expert_bytes`` and ``expert_read_share``).  The least time of the
window's work is the larger of its operations over the peak rate and
its bytes over the peak bandwidth, over the chips; the bytes are, for
each decode step, every fixed weight once and each held expert's weights
weighted by the share of held experts ``1 - (1 - top_k/E)^b`` that a
step of ``b`` tokens reaches (``b`` the window's decode tokens per
decode step), the live latent rows of each token's context, and the
latent rows the step writes.  Over the program's device time."""
from benchkit import counting


def read(ctx):
    t, w, sh = ctx.trace, ctx.out.work, ctx.shape
    if t is None or w is None or ctx.peak is None \
            or not hasattr(sh, "expert_read_share"):
        return None
    busy = t["program_s"].get("serve_scan", 0.0)
    steps = ctx.out.counters["decode_steps"]
    if busy <= 0 or w.decode_steps <= 0 or steps <= 0:
        return None
    b = ctx.out.counters["decode_tokens"] / steps
    per_step = (sh.weight_bytes + sh.expert_bytes * sh.expert_read_share(b)
                + b * sh.kv_bytes_per_token)
    nbytes = w.decode_steps * per_step + w.decode_kv_bytes
    least = counting.least_time(w.decode_flops, nbytes, ctx.peak) / ctx.chips
    return 100.0 * least / busy
