"""Model step: the whole traced interval's share of the chips' peak: the
operations of every prompt token computed and every decode token in the
interval (attention over its live context, the output head only where
logits are read) over (interval * chips * peak bf16 rate)."""


def read(ctx):
    t, w = ctx.trace, ctx.out.work
    if t is None or w is None or ctx.peak is None:
        return None
    flops = w.decode_flops + w.prefill_flops
    if flops <= 0:
        return None
    return 100.0 * flops / (t["window_s"] * ctx.chips
                            * ctx.peak["flops_bf16"])
