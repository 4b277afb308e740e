"""Model step: the prefill programs' share of the chips' peak in the
traced interval: the counted operations of every prompt token computed
there (the family's ``prefill_flops``, attention over each position's
keys, the head where logits are read) over the device time of the
prefill, chunk, suffix and page-copy programs times the chips' peak
bf16 rate."""

PROGRAMS = ("prefill_paged", "suffix_prefill", "chunk_prefill", "copy_page")


def read(ctx):
    t, w = ctx.trace, ctx.out.work
    if t is None or w is None or ctx.peak is None or w.prefill_flops <= 0:
        return None
    busy = sum(t["program_s"].get(p, 0.0) for p in PROGRAMS)
    if busy <= 0:
        return None
    return 100.0 * w.prefill_flops / (busy * ctx.chips
                                      * ctx.peak["flops_bf16"])
