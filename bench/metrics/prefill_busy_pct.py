"""Engine: share of the device's busy time in the traced interval spent
in the prefill programs (whole prompt, suffix, chunk) and the
copy-on-write page copy, from the device trace."""

PROGRAMS = ("prefill_paged", "suffix_prefill", "chunk_prefill", "copy_page")


def read(ctx):
    t = ctx.trace
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * sum(t["program_s"].get(p, 0.0)
                       for p in PROGRAMS) / t["busy_s"]
