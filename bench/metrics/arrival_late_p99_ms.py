"""Load generator: how late the harness submitted requests, p99 over
those due in the window, in ms (submit time minus due time on the
harness's clock).  A request due while ``step`` runs waits for it to
return, so this is bounded below by the longest step.  Requests due
while the profiler starts or stops are left out: their lateness is the
measurement's."""
import math


def read(ctx):
    out = ctx.out
    late = sorted(r.submitted - r.due for r in out.records
                  if out.t0 <= r.due < out.t1
                  and not any(s <= r.due <= e for s, e in out.pauses))
    if not late:
        return None
    return 1e3 * late[max(0, math.ceil(0.99 * len(late)) - 1)]
