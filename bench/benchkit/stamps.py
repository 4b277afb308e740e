"""A request's time to first token, split by the program's lifecycle
stamps.

The scheduler stamps each request on ``perf_counter``, the harness's
clock: when it was submitted, admitted (the admission that led to its
first token) and when its first token was made.  So the time from when
a request was due until the harness saw its first token splits into

* ``late``: due until the program's submit stamp (how late the load
  loop submitted it, blocked in an engine step);
* ``queue``: submitted until admitted (waiting for a slot);
* ``prefill``: admitted until the first token (the windows its chunked
  prefill took);
* ``returned``: first token until the harness saw it (the rest of the
  ``step()`` call that made it).

No per-layer metric reads these yet: a traced run's profiler holds the
load loop for seconds inside the window, and the queue that builds
behind it would read as queue wait.  ``bench/scope_report.py`` prints
them.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from benchkit.cell import quantile

PARTS = ("late", "queue", "prefill", "returned", "ttft")


def parts(out, before: Optional[float] = None) -> List[Dict[str, float]]:
    """Each part in seconds, per request of ``ttft_p90_s``'s population
    (due in the window, not failed, with a first token); with ``before``,
    only the requests whose first token the harness saw before then.
    Empty where the program does not stamp its requests."""
    rows = []
    for r in out.records:
        q = r.req
        if not out.t0 <= r.due < out.t1 or r.failed or r.first is None \
                or (before is not None and r.first >= before) \
                or getattr(q, "first_token_at", None) is None:
            continue
        rows.append({"late": q.submitted_at - r.due,
                     "queue": q.admitted_at - q.submitted_at,
                     "prefill": q.first_token_at - q.admitted_at,
                     "returned": r.first - q.first_token_at,
                     "ttft": r.first - r.due})
    return rows


def summary(rows: List[Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    """p50, p90 (nearest rank, as ``ttft_p90_s``) and mean of each part,
    in seconds; empty for no rows."""
    if not rows:
        return {}
    return {p: {"p50": quantile([r[p] for r in rows], 0.5),
                "p90": quantile([r[p] for r in rows], 0.9),
                "mean": sum(r[p] for r in rows) / len(rows)}
            for p in PARTS}
