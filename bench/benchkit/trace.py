"""From a profiler trace to device times.

:func:`extract` reads the ``.xplane.pb`` the JAX profiler writes into a
small plain record: per device, the compiled programs (``XLA Modules``)
and operations (``XLA Ops``) with their start and end in nanoseconds,
and the harness's own host spans (names starting ``bench.``).
:func:`reduce` turns that record, clipped to the traced interval, into
busy and idle time (busy: the union of the operations' intervals),
device time per program, the device time per program spent in
cross-chip collective operations, the operations that took most time by
their self time (a ``while`` less its body) and the longest idle gaps,
each named after the harness span that was open across it.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
TRACED_SPAN = "bench.traced"          # the harness opens it around the
                                      # whole traced interval
# operations that move data between chips, by their HLO name
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all")


def extract(path: str) -> dict:
    """The record :func:`reduce` reads, from one ``.xplane.pb`` file."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            rec = devices.setdefault(plane.name, {"modules": [], "ops": []})
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    rec[key] += [(op_name(e.name), int(e.start_ns),
                                  int(e.end_ns)) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def op_name(event: str) -> str:
    """``%copy.85 = bf16[...] copy(...)`` -> ``copy.85``."""
    return event.split(" = ")[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit_serve_scan(17)`` -> ``serve_scan``."""
    name = re.sub(r"\(.*$", "", module)
    return name[4:] if name.startswith("jit_") else name


def _clip(events: List[Event], t0: int, t1: int) -> List[Event]:
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def _union(events: List[Event]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for _, s, e in sorted(events, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(events: List[Event]) -> List[Event]:
    """(name, start, self time) of each event: its duration less the time
    of the events nested in it (an XLA ``while`` holds its body's
    operations)."""
    out: List[Event] = []
    stack: List[list] = []             # [name, start, end, self time]
    for n, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            x = stack.pop()
            out.append((x[0], x[1], x[3]))
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append([n, s, e, e - s])
    out += [(x[0], x[1], x[3]) for x in stack]
    return out


def traced_interval(rec: dict) -> Tuple[int, int]:
    spans = [e for e in rec["host"] if e[0] == TRACED_SPAN]
    if not spans:
        raise ValueError(f"no {TRACED_SPAN!r} span in the trace")
    return spans[0][1], spans[0][2]


def _label(host: List[Event], t: int) -> str:
    """The innermost harness span open at ``t``; ``bench.loop`` where
    the harness is between its calls."""
    best = None
    for name, s, e in host:
        if s > t:
            break
        if e >= t and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "bench.loop"


def reduce(rec: dict, top: int = 10) -> dict:
    """Device times over the traced interval, averaged over devices."""
    t0, t1 = traced_interval(rec)
    window = (t1 - t0) / 1e9
    host = [e for e in rec["host"] if e[0] != TRACED_SPAN]
    devs = sorted(rec["devices"])
    if not devs:
        raise ValueError("no device plane in the trace")
    busy = 0.0
    programs: Dict[str, float] = {}
    collective: Dict[str, float] = {}
    ops: Dict[str, float] = {}
    gaps: List[Tuple[str, float]] = []
    for i, dev in enumerate(devs):
        mods = _clip(rec["devices"][dev]["modules"], t0, t1)
        opl = _clip(rec["devices"][dev]["ops"], t0, t1) or mods
        segs = _union(opl)
        busy += sum(e - s for s, e in segs) / 1e9
        for n, s, e in mods:
            p = program_name(n)
            programs[p] = programs.get(p, 0.0) + (e - s) / 1e9
        by_start = sorted(mods, key=lambda x: x[1])
        starts = [m[1] for m in by_start]
        for n, s, own in _self_times(opl):
            j = bisect.bisect_right(starts, s) - 1
            prog = program_name(by_start[j][0]) if j >= 0 \
                and by_start[j][2] >= s else "?"
            key = f"{prog}/{n}"
            ops[key] = ops.get(key, 0.0) + own / 1e9
            if COLLECTIVE.search(n):
                collective[prog] = collective.get(prog, 0.0) + own / 1e9
        if i == 0:
            edges = [t0] + [x for seg in segs for x in seg] + [t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((_label(host, (a + b) // 2), (b - a) / 1e9))
    n = len(devs)
    return {
        "window_s": window,
        "busy_s": busy / n,
        "idle_share": 1.0 - busy / n / window,
        "program_s": {k: v / n for k, v in programs.items()},
        "collective_s": {k: v / n for k, v in collective.items()},
        "device_ops": [[k, v / n] for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in sorted(
            gaps, key=lambda kv: -kv[1])[:top]],
    }
