"""Engine phases and model scopes in a profiler trace.

The program annotates each phase of its engine step on the profiler's
clock (host spans ``engine.<phase>``) and scopes the operations of its
step programs (``jax.named_scope``: ``qkv``, ``kv_append``,
``kv_gather``, ``attend``, ``out_proj``, ``mlp``, ``head``, ``embed``).
:func:`extract` reads both from an ``.xplane.pb`` on top of
:func:`benchkit.trace.extract`'s record; :func:`reduce` turns that
record, clipped to the traced interval, into

* ``scope_s``: device self time per ``program/scope``, with
  ``program/-`` for operations under no model scope and ``program/?``
  for operations whose scope is not known, averaged over devices;
* ``idle_by_phase``: the device's idle time in the interval (the first
  device, as ``trace.reduce``'s idle gaps), split by the ``engine.*``
  span open on the host while the device stood idle, ``outside`` where
  none was.

A TPU v5e trace gives an operation's event no ``op_name`` (its stats
are offsets and durations), so an operation's scope comes from the
compiled program's HLO text: :func:`hlo_scopes` maps each instruction
to its scope, ``-`` where it carries no ``op_name`` (copies of a loop's
carry, layout copies the compiler made).  One program compiles to a
variant per static shape (``serve_scan`` per window size), and the
variants number their instructions differently, so an instruction is
known by its name and result shape (:func:`signature`), and each
compiled module met in the trace is matched to the variant that knows
most of its operations.  An operation no given variant knows reads as
``?``.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Tuple

from benchkit import trace

SCOPES = ("qkv", "kv_append", "kv_gather", "attend", "out_proj", "mlp",
          "head", "embed")
PHASE_PREFIX = "engine."
NO_SCOPE = "-"
UNKNOWN = "?"
# a module is matched to a variant only if the variant knows at least
# this share of the module's operations
MIN_MATCH = 0.9

Signature = Tuple[str, str]            # (instruction name, result shape)
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def scope_of(op_name: Optional[str]) -> str:
    """The innermost model scope named in ``op_name``, else ``-``."""
    for part in reversed((op_name or "").split("/")):
        if part in SCOPES:
            return part
    return NO_SCOPE


def signature(line: str) -> Optional[Signature]:
    """(name, first array shape of the result) of an HLO instruction,
    from a line of compiled text or a trace's operation event (which
    prints operand shapes too, after the result's)."""
    m = _INSTR.match(line)
    return (m.group(1), m.group(2) or "") if m else None


def hlo_scopes(hlo_text: str) -> Dict[Signature, str]:
    """Signature -> scope of every instruction of compiled HLO text that
    prints each instruction's metadata."""
    out = {}
    for line in hlo_text.splitlines():
        sig = signature(line)
        if sig is not None:
            m = re.search(r'metadata=\{[^}]*op_name="([^"]*)"', line)
            out[sig] = scope_of(m.group(1) if m else None)
    return out


def match(ops: List[Signature], variants: List[Dict[Signature, str]]
          ) -> Tuple[Optional[int], float]:
    """The variant that knows most of a module's operations, and the
    share it knows; ``None`` below :data:`MIN_MATCH`."""
    best, share = None, 0.0
    for i, v in enumerate(variants):
        s = sum(o in v for o in ops) / max(len(ops), 1)
        if s > share:
            best, share = i, s
    return (best if share >= MIN_MATCH else None), share


def extract(path: str,
            hlo: Optional[Dict[str, List[Dict[Signature, str]]]] = None
            ) -> dict:
    """:func:`benchkit.trace.extract`'s record plus ``engine``: the host
    spans named ``engine.*``, and per device ``scopes``: each operation's
    scope, in the order of its ``ops``.  ``hlo`` maps a program name
    (``serve_scan``) to the :func:`hlo_scopes` of its compiled variants;
    operations of other programs read as ``?``.  ``mapped`` counts the
    operations found, ``matched`` gives each compiled module met (by its
    trace name) the variant it was matched to and the share known."""
    import jax
    rec = trace.extract(path)
    pd = jax.profiler.ProfileData.from_file(path)
    rec["engine"] = sorted(
        ((e.name, int(e.start_ns), int(e.end_ns))
         for plane in pd.planes if plane.name.startswith("/host:")
         for line in plane.lines for e in line.events
         if e.name.startswith(PHASE_PREFIX)), key=lambda e: e[1])
    # the operations' full event names, in trace.extract's order
    raw = {plane.name: [e.name for line in plane.lines
                        if line.name == "XLA Ops" for e in line.events]
           for plane in pd.planes if plane.name in rec["devices"]}
    rec["mapped"], rec["matched"] = 0, {}
    for dev, d in rec["devices"].items():
        mods = sorted(d["modules"], key=lambda x: x[1])
        starts = [m[1] for m in mods]
        owner = []                      # each op's module, or None
        for _, s, _ in d["ops"]:
            j = bisect.bisect_right(starts, s) - 1
            owner.append(mods[j][0] if j >= 0 and mods[j][2] >= s
                         else None)
        sigs = [signature(n) for n in raw[dev]]
        by_module: Dict[str, List[Signature]] = {}
        for mod, sig in zip(owner, sigs):
            if mod is not None:
                by_module.setdefault(mod, []).append(sig)
        chosen = {}
        for mod, ops in by_module.items():
            variants = (hlo or {}).get(trace.program_name(mod))
            if variants:
                i, share = match(ops, variants)
                rec["matched"][mod] = [i, share]
                chosen[mod] = variants[i] if i is not None else None
        d["scopes"] = []
        for mod, sig in zip(owner, sigs):
            found = (chosen.get(mod) or {}).get(sig)
            rec["mapped"] += found is not None
            d["scopes"].append(found or UNKNOWN)
    return rec


def reduce(rec: dict) -> dict:
    """``scope_s`` and ``idle_by_phase`` over the traced interval; a
    record without scopes or engine spans (``trace.extract``'s) reads
    as all ``-`` and all ``outside``."""
    t0, t1 = trace.traced_interval(rec)
    devs = sorted(rec["devices"])
    scope_s: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for i, dev in enumerate(devs):
        d = rec["devices"][dev]
        mods = sorted(trace._clip(d["modules"], t0, t1), key=lambda x: x[1])
        starts = [m[1] for m in mods]
        scoped = trace._clip(
            [(sc, s, e) for (_, s, e), sc in zip(
                d["ops"], d.get("scopes") or [NO_SCOPE] * len(d["ops"]))],
            t0, t1)
        for sc, s, own in trace._self_times(scoped):
            j = bisect.bisect_right(starts, s) - 1
            prog = trace.program_name(mods[j][0]) if j >= 0 \
                and mods[j][2] >= s else "?"
            key = f"{prog}/{sc}"
            scope_s[key] = scope_s.get(key, 0.0) + own / 1e9
        if i == 0:
            segs = trace._union(scoped or mods)
            edges = [t0] + [x for seg in segs for x in seg] + [t1]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2])
                    if b > a]
            for phase, secs in _split(gaps, rec.get("engine", [])).items():
                idle[phase] = idle.get(phase, 0.0) + secs
    n = len(devs)
    return {"scope_s": {k: v / n for k, v in scope_s.items()},
            "idle_by_phase": idle}


def _split(gaps: List[Tuple[int, int]], spans: List[trace.Event]
           ) -> Dict[str, float]:
    """Seconds of each gap under each span (spans do not nest), and
    ``outside`` for the rest."""
    out: Dict[str, float] = {}
    for a, b in gaps:
        covered = 0
        for name, s, e in spans:
            if s >= b:
                break
            lap = min(b, e) - max(a, s)
            if lap > 0:
                out[name] = out.get(name, 0.0) + lap / 1e9
                covered += lap
        if b - a > covered:
            out["outside"] = out.get("outside", 0.0) \
                + (b - a - covered) / 1e9
    return out
