"""The load loop: offers a schedule to the engine on the wall clock and
records, per request, when it was due, submitted, and when each of its
tokens came back.

A request is submitted when it is due, whatever the engine is doing:
at a fixed time after the load starts, or, where the schedule chains it
to an earlier request (a closed loop), that long after the earlier one
finished.  Between submissions the loop calls ``eng.step()`` (the
engine's own ``safe_horizon`` sizes each decode window) or, when the
engine is idle, waits for the next arrival.  Every time is the
harness's ``perf_counter``; tokens come back when ``step`` returns,
which blocks on the device.

With ``trace`` the loop wraps each call in a host span on the
profiler's clock (``bench.submit``, ``bench.step``, ``bench.wait``),
traces an interval inside the window, and counts the model work the
engine did in that interval from the token and prefill progress it
observes (see :mod:`benchkit.counting`).
"""
from __future__ import annotations

import contextlib
import gc
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from benchkit import counting
from benchkit.traffic import Item, Schedule

# how long after the window closes the loop keeps serving so that every
# request due in the window gets its first token (later is late, and its
# time to first token counts the wait)
DRAIN_LIMIT_S = 60.0
# the traced interval: from this share of the window, this long (at most
# half the window); a few seconds keep the trace small
TRACE_AT = 0.25
TRACE_S = 4.0


@dataclass
class Record:
    item: Item
    due: float                         # absolute perf_counter seconds
    req: object = None                 # the engine's Request
    submitted: Optional[float] = None
    first: Optional[float] = None
    seen: int = 0                      # tokens seen so far
    seen_in_window: int = 0            # tokens seen by the window's close
    last_in_window: Optional[float] = None
    done: Optional[float] = None
    failed: bool = False
    prefilled: Optional[int] = None    # prompt tokens computed so far


@dataclass
class Work:
    """Model work the engine did inside the traced interval."""
    decode_flops: float = 0.0
    decode_kv_bytes: float = 0.0
    decode_steps: int = 0
    prefill_flops: float = 0.0


@dataclass
class Outcome:
    records: List[Record]
    t0: float
    t1: float
    t_stop: float                      # when the loop stopped serving
    counters: Dict[str, float]
    work: Optional[Work] = None
    compiles_in_window: int = 0
    preemptions: int = 0
    window_tokens: int = 0
    # the window as measured: the loop's first look at the clock at or
    # after ``t0``, and its first at or after ``t1``.  The engine hands
    # tokens back a decode window at a time, so these edges fall on its
    # step boundaries, and ``window_tokens`` counts the tokens that came
    # back between them
    opened: float = 0.0
    closed: float = 0.0
    # (start, end) of each profiler start or stop call, which holds the
    # loop: requests due then are late because of the measurement
    pauses: List[tuple] = field(default_factory=list)
    # (start, end) of each ``eng.step`` call begun inside the window, and
    # (generation, seconds) of each garbage collection in it: where the
    # host stood still
    steps: List[tuple] = field(default_factory=list)
    gc_pauses: List[tuple] = field(default_factory=list)


COUNTERS = ("decode_steps", "decode_tokens", "decode_time_s",
            "prefill_tokens")


def counters(eng) -> Dict[str, float]:
    out = {k: getattr(eng, k) for k in COUNTERS}
    out["prefill_tokens_cached"] = (eng.cache.stats.tokens_cached
                                    if eng.cache is not None else 0)
    return out


class CompileCounter:
    """Counts programs lowered (every jit cache miss lowers one) while
    ``armed``, and, always, programs the persistent compilation cache
    served or missed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if self.armed and event == self.EVENT:
            self.count += 1

    def _on_event(self, event, **kw):
        if event in self.CACHE:
            self.cache[self.CACHE[event]] += 1


class GcPauses:
    """The garbage collections the interpreter makes while ``armed``,
    as (generation, seconds)."""

    def __init__(self):
        self.armed = False
        self.pauses: List[tuple] = []
        self._t = 0.0
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.armed:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self):
        gc.callbacks.remove(self._on)


class Loop:
    def __init__(self, eng, schedule: Schedule, shape,
                 trace_dir: Optional[str] = None):
        """``shape`` is the configuration's work count (see
        :mod:`benchkit.counting`)."""
        self.eng = eng
        self.schedule = schedule
        self.shape = shape
        self.clock = time.perf_counter
        self.trace_dir = trace_dir
        self.live: List[Record] = []
        self.records: List[Record] = []
        self.work: Optional[Work] = None
        self.preemptions = 0
        self.window_tokens = 0
        self.pauses: List[tuple] = []
        self.steps: List[tuple] = []
        self.t0 = self.t1 = 0.0
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.tracing = False
        self.traced_work: Optional[Work] = None
        self._traced_span = None
        self._steps_at_start = 0

    # -- spans on the profiler's clock (only when tracing) ------------------
    def _span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _start_trace(self) -> None:
        import jax
        t = self.clock()
        jax.profiler.start_trace(self.trace_dir)
        self.pauses.append((t, self.clock()))
        self._traced_span = jax.profiler.TraceAnnotation("bench.traced")
        self._traced_span.__enter__()
        self.work = Work()
        self._steps_at_start = self.eng.decode_steps
        self.tracing = True

    def _stop_trace(self) -> None:
        import jax
        t = self.clock()
        jax.block_until_ready(self.eng.pools)
        self.work.decode_steps = self.eng.decode_steps - self._steps_at_start
        self._traced_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.pauses.append((t, self.clock()))
        self.traced_work, self.work = self.work, None
        self.tracing = False

    def _busy(self) -> bool:
        s = self.eng.sched
        return bool(s.waiting or s.running or s.prefilling)

    def _submit(self, rec: Record, now: float) -> None:
        with self._span("bench.submit"):
            try:
                rec.req = self.eng.submit(rec.item.prompt, rec.item.gen,
                                          tenant=rec.item.tenant)
            except ValueError:
                rec.failed = True
        rec.submitted = now
        if not rec.failed:
            self.live.append(rec)

    def _observe(self, now: float) -> List[Record]:
        """Token and prefill progress since the last call; returns the
        records that finished."""
        done = []
        sh, work = self.shape, self.work
        for rec in self.live:
            req = rec.req
            n = len(req.tokens)
            if n < rec.seen:            # preempted: recompute from scratch
                self.preemptions += 1
                rec.seen, rec.prefilled = 0, None
            if req.state != "waiting":
                if rec.prefilled is None:      # admitted: cached prefix
                    rec.prefilled = req.cached_tokens
                upto = req.prompt_len if n else max(req.prefilled,
                                                    rec.prefilled)
                if upto > rec.prefilled and work is not None:
                    work.prefill_flops += counting.prefill(
                        sh, rec.prefilled, upto - rec.prefilled,
                        logits=upto == req.prompt_len)
                rec.prefilled = upto
            if work is not None:
                for i in range(max(rec.seen, 1), n):
                    ctx = req.prompt_len + i
                    work.decode_flops += sh.token_flops(ctx) + sh.head_flops
                    work.decode_kv_bytes += ctx * sh.kv_bytes_per_token
            if n > rec.seen:
                if rec.first is None:
                    rec.first = now
                if self.opened is not None and self.closed is None:
                    self.window_tokens += n - rec.seen
                if now <= self.t1:
                    rec.seen_in_window = n
                    rec.last_in_window = now
                rec.seen = n
            if req.state == "finished":
                rec.done = now
                done.append(rec)
        if done:
            self.live = [r for r in self.live if r.done is None]
        return done

    def run(self, seconds: float, compiles: Optional[CompileCounter] = None
            ) -> Outcome:
        sched = self.schedule
        start = self.clock()
        self.t0 = start + sched.warm_in_s
        self.t1 = self.t0 + seconds
        chained: Dict[int, List] = {}
        pending = []
        for i in sched.items:
            if i.after < 0:
                pending.append(Record(i, due=start + i.due_s))
            else:
                chained.setdefault(i.after, []).append(i)
        pending.sort(key=lambda r: r.due)
        nxt = 0

        def release(rec: Record, t: float) -> None:
            """Make the requests chained to ``rec`` due, in due order."""
            for i in chained.pop(rec.item.index, ()):
                new = Record(i, due=t + i.due_s)
                k = nxt
                while k < len(pending) and pending[k].due <= new.due:
                    k += 1
                pending.insert(k, new)
        trace_on = trace_off = None
        if self.trace_dir is not None:
            trace_on = self.t0 + TRACE_AT * seconds
            trace_off = trace_on + min(TRACE_S, 0.5 * seconds)
        snap0 = None
        gcs = GcPauses()
        while True:
            now = self.clock()
            if snap0 is None and now >= self.t0:
                self.opened = now
                snap0 = counters(self.eng)
                gcs.armed = True
                if compiles is not None:
                    compiles.armed = True
            if now >= self.t1:
                if self.closed is None:
                    self.closed = now
                gcs.armed = False
                if compiles is not None:
                    compiles.armed = False
                if not self.tracing and not any(
                        r.first is None and r.due < self.t1
                        for r in self.live) \
                        or now >= self.t1 + DRAIN_LIMIT_S:
                    break
            while nxt < len(pending) and pending[nxt].due <= now:
                rec = pending[nxt]
                nxt += 1
                self.records.append(rec)
                self._submit(rec, self.clock())
                if rec.failed:
                    release(rec, rec.submitted)
            if trace_on is not None and now >= trace_on:
                self._start_trace()
                trace_on = None
            elif self.tracing and now >= trace_off:
                self._stop_trace()
            if self._busy():
                ts = self.clock()
                with self._span("bench.step"):
                    self.eng.step()
                t = self.clock()
                if self.t0 <= ts < self.t1:
                    self.steps.append((ts, t))
                for rec in self._observe(t):
                    release(rec, t)
            else:
                wake = pending[nxt].due if nxt < len(pending) else now + 1e-3
                with self._span("bench.wait"):
                    time.sleep(max(0.0, min(wake, self.t1) - self.clock()))
        snap1 = counters(self.eng)
        gcs.close()
        return Outcome(
            records=self.records, t0=self.t0, t1=self.t1,
            t_stop=self.clock(),
            counters={k: snap1[k] - (snap0 or snap1)[k] for k in snap1},
            work=self.traced_work,
            compiles_in_window=compiles.count if compiles else 0,
            preemptions=self.preemptions,
            window_tokens=self.window_tokens, opened=self.opened,
            closed=self.closed, pauses=self.pauses,
            steps=self.steps, gc_pauses=gcs.pauses)
