"""The engine's span hook and the requests' lifecycle stamps.

Every phase of ``PagedEngine.step`` goes through one hook that annotates
the profiler's trace and adds ``perf_counter`` seconds and a count to
the registry; phases never overlap, every device wait (a decode
window's tokens, a prefill's first token) is a ``pull``, and the
``StepTracer``'s dispatch spans come from the same hook.  The scheduler
stamps each request on the same clock, in lifecycle order, also across
a preemption."""
import glob
import time

import numpy as np
import pytest

from conftest import get_tiny_model, make_engine, seeded_prompts

PHASES = {"plan", "cow_copy", "prefill", "chunk", "push", "dispatch",
          "pull", "complete"}


def _phase_seconds(eng) -> float:
    return sum(v for k, v in eng.registry.counters.items()
               if k.startswith("engine.") and k.endswith("_s"))


def _stamp_order(r):
    return (r.submitted_at <= r.admitted_at <= r.first_token_at
            <= r.finished_at)


def test_stamps_in_lifecycle_order_under_preemption():
    """Chunked prefill, prefix cache (with copy-on-write) and a pool
    tight enough to preempt: every finished request was stamped
    submitted <= admitted <= first token <= finished."""
    cfg, params = get_tiny_model()
    prompts = seeded_prompts(cfg, 6, 12, seed=7, shared=9)
    eng = make_engine(cfg, params, max_batch=3, page_size=4, n_pages=14,
                      max_len=20, prefill_budget=0.0, prefix_cache=True,
                      chunked_prefill=True, chunk_tokens=4)
    for i, p in enumerate(prompts):
        eng.submit(np.asarray(p), 8, rid=f"r{i}")
    fin = eng.run()
    assert len(fin) == 6
    assert eng.metrics()["preemptions"] >= 1
    assert eng.metrics()["prefix_hits"] >= 1
    for r in fin:
        assert _stamp_order(r), (r.rid, r.submitted_at, r.admitted_at,
                                 r.first_token_at, r.finished_at)


def test_a_recompute_keeps_the_first_token_stamp():
    """A request preempted after its first token keeps that stamp and
    the admission that led to it; its recompute moves neither."""
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, max_batch=2, page_size=4, n_pages=16,
                      max_len=24)
    req = eng.submit(np.asarray(seeded_prompts(cfg, 1, 8)[0]), 6)
    eng.step()
    first, admitted = req.first_token_at, req.admitted_at
    assert first is not None and req.tokens
    eng.sched._preempt(req, type(eng.sched.plan_step())())
    eng.run()
    assert req.preemptions == 1 and req.state == "finished"
    assert (req.admitted_at, req.first_token_at) == (admitted, first)
    assert _stamp_order(req)


@pytest.mark.parametrize("kw,prompts", [
    (dict(chunked_prefill=True, chunk_tokens=4, prefix_cache=True),
     dict(shared=6)),
    (dict(fused=False), dict()),
    (dict(spec_decode=True, spec_k=3), dict(motif=3)),
], ids=["chunked-prefix", "per-step", "spec"])
def test_phase_seconds_fit_inside_each_step_call(kw, prompts):
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, max_len=40, **kw)
    for i, p in enumerate(seeded_prompts(cfg, 4, 12, **prompts)):
        eng.submit(np.asarray(p), 8, rid=f"r{i}")
    walls = []
    while eng.sched.waiting or eng.sched.running or eng.sched.prefilling:
        before = _phase_seconds(eng)
        t0 = time.perf_counter()
        eng.step()
        walls.append(time.perf_counter() - t0)
        assert _phase_seconds(eng) - before <= walls[-1]
    names = {k[len("engine."):].rsplit("_", 1)[0]
             for k in eng.registry.counters if k.startswith("engine.")}
    assert {"plan", "push", "dispatch", "pull", "complete"} <= names <= PHASES
    c = eng.registry.counters
    assert eng.step_calls == len(walls)
    # host pass: each call's time less its pull, so at most the calls'
    # wall time less the pulls, and at least what the phases other than
    # the pull took
    assert eng.host_pass_s <= sum(walls) - c["engine.pull_s"] + 1e-9
    assert eng.host_pass_s >= _phase_seconds(eng) - c["engine.pull_s"] \
        - 1e-9


@pytest.mark.parametrize("kw", [dict(), dict(spec_decode=True, spec_k=3),
                                dict(chunked_prefill=True, chunk_tokens=4)],
                         ids=["scan", "spec", "chunked"])
def test_one_pull_per_decode_window(kw):
    """Every device wait is a ``pull``: one per decode window or verify,
    and one per request for its first token (monolithic prefill or
    final chunk)."""
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, max_len=40, **kw)
    for i, p in enumerate(seeded_prompts(cfg, 5, 10, motif=3)):
        eng.submit(np.asarray(p), 9, rid=f"r{i}")
    fin = eng.run()
    assert len(fin) == 5 and eng.metrics()["preemptions"] == 0
    assert eng.windows_run > 0
    assert eng.registry.counter("engine.pull_n") == eng.windows_run + 5
    chunked = kw.get("chunked_prefill", False)
    assert eng.registry.counter("engine.prefill_n") == (0 if chunked else 5)


class _SlowArgmax:
    """``jax.numpy`` with an ``argmax`` that waits first: the engine
    takes a first token's argmax only inside its pull."""

    def __init__(self, jnp, wait_s):
        self._jnp, self.wait_s, self.calls = jnp, wait_s, 0

    def __getattr__(self, name):
        return getattr(self._jnp, name)

    def argmax(self, *a, **kw):
        self.calls += 1
        time.sleep(self.wait_s)
        return self._jnp.argmax(*a, **kw)


@pytest.mark.parametrize("kw", [dict(),
                                dict(chunked_prefill=True, chunk_tokens=4)],
                         ids=["monolithic", "chunked"])
def test_host_pass_excludes_the_first_token_pulls(kw):
    """A slow first-token pull lands in ``engine.pull_s`` and not in
    ``host_pass_s`` (nor in the ``prefill`` / ``chunk`` phases)."""
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, max_len=40, **kw)
    prompts = seeded_prompts(cfg, 4, 10)
    for i, p in enumerate(prompts):    # the same run once, to compile
        eng.submit(np.asarray(p), 3, rid=f"w{i}")
    eng.run()
    eng.reset_metrics()
    wait = 0.25
    eng._jnp = slow = _SlowArgmax(eng._jnp, wait)
    for i, p in enumerate(prompts):
        eng.submit(np.asarray(p), 3, rid=f"r{i}")
    eng.run()
    assert slow.calls == 4
    c = eng.registry.counters
    assert c["engine.pull_s"] >= 4 * wait
    assert eng.host_pass_s < wait
    assert c.get("engine.prefill_s", 0) + c.get("engine.chunk_s", 0) < wait


@pytest.mark.parametrize("kw,program", [
    (dict(), "prefill"),
    (dict(chunked_prefill=True, chunk_tokens=4), "chunk_prefill"),
], ids=["monolithic", "chunked"])
def test_tracer_dispatch_spans_come_from_the_span_hook(kw, program):
    """One span system: with tracing on, each decode window's pull
    records the tracer's ``scan`` span and each first token's pull its
    prefill program's span, each measured from its program's dispatch,
    so every pull lies inside one of them."""
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params, trace=True, **kw)
    for i, p in enumerate(seeded_prompts(cfg, 4, 8)):
        eng.submit(np.asarray(p), 6, rid=f"r{i}")
    eng.run()
    spans = [s for s in eng.tracer.spans if s.cat == "dispatch"]
    c = eng.registry.counters
    names = [s.name for s in spans]
    assert names.count("scan") == eng.windows_run
    if program == "prefill":
        assert names.count("prefill") == c["engine.prefill_n"] == 4
    else:
        # every chunk: the intermediate ones at their dispatch, the
        # final one at its first token
        assert names.count("chunk_prefill") == c["engine.chunk_n"] \
            == eng.chunk_dispatches
    assert set(names) == {"scan", program}
    measured = sum(s.args["measured_s"] for s in spans)
    assert all(s.args["measured_s"] >= 0 for s in spans)
    assert c["engine.pull_s"] <= measured


def test_engine_phases_reach_the_profiler_trace(tmp_path):
    import jax
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params)
    for i, p in enumerate(seeded_prompts(cfg, 2, 8)):
        eng.submit(np.asarray(p), 4, rid=f"r{i}")
    eng.run()                         # compile outside the trace
    for i, p in enumerate(seeded_prompts(cfg, 2, 8, seed=1)):
        eng.submit(np.asarray(p), 4, rid=f"s{i}")
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")}
    assert {"engine.plan", "engine.prefill", "engine.push",
            "engine.dispatch", "engine.pull", "engine.complete"} <= names


def test_step_programs_carry_their_model_scopes():
    """The scopes reach the compiled program's op metadata, which is
    what a device trace names operations by."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serving.engine import _jitted_steps
    cfg, params = get_tiny_model()
    B, ps, nmax = 2, 4, 8
    pools = jax.eval_shape(lambda: lm.init_paged_caches(cfg, n_pages=16,
                                                        page_size=ps))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    text = _jitted_steps(cfg)["scan"].lower(
        params, i32(B, 1), pools, i32(B, nmax), i32(B), i32(B),
        k=2).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert any(n.startswith("jit(serve_scan)/serve_scan/") for n in names)
    parts = {p for n in names for p in n.split("/")}
    assert {"embed", "qkv", "kv_append", "kv_gather", "attend", "out_proj",
            "mlp", "head"} <= parts
