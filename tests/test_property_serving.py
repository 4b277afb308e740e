"""Property-based allocator/scheduler tests (hypothesis-driven).

Randomized op sequences — alloc / share / release / grow / reserve /
truncate_to / free — run against :class:`PageAllocator`, asserting after
every op the invariants the serving stack leans on:

* refcount conservation — every non-null page is on exactly one side
  (free list at refcount 0, or allocated at refcount >= 1), and the
  free list holds no duplicates (``check_conservation``);
* no double free — releasing an unallocated page always raises;
* null-page invariance — page 0 is never allocated, held, shared or
  refcounted, no matter the op sequence;
* fault-plane extension — op sequences that interleave ``fail_node`` /
  ``restore_node`` keep the three-way conservation partition (free /
  allocated / quarantined-parked), never re-allocate or share a
  quarantined page while its node is down, and drain back to a whole
  pool once every node restores.

Plus scheduler conservation under randomized arrival traces (both the
monolithic FIFO machine and the chunked EDF machine with chunk-step
transitions: request conservation, strict chunk progress per round — the
no-starvation property — and page-aligned chunk boundaries), a
chunked-vs-monolithic engine bit-identity property over drawn
(chunk_tokens, prompt_len) pairs including non-page-aligned tails, and
algebraic properties of the n-gram proposer/acceptance rule.

See docs/TESTING.md.
"""
import pytest

from hypothesis import given, settings, strategies as st
from repro.serving import (ContinuousBatchScheduler, NULL_PAGE,
                           PageAllocator, Request, propose_ngram)
from repro.serving.spec_decode import NGramSpec

# an op is (opcode, rid index, size): the interpreter maps out-of-domain
# ops to no-ops so every generated sequence is valid
OPS = st.lists(st.tuples(st.integers(0, 6), st.integers(0, 3),
                         st.integers(0, 9)), max_size=60)


def _check_invariants(a: PageAllocator):
    assert a.check_conservation()
    assert NULL_PAGE not in a.refcount
    for pages in a.held.values():
        assert NULL_PAGE not in pages
    assert a.free_pages + a.pages_in_use == a.n_pages - 1


def _apply(a: PageAllocator, shared_refs, op):
    """One interpreter step; ``shared_refs`` tracks extra references we
    took (a stand-in for prefix-cache nodes / second tenants) so the
    drain phase can balance them."""
    code, r, n = op
    rid = f"r{r}"
    held = a.held.get(rid)
    if code == 0 and held is None:
        a.alloc(rid, n % 5 + 1)
    elif code == 1 and held is not None:
        a.grow(rid, n % 3 + 1)
    elif code == 2 and held is not None:
        a.free(rid)
    elif code == 3 and held:
        page = held[n % len(held)]
        a.share(page)
        shared_refs.append(page)
    elif code == 4 and shared_refs:
        a.release_page(shared_refs.pop(n % len(shared_refs)))
    elif code == 5 and held is not None:
        a.reserve(rid, n * a.page_size // 2)
    elif code == 6 and held is not None:
        a.truncate_to(rid, n * a.page_size // 2)


@settings(max_examples=60, deadline=None)
@given(OPS)
def test_allocator_random_ops_conserve_pages(ops):
    a = PageAllocator(n_pages=17, page_size=4, n_nodes=3)
    shared_refs = []
    for op in ops:
        _apply(a, shared_refs, op)
        _check_invariants(a)
    # drain: balance every reference; the pool must come back whole
    for page in shared_refs:
        a.release_page(page)
    for rid in list(a.held):
        a.free(rid)
    _check_invariants(a)
    assert a.pages_in_use == 0 and a.free_pages == a.n_pages - 1


@settings(max_examples=60, deadline=None)
@given(OPS)
def test_allocator_random_ops_never_double_free(ops):
    """After any op sequence, releasing a page that is on the free list
    raises instead of corrupting the free list."""
    a = PageAllocator(n_pages=9, page_size=4, n_nodes=2)
    shared_refs = []
    for op in ops:
        _apply(a, shared_refs, op)
    free = [p for f in a._free_by_node for p in f]
    for page in free[:3]:
        with pytest.raises(ValueError):
            a.release_page(page)
    with pytest.raises(ValueError):
        a.share(NULL_PAGE)
    _check_invariants(a)


# the fault-aware op space adds fail_node (7) and restore_node (8)
FAULT_OPS = st.lists(st.tuples(st.integers(0, 8), st.integers(0, 3),
                               st.integers(0, 9)), max_size=60)


def _check_fault_invariants(a: PageAllocator):
    """The quarantine-extended partition: free + allocated +
    quarantined-parked == n_pages - 1, with no page on two sides."""
    assert a.check_conservation()
    assert NULL_PAGE not in a.refcount
    assert NULL_PAGE not in a.quarantined
    free = [p for f in a._free_by_node for p in f]
    assert not (set(free) & a.quarantined)
    for node in a.failed_nodes:
        assert not a._free_by_node[node], \
            "a failed node's free list must be empty"
    parked = len(a.quarantined - set(a.refcount))
    assert a.free_pages + a.pages_in_use + parked == a.n_pages - 1


def _apply_faulty(a: PageAllocator, shared_refs, op):
    """The fault-aware interpreter: base ops plus node fail/restore.
    ``share`` only targets non-quarantined pages (sharing a quarantined
    page is *asserted* to raise separately)."""
    code, r, n = op
    if code == 7:
        a.fail_node(n % a.n_nodes)
    elif code == 8:
        a.restore_node(n % a.n_nodes)
    elif code == 3:
        held = a.held.get(f"r{r}")
        if held:
            page = held[n % len(held)]
            if page not in a.quarantined:
                a.share(page)
                shared_refs.append(page)
    else:
        _apply(a, shared_refs, op)


@settings(max_examples=60, deadline=None)
@given(FAULT_OPS)
def test_allocator_fault_ops_conserve_and_quarantine(ops):
    """Random interleavings of alloc/share/release/grow/truncate with
    node failures and re-joins: the extended conservation partition
    holds after EVERY op, a quarantined page is never re-allocated or
    shared while its node is down, and once every node restores and
    every reference drains the pool comes back whole."""
    a = PageAllocator(n_pages=17, page_size=4, n_nodes=3)
    shared_refs = []
    for op in ops:
        _apply_faulty(a, shared_refs, op)
        _check_fault_invariants(a)
        if a.quarantined:
            # never re-served: a quarantined page cannot gain readers
            with pytest.raises(ValueError):
                a.share(next(iter(a.quarantined)))
        # and never re-allocated: a fresh allocation only sees healthy
        # stripes
        probe = a.alloc("probe", 2)
        if probe is not None:
            assert not (set(probe) & a.quarantined)
            a.free("probe")
        _check_fault_invariants(a)
    # drain: restore every node, release every reference — the pool
    # must come back whole (no page leaked into quarantine limbo)
    for node in range(a.n_nodes):
        a.restore_node(node)
    assert not a.quarantined and not a.failed_nodes
    for page in shared_refs:
        a.release_page(page)
    for rid in list(a.held):
        a.free(rid)
    _check_invariants(a)
    assert a.pages_in_use == 0 and a.free_pages == a.n_pages - 1


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 8), st.integers(1, 6)),
                min_size=1, max_size=10),
       st.integers(8, 20), st.integers(1, 4))
def test_scheduler_random_traces_conserve_requests(reqs, n_pages,
                                                   max_batch):
    """Any admissible random trace drains with every request finished
    exactly once, every token accounted for, and every page returned —
    preemption and page pressure included."""
    a = PageAllocator(n_pages=n_pages, page_size=4, n_nodes=2)
    s = ContinuousBatchScheduler(a, max_batch=max_batch)
    submitted = 0
    for i, (plen, gen) in enumerate(reqs):
        if a.pages_for(plen + gen) > n_pages - 1:
            continue               # larger-than-pool requests are rejected
        s.submit(Request(rid=f"q{i}", prompt_len=plen, gen=gen))
        submitted += 1
    steps = 0
    while (s.waiting or s.running) and steps < 2000:
        plan = s.plan_step()
        for req in plan.admitted:
            s.note_first_token(req, token=1)
        s.complete_step({slot: 1 for slot in list(s.running)})
        steps += 1
    assert steps < 2000, "scheduler wedged"
    assert s.conserved(submitted)
    assert len(s.finished) == submitted
    for r in s.finished:
        assert len(r.tokens) == r.gen
    assert a.pages_in_use == 0
    _check_invariants(a)


@settings(max_examples=40, deadline=None)
@given(st.lists(st.tuples(st.integers(1, 12), st.integers(1, 6),
                          st.integers(0, 2)),
                min_size=1, max_size=10),
       st.integers(10, 24), st.integers(1, 4), st.integers(2, 9))
def test_chunked_scheduler_random_traces_conserve_and_progress(
        reqs, n_pages, max_batch, chunk_tokens):
    """The chunked op-machine: any admissible random trace — drawn
    prompt/gen lengths, drawn SLO classes, drawn (possibly misaligned)
    chunk size, page pressure and preemption included — drains through
    chunk-step transitions with

    * request conservation (every request finished exactly once, every
      token accounted for, every page returned);
    * STRICT chunk progress: each ``plan_chunks`` round advances every
      prefilling request by >= 1 chunk (the no-starvation guarantee);
    * non-final chunk boundaries page-aligned whenever the chunk can
      reach a boundary (small chunks stay inside the start's page);
    * allocator refcount conservation after every round."""
    slo_names = ("interactive", "standard", "batch")
    a = PageAllocator(n_pages=n_pages, page_size=4, n_nodes=2)
    s = ContinuousBatchScheduler(a, max_batch=max_batch, chunked=True,
                                 chunk_tokens=chunk_tokens,
                                 prefill_cost_s=lambda n: float(n),
                                 decode_cost_s=1.0)
    submitted = 0
    for i, (plen, gen, slo_i) in enumerate(reqs):
        if a.pages_for(plen + gen) > n_pages - 1:
            continue               # larger-than-pool requests are rejected
        s.submit(Request(rid=f"q{i}", prompt_len=plen, gen=gen,
                         slo=slo_names[slo_i]))
        submitted += 1
    steps = 0
    while (s.waiting or s.prefilling or s.running) and steps < 2000:
        s.plan_step()
        before = {r.rid: r.prefilled for r in s.prefilling.values()}
        tasks = s.plan_chunks(window=2)
        advanced = set()
        for req, start, n in tasks:
            assert n >= 1 and start + n <= req.prompt_len
            end = start + n
            if end < req.prompt_len:
                # non-final chunks land on a page boundary unless the
                # chunk is too small to reach one from its start (then
                # it stays inside the start's page and realigns later)
                assert end % a.page_size == 0 \
                    or end // a.page_size == start // a.page_size
            advanced.add(req.rid)
        # strict progress: every request that was prefilling when the
        # round was planned got at least one chunk
        assert advanced == set(before), "a prefilling request starved"
        for req in list(s.prefilling.values()):
            assert req.prefilled >= before[req.rid] + 1
            if req.prefilled == req.prompt_len:
                s.finish_prefill(req, token=1)
        s.complete_step({slot: 1 for slot in list(s.running)})
        assert a.check_conservation()
        assert NULL_PAGE not in a.refcount
        steps += 1
    assert steps < 2000, "chunked scheduler wedged"
    assert s.conserved(submitted)
    assert len(s.finished) == submitted
    for r in s.finished:
        assert len(r.tokens) == r.gen
    assert a.pages_in_use == 0
    _check_invariants(a)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 12), st.lists(st.integers(5, 18), min_size=1,
                                    max_size=3),
       st.integers(2, 5))
def test_chunked_engine_bit_identical_to_monolithic(chunk_tokens, plens,
                                                    gen):
    """Chunked prefill is bit-identical to the monolithic engine, and to
    itself with tracing on, for ANY drawn (chunk_tokens, prompt_len)
    pair — page-aligned or not, final chunks partial or not.  Few
    examples (the engine compiles per pow2 prefill bucket), but each
    drives the full dispatch path."""
    import sys
    sys.path.insert(0, "tests")
    import numpy as np
    from conftest import get_tiny_model, make_engine, seeded_prompts

    cfg, params = get_tiny_model()
    max_len = max(plens) + gen
    prompts = [seeded_prompts(cfg, 1, plen, seed=60 + i)[0]
               for i, plen in enumerate(plens)]

    def run(chunked, trace=False):
        eng = make_engine(cfg, params, max_batch=2, page_size=4,
                          n_pages=48, max_len=max_len, fused=True,
                          max_window=4, chunked_prefill=chunked,
                          chunk_tokens=chunk_tokens, trace=trace)
        for i, (p, g) in enumerate(zip(prompts, [gen] * len(prompts))):
            eng.submit(np.asarray(p), g, rid=f"r{i}", slo="interactive")
        toks = {r.rid: list(r.tokens) for r in eng.run()}
        assert eng.alloc.pages_in_use == 0
        assert eng.alloc.check_conservation()
        return toks

    chunked = run(True)
    assert chunked == run(False), (chunk_tokens, plens, gen)
    # the flight recorder observes, never steers
    assert chunked == run(True, trace=True), (chunk_tokens, plens, gen)


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 6), min_size=0, max_size=40),
       st.integers(1, 8))
def test_propose_ngram_drafts_are_history_slices(history, k):
    """A non-empty draft is always a verbatim slice of the history that
    follows an occurrence of the history's own tail n-gram."""
    d = propose_ngram(history, k, max_n=3)
    assert len(d) <= k
    if not d:
        return
    found = False
    for n in range(1, 4):
        if n >= len(history):
            break
        tail = list(history[-n:])
        for i in range(len(history) - n):
            if list(history[i:i + n]) == tail \
                    and list(history[i + n:i + n + k]) == d:
                found = True
    assert found


@settings(max_examples=100, deadline=None)
@given(st.lists(st.integers(0, 6), min_size=0, max_size=28),
       st.integers(0, 9), st.integers(1, 3),
       st.lists(st.integers(0, 6), min_size=0, max_size=4),
       st.data())
def test_device_propose_matches_host_proposer(history, k, max_n, junk,
                                              data):
    """The differential proposer oracle (docs/TESTING.md rung): the
    jitted :func:`device_propose` suffix match over a fixed-width,
    junk-padded device buffer is token-identical to the host reference
    :func:`propose_ngram` over the exact history — same
    longest-n-first, earliest-occurrence, end-of-history-clipped
    drafts, for looping, aperiodic, shorter-than-n and padding-adjacent
    histories alike.  The padding bytes beyond ``hist_len`` are drawn
    adversarially (including copies of the history's own tail, the case
    a missing validity mask would false-match)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serving.spec_decode import device_propose

    H, k_max = 32, 9
    min_n = data.draw(st.integers(1, max_n))
    buf = np.zeros((H,), np.int32)
    buf[:len(history)] = history
    # adversarial tail padding right past hist_len: junk, then repeat
    # the history's own tail so clipped indices look like matches
    pad = junk + list(history[-3:])
    buf[len(history):len(history) + len(pad)] = pad[:H - len(history)]
    fn = jax.jit(device_propose, static_argnames=("k_max", "max_n",
                                                  "min_n"))
    draft, m = fn(jnp.asarray(buf), jnp.int32(len(history)),
                  jnp.int32(k), k_max=k_max, max_n=max_n, min_n=min_n)
    draft, m = np.asarray(draft), int(m)
    ref = propose_ngram(history, min(k, k_max), max_n=max_n, min_n=min_n)
    assert list(draft[:m]) == ref
    assert all(int(t) == 0 for t in draft[m:])   # zero-masked past m


@settings(max_examples=60, deadline=None)
@given(st.lists(st.integers(0, 3), min_size=1, max_size=8),
       st.lists(st.integers(0, 3), min_size=2, max_size=9))
def test_accept_rule_emits_exactly_the_greedy_tokens(draft, greedy):
    """accept() output == the greedy sequence up to and including the
    first divergence — never more, never different (this is the whole
    exactness argument for speculative decoding)."""
    if len(greedy) < len(draft) + 1:
        draft = draft[:len(greedy) - 1]
    spec = NGramSpec(k=8)
    out = spec.accept(draft, greedy)
    assert 1 <= len(out) <= len(draft) + 1
    assert out == [int(t) for t in greedy[:len(out)]]
    a = len(out) - 1
    assert draft[:a] == greedy[:a]
    if a < len(draft):
        assert draft[a] != greedy[a]

