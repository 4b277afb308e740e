"""Swallow §III + §X-B composed: the paged-KV continuous-batching engine.

What is reproduced: the farmer-worker loop (§III, C3) running against a
striped memory server (§X-B) — the device-side half of the serving
subsystem.  Per-layer KV pools (``lm.init_paged_caches``) are the
striped store, the block-table matrix is the address map, and the jitted
steps (``make_paged_serve_step`` / ``make_paged_serve_scan``) decode
every occupied slot of the batch while :mod:`repro.serving.scheduler`
refills freed slots with priced prefills.

Device-resident decode (the paper's C/C lesson applied to the
host↔device "interconnect"): slot state — tokens, positions, block
tables — lives in device arrays; the host keeps a numpy *mirror* that is
pushed only when scheduler bookkeeping dirties it (admission, growth,
preemption, completion), and results are pulled once per fused window,
not once per token.  ``h2d_syncs`` / ``d2h_syncs`` count those events:
per-step mode is O(1 per token), fused mode O(1 per window) — the same
per-message-overhead argument Swallow §V makes for its interconnect.

Fused windows decode K tokens in one ``lax.scan`` dispatch; K is the
scheduler's ``safe_horizon`` (no completion, page-boundary crossing
without a pre-reserved page, or pending priced admission inside the
window), bucketed to powers of two so at most log2(max_window)+1 scan
shapes ever compile.  ``fused=False`` keeps the original per-step
semantics as the K=1 fallback.

Prefix sharing (``prefix_cache=True``): admissions are matched against
:mod:`repro.serving.prefix_cache` — the radix tree over token IDs whose
nodes own ref-counted pages on the striped store.  A hit skips prefill
for the cached prefix (the block row simply points at the shared pages —
the paged attention gather needs no kernel change), COW-copies the
divergence page on device when the match ends mid-page, and prefills
only the uncached suffix through the teacher-forced decode scan.  Greedy
tokens are bit-identical with the cache on or off (pinned by
tests/test_prefix_cache.py) — sharing moves KV entries, never changes
them.

Speculative decoding (``spec_decode=True``): each running sequence
drafts up to K tokens from its own prompt+output history
(:mod:`repro.serving.spec_decode`, weightless n-gram lookup) and a
single ``verify_window_paged`` dispatch scores all K+1 positions against
the paged KV — the accepted prefix plus the verifier's bonus token land
from ONE model pass, cutting *model dispatches per emitted token* below
1.0 (the ``dispatches_per_token`` observable).  Drafting itself runs on
device by default (``spec_proposer="device"``): each slot's token
history is a device-resident row appended by the fused
draft+verify+accept dispatch chain (``make_spec_draft_verify``), so a
steady-state speculation window moves no draft bytes over the
host↔device link at all — the payload-per-message lesson applied to the
drafting path, which is what turns PR 5's dispatch-count win into a
wall-clock win.  K adapts per request from an acceptance EWMA
(``spec_k="auto"``) and a priced gate (:meth:`PagedEngine._spec_gate`,
on :func:`repro.core.costs.estimate` numbers) buys a verify only where
it beats the scan it displaces.  Speculation is capped by the
scheduler's ``safe_horizon`` (no scheduling event inside the window),
rejected KV is appended then rolled back (``PageAllocator.truncate_to``
releases whole rejected pages; partial slots are masked by position),
and slots the gate prices out ride the normal fused window — so greedy
tokens stay bit-identical with speculation on or off
(tests/test_spec_decode.py, tests/test_serving_fuzz.py).

Chunked prefill (``chunked_prefill=True``): admission no longer pays a
prompt's whole prefill up front.  Admitted requests sit in the
scheduler's ``prefilling`` state (pages fully allocated, slot held, no
device mirror entry) and each engine step dispatches a budgeted round of
page-aligned chunk slices (``make_chunk_prefill`` — the suffix-prefill
body at successive offsets) *before* the decode window, so a 10k-token
prompt costs each decoding tenant at most the SLO-priced chunk budget
per window instead of a full stall.  Only the final chunk's logits are
pulled (the first token); composing chunks writes bit-identical KV to
one monolithic dispatch, so greedy tokens match with chunking on or off
(tests/test_serving_fuzz.py's 32-config cube).

Greedy decoding throughout: fused vs per-step vs dense token equality is
an acceptance gate (tests/test_serving.py), and it is also what makes
recompute-preemption exact.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, List, Optional

import numpy as np

from repro.core.memory_server import stripe_slab_index
from repro.serving.paged_kv import NULL_PAGE, PageAllocator
from repro.serving.scheduler import ContinuousBatchScheduler, Request
from repro.serving.spec_decode import NGramSpec, SpecStats
from repro.serving.telemetry import MetricsRegistry, StepTracer, counter_attr


@functools.lru_cache(maxsize=8)
def _jitted_steps(cfg, mesh=None):
    """One set of jitted step functions per (hashable, frozen) config —
    engines constructed with the same config share compile caches
    instead of re-tracing per instance (a large win for the test suite
    and for on/off A-B benchmark runs).  Donation is per-call, so
    sharing the jitted callables across engines is safe: each engine
    donates its own pools.  Bounded so a long-lived process sweeping
    many configs does not retain compiled executables forever.
    ``mesh`` (hashable) keys the cache too: the same config traced with
    striped pools compiles different (shard_map) programs than the
    single-device engine, and the two must never share executables."""
    import jax
    from repro import steps as steps_mod
    return {
        "prefill": jax.jit(steps_mod.make_paged_prefill_step(cfg),
                           donate_argnums=(2,)),
        "serve": jax.jit(steps_mod.make_paged_serve_step(cfg),
                         donate_argnums=(2,)),
        "scan": jax.jit(steps_mod.make_paged_serve_scan(cfg),
                        static_argnames=("k",), donate_argnums=(2,)),
        "suffix": jax.jit(steps_mod.make_paged_suffix_prefill(cfg),
                          donate_argnums=(2,)),
        "chunk": jax.jit(steps_mod.make_chunk_prefill(cfg),
                         donate_argnums=(2,)),
        "verify": jax.jit(steps_mod.make_verify_window(cfg),
                          donate_argnums=(2,)),
        "spec": jax.jit(steps_mod.make_spec_draft_verify(cfg),
                        static_argnames=("W", "max_n", "min_n"),
                        donate_argnums=(1, 2)),
        "copy_page": jax.jit(steps_mod.make_page_copy(),
                             donate_argnums=(0,)),
    }


class PagedEngine:
    """Paged-KV serving engine over one model + one device mesh.

    ``max_len`` bounds prompt+gen per sequence; the block table has
    ``ceil(max_len / page_size)`` entries per slot.  ``n_pages`` includes
    the reserved null page.  ``fused=True`` decodes in multi-token
    windows of up to ``max_window`` steps per dispatch; ``fused=False``
    is the per-step fallback with identical tokens.

    Every phase of :meth:`step` goes through :meth:`_span`, always: an
    ``engine.<phase>`` annotation on the profiler's clock and the
    registry counters ``engine.<phase>_s`` / ``_n``.  ``trace=True``
    also arms the :class:`~repro.serving.telemetry.StepTracer` flight
    recorder (request-lifecycle + dispatch spans, Chrome-trace export);
    tracing never feeds back into scheduling, so tokens are
    bit-identical on or off.
    """

    # every engine counter is one registry slot exposed as an attribute
    # (same external names, one implementation — see serving/telemetry.py)
    steps_run = counter_attr()
    windows_run = counter_attr()
    decode_steps = counter_attr()
    decode_tokens = counter_attr()
    tokens_emitted = counter_attr()
    decode_time_s = counter_attr()
    spec_time_s = counter_attr()       # draft+verify subset of decode_time_s
    step_calls = counter_attr()        # calls of step()
    host_pass_s = counter_attr()       # step() time less its pulls
    h2d_syncs = counter_attr()
    d2h_syncs = counter_attr()
    block_row_writes = counter_attr()
    peak_pages = counter_attr()
    prefill_tokens = counter_attr()    # prompt tokens actually computed
    chunk_dispatches = counter_attr()  # chunked-prefill model dispatches
    # sequential model executions (a fused K-scan counts K): the
    # denominator-side of dispatches_per_token, the observable
    # speculative decoding attacks
    model_passes = counter_attr()
    # fault-plane counters (repro.serving.faults)
    node_failures = counter_attr()
    node_joins = counter_attr()
    pages_quarantined_total = counter_attr()
    requests_recovered = counter_attr()
    tokens_recomputed = counter_attr()  # emitted tokens discarded by resets
    quarantined_served = counter_attr()  # MUST stay 0: stale-read guard hits
    # routed-expert layers, from the fused decode windows' own counts
    # (pulled with their tokens): tokens through expert layers, one per
    # occupied slot, step and layer, and their (token, expert) pairs
    # that landed on the experts this device holds
    moe_tokens = counter_attr()
    moe_pairs_held = counter_attr()

    def __init__(self, cfg, params, *, max_batch: int = 4,
                 page_size: int = 16, n_pages: int = 64,
                 max_len: int = 256, n_nodes: int = 1,
                 link_mode: str = "circuit", prefill_budget: float = 2.0,
                 fused: bool = True, max_window: int = 8,
                 prefix_cache: bool = False, spec_decode: bool = False,
                 spec_k=8, spec_ngram: int = 3,
                 spec_proposer: str = "device",
                 chunked_prefill: bool = False, chunk_tokens: int = 0,
                 fault_plan=None, trace: bool = False,
                 trace_capacity: int = 4096, mesh=None):
        import jax
        import jax.numpy as jnp
        from repro.models import lm

        assert lm.paged_decodable(cfg), \
            f"{cfg.name} is not paged-decodable (global or latent " \
            "attention layers only, causal)"
        assert spec_proposer in ("device", "host")
        # mesh: a jax Mesh whose "model" axis stripes the page pools
        # (page p lives on node p % M via core/memory_server
        # .stripe_slab_index — the host allocator's striped_owner
        # accounting and the device placement agree by construction).
        # The "data" axis, when present, just replicates engine work.
        self.mesh = mesh
        self._stripe = 1
        if mesh is not None and "model" in getattr(mesh, "axis_names", ()):
            self._stripe = int(mesh.shape["model"])
        if self._stripe > 1:
            if n_nodes == 1:
                n_nodes = self._stripe
            elif n_nodes != self._stripe:
                raise ValueError(
                    f"n_nodes={n_nodes} disagrees with the mesh's model "
                    f"degree {self._stripe}: the host allocator's stripe "
                    "and the device stripe must be the same partition")
            if "mla" in cfg.layer_kinds:
                raise NotImplementedError(
                    "latent (mla) pools are not striped over a mesh: "
                    "the striped decode merges GQA partials only; serve "
                    f"{cfg.name} on one device")
            if n_pages % self._stripe:
                raise ValueError(
                    f"n_pages={n_pages} not divisible by the stripe "
                    f"degree {self._stripe}: every node must own an "
                    "equal contiguous slab shard")
        # the registry must exist before any counter_attr assignment below
        self.registry = MetricsRegistry()
        self.tracer = StepTracer(capacity=trace_capacity) if trace else None
        self.cfg = cfg
        self.params = params
        self.page_size = page_size
        self.max_batch = max_batch
        self.max_len = max_len
        self.nmax = -(-max_len // page_size)
        self.fused = fused
        self.max_window = max(1, int(max_window))
        self.spec = None
        self._spec_host = spec_proposer == "host"
        if spec_decode:
            # "auto": adapt K per request from its acceptance EWMA, with
            # headroom to draft past max_window (a deep verify is still
            # ONE dispatch — depth is nearly free when acceptance earns it)
            if spec_k == "auto":
                self.spec = NGramSpec(
                    k=max(2 * self.max_window - 1, 3), max_n=spec_ngram,
                    adaptive=True)
            else:
                self.spec = NGramSpec(k=int(spec_k), max_n=spec_ngram)
        self._jnp = jnp
        self._annotation = jax.profiler.TraceAnnotation

        self.alloc = PageAllocator(n_pages=n_pages, page_size=page_size,
                                   n_nodes=n_nodes, registry=self.registry)
        self.cache = None
        if prefix_cache:
            from repro.serving.prefix_cache import PrefixCache
            self.cache = PrefixCache(self.alloc, registry=self.registry)
            # under pool pressure, LRU-evict cold cache pages before the
            # scheduler resorts to preempting tenants
            self.alloc.reclaim = self.cache.evict
        self.link_mode = link_mode
        self.n_nodes = n_nodes
        from repro.configs.base import ShapeConfig
        self.decode_estimate = self._estimate(
            ShapeConfig("serve_decode", max_len, max_batch, "decode"),
            link_mode, n_nodes)
        self.sched = ContinuousBatchScheduler(
            self.alloc, max_batch,
            prefill_cost_s=self._prefill_cost(link_mode, n_nodes),
            decode_cost_s=self.decode_estimate.step_time_s,
            prefill_budget=prefill_budget,
            prefix_cache=self.cache,
            chunked=chunked_prefill, chunk_tokens=chunk_tokens,
            registry=self.registry, tracer=self.tracer)

        self.pools = lm.init_paged_caches(cfg, n_pages=n_pages,
                                          page_size=page_size)
        if self._stripe > 1:
            # place each pool leaf's page axis (third-from-last) over the
            # mesh: node d holds slab rows [d*P/M, (d+1)*P/M) — exactly
            # the pages stripe_slab_index maps to it.  Parameters are
            # replicated on every device once here, not re-sent from the
            # first device on every dispatch
            from repro.parallel.sharding import SERVING_RULES, use_sharding
            with use_sharding(mesh, SERVING_RULES) as env:
                self.params = jax.device_put(params, env.sharding())
                self.pools = jax.device_put(
                    self.pools,
                    jax.tree.map(
                        lambda a: env.sharding(
                            *(((None,) * (a.ndim - 3))
                              + ("pages", None, None))),
                        self.pools))
        steps = _jitted_steps(cfg, mesh if self._stripe > 1 else None)
        self._prefill = steps["prefill"]
        self._serve = steps["serve"]
        self._scan = steps["scan"]
        self._suffix = steps["suffix"]
        self._chunk = steps["chunk"]
        self._verify = steps["verify"]
        self._spec_step = steps["spec"]
        self._copy_page = steps["copy_page"]
        # pool bytes one token occupies across the whole stack (k + v of
        # a global-attention layer, a latent row of a latent one) — the
        # unit behind the bytes_deduped gauge
        self.kv_bytes_per_token = lm.paged_bytes_per_token(cfg)
        # host MIRROR of slot state; the device copies are authoritative
        # between window boundaries
        self.block_tables = np.full((max_batch, self.nmax), NULL_PAGE,
                                    np.int32)
        self.tokens = np.zeros((max_batch, 1), np.int32)
        self.pos = np.zeros((max_batch,), np.int32)
        self.active = np.zeros((max_batch,), np.int32)
        # device-resident slot state (synced from the mirror on demand)
        self.d_tokens = jnp.asarray(self.tokens)
        self.d_pos = jnp.asarray(self.pos)
        self.d_block = jnp.asarray(self.block_tables)
        self.d_active = jnp.asarray(self.active)
        self._dirty = False
        self._dirty_block = False
        # dirty-tracking signature per slot: (rid, preemptions, n_pages)
        self._slot_sig: List[Optional[tuple]] = [None] * max_batch
        # device-resident token histories for speculative drafting: row s
        # holds slot s's prompt+output tokens (the device proposer's
        # input AND output — accepted emissions are appended on device,
        # so steady-state speculation pushes no history at all).
        # _hist_state[s] = ((rid, preemptions), device-valid length):
        # the dirty-tracking key that decides when a row must be pushed
        self.d_hist = jnp.zeros((max_batch, max_len), jnp.int32) \
            if self.spec is not None else None
        self._hist_state: List[Optional[tuple]] = [None] * max_batch
        self._n_submitted = 0
        # seed every registry counter key (descriptors write through);
        # zeroing here keeps the snapshot schema complete from step 0
        self.steps_run = 0
        self.windows_run = 0
        self.decode_steps = 0
        self.decode_tokens = 0
        self.tokens_emitted = 0
        self.decode_time_s = 0.0
        self.spec_time_s = 0.0
        self.step_calls = 0
        self.host_pass_s = 0.0
        self.h2d_syncs = 0
        self.d2h_syncs = 0
        self.block_row_writes = 0
        self.peak_pages = 0
        self.prefill_tokens = 0
        self.chunk_dispatches = 0
        self.model_passes = 0
        self.node_failures = 0
        self.node_joins = 0
        self.pages_quarantined_total = 0
        self.requests_recovered = 0
        self.tokens_recomputed = 0
        self.quarantined_served = 0
        self.moe_tokens = 0
        self.moe_pairs_held = 0
        # dispatch-span attribution: (predicted seconds, predicted §VI
        # joules across the fleet) per prefill-shaped width, memoized —
        # the cost engine prices each width once
        self._pred_cache: Dict[int, tuple] = {}
        # predicted stripe-interconnect cost per (tokens, merges) shape
        self._comms_cache: Dict[tuple, tuple] = {}
        self.faults = None
        if fault_plan is not None:
            self.install_faults(fault_plan)
        self.t0 = time.time()

    def install_faults(self, plan) -> None:
        """Attach a :class:`repro.serving.faults.FaultPlan`; its step-0
        is the *current* scheduler step, so install after warmup and
        ``reset_metrics`` to keep warmup traffic out of the chaos
        window."""
        from repro.serving.faults import FaultPlane
        self.faults = FaultPlane(plan, self.n_nodes,
                                 epoch=self.sched.step_idx,
                                 registry=self.registry)
        self.sched.transient_gate = self.faults.transient_gate

    def reset_metrics(self):
        """Zero every counter/clock/digest (e.g. after a warmup pass)
        while keeping the compiled steps, pools and allocator state.
        One registry reset covers the engine, scheduler, allocator
        gauges, prefix-cache and fault-plane counters AND the streaming
        histogram digests — warmup traffic must not survive into
        chaos/SLO percentiles.  The prefix-cache *tree* is kept (call
        ``cache.clear()`` to start cold); its counters restart.  The
        tracer ring restarts too, so an exported trace begins at the
        post-warmup epoch."""
        self.registry.reset()
        self.sched.finished.clear()
        self._n_submitted = 0
        self.sched.shed.clear()
        self.sched.recovery_steps.clear()
        if self.spec is not None:
            self.spec.stats = SpecStats()
        if self.tracer is not None:
            self.tracer.reset()
        self.t0 = time.time()

    # -- cost-engine pricing (the scheduler's admission inputs) ------------
    def _estimate(self, shape, link_mode, n_nodes):
        from repro.core import costs
        return costs.estimate(self.cfg, costs.Layout(data=1, model=n_nodes),
                              link_mode, shape)

    def _prefill_cost(self, link_mode, n_nodes):
        from repro.configs.base import ShapeConfig

        def cost(prompt_len: int) -> float:
            shape = ShapeConfig("serve_prefill", max(prompt_len, 1), 1,
                                "prefill")
            return self._estimate(shape, link_mode, n_nodes).step_time_s
        return cost

    # -- predicted-vs-measured attribution (telemetry spans) ---------------
    def _serving_comms(self, n_tokens: int, n_merges: int) -> tuple:
        """(predicted seconds, predicted wire bytes/device) of the stripe
        interconnect traffic one dispatch implies — the §V link model on
        the (M-1)/M remote fraction of ``n_tokens`` KV writes plus
        ``n_merges`` per-layer decode-partials merges.  (0, 0) on a
        single stripe.  Memoized per shape."""
        if self._stripe <= 1:
            return (0.0, 0.0)
        key = (int(n_tokens), int(n_merges))
        hit = self._comms_cache.get(key)
        if hit is None:
            from repro.core import costs
            hit = self._comms_cache[key] = costs.serving_comm_cost(
                self.cfg, costs.Layout(data=1, model=self._stripe),
                self.link_mode, n_tokens=key[0], n_merges=key[1])
        return hit

    def _predict_prefill(self, n_tokens: int) -> tuple:
        """(predicted seconds, predicted joules[, comms seconds, comms
        bytes]) for one prefill-shaped dispatch of ``n_tokens`` — prices
        prefill, suffix prefill, chunk slices and spec verify widths.
        Memoized per width; the comms tail appears only under a stripe."""
        n = max(int(n_tokens), 1)
        hit = self._pred_cache.get(n)
        if hit is None:
            from repro.configs.base import ShapeConfig
            est = self._estimate(
                ShapeConfig("serve_prefill", n, 1, "prefill"),
                self.link_mode, self.n_nodes)
            hit = (est.step_time_s, est.energy.total_j * self.n_nodes)
            if self._stripe > 1:
                hit = hit + self._serving_comms(n, 0)
            self._pred_cache[n] = hit
        return hit

    def _predict_scan(self, k: int) -> tuple:
        """(seconds, joules[, comms seconds, comms bytes]) for a fused
        K-step decode window — K times the admission-priced decode step
        (each step writes one KV entry per slot and merges the stripes'
        decode partials once)."""
        base = (k * self.sched.decode_cost_s,
                k * self.decode_estimate.energy.total_j * self.n_nodes)
        if self._stripe > 1:
            cs, cb = self._serving_comms(self.max_batch, 1)
            base = base + (k * cs, k * cb)
        return base

    def _predict_cow(self) -> tuple:
        """(seconds, joules) for one device page copy: read + write one
        page of KV through HBM (the §VI traffic term; no FLOPs)."""
        from repro.core.energy import step_energy
        from repro.launch.mesh import HBM_BW
        nbytes = 2 * self.page_size * self.kv_bytes_per_token
        secs = nbytes / HBM_BW
        return secs, step_energy(flops_per_chip=0.0,
                                 hbm_bytes_per_chip=nbytes,
                                 ici_bytes_per_chip=0.0,
                                 step_seconds=secs).total_j

    @contextlib.contextmanager
    def _span(self, phase: str, predfn=None, *,
              program: Optional[str] = None, since: Optional[float] = None,
              **extra):
        """The engine's one span hook.  Always: a ``jax.profiler``
        annotation ``engine.<phase>`` (recorded only while a profile is
        taken, on the device trace's clock) and the registry counters
        ``engine.<phase>_s`` (``perf_counter`` seconds) and
        ``engine.<phase>_n``.  Phases never nest, so the ``_s`` counters
        sum to at most the wall time of :meth:`step`.

        A phase is what the host does; the tracer's dispatch spans are
        the step programs, each from its dispatch until its result is in
        hand.  So with tracing on, a span given ``predfn`` also records
        the :class:`StepTracer` dispatch span of ``program`` (default:
        the phase, as for ``cow_copy``), measured from ``since``
        (default: the span's start; a ``pull`` names the program whose
        result it waits for and when that was dispatched) and stamped
        with the current step and the cost engine's (seconds, joules)
        prediction; ``predfn`` is never called otherwise."""
        step = self.sched.step_idx
        t0 = time.perf_counter()
        try:
            with self._annotation("engine." + phase):
                yield
        finally:
            t1 = time.perf_counter()
            self.registry.inc(f"engine.{phase}_s", t1 - t0)
            self.registry.inc(f"engine.{phase}_n")
            if self.tracer is not None and predfn is not None:
                vals = predfn()
                if len(vals) >= 4:
                    # striped engine: the predicted interconnect share
                    # rides the span so rollup_dispatch_events can
                    # attribute it per phase
                    extra = dict(extra, predicted_comms_s=vals[2],
                                 comms_bytes=vals[3])
                self.tracer.record_dispatch(
                    program or phase, step, t0 if since is None else since,
                    t1, predicted_s=vals[0], predicted_j=vals[1], **extra)

    def _flight_dump(self, reason: str) -> Optional[str]:
        """Invariant-violation post-mortem: dump the flight recorder's
        last N spans + a registry snapshot before the caller raises.
        No tracer armed -> no dump (never mask the original error)."""
        if self.tracer is None:
            return None
        try:
            path = self.tracer.flight_dump(reason, registry=self.registry)
        except OSError:
            return None
        print(f"[flight-recorder] dumped last {len(self.tracer.spans)} "
              f"spans to {path}")
        return path

    # -- request intake ----------------------------------------------------
    def submit(self, prompt, gen: int, *, tenant: str = "default",
               rid: Optional[str] = None, slo: str = "standard") -> Request:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.shape[0] == 0:
            # a request must carry at least one token: prefill needs a
            # position to produce the first logit, and the allocator's
            # pages_for(0) == 0 means an empty prompt would occupy a
            # scheduler slot while owning no pages at all
            raise ValueError(
                "empty prompt: zero-length requests are rejected at "
                "submit (a prompt needs >= 1 token to prefill a first "
                "logit)")
        assert prompt.shape[0] + gen <= self.max_len
        rid = rid or f"r{self._n_submitted}"
        self._n_submitted += 1
        key = tuple(int(t) for t in prompt) if self.cache is not None \
            else None
        req = Request(rid=rid, prompt_len=int(prompt.shape[0]), gen=gen,
                      tenant=tenant, prompt=prompt, prompt_key=key, slo=slo)
        self.sched.submit(req)
        return req

    def score(self, prompt, forced) -> np.ndarray:
        """Teacher-forced logits of one sequence through the engine's own
        paged steps — the logits-level oracle hook.  Row 0 is the
        prefill's next-token logits; row i+1 the decode step fed
        ``forced[i]``.  Returns float32 (1 + len(forced), V).  Borrows
        slot 0 and fresh pages, so call it with no request in flight."""
        if self.sched.waiting or self.sched.running or self.sched.prefilling:
            raise RuntimeError("score needs an idle engine")
        jnp = self._jnp
        prompt = np.asarray(prompt, np.int32)
        n = prompt.shape[0] + len(forced)
        if n > self.max_len:
            raise ValueError(f"score: {n} tokens exceed max_len "
                             f"{self.max_len}")
        rid = "__score__"
        if self.alloc.alloc(rid, self.alloc.pages_for(n)) is None:
            raise RuntimeError("score: no free pages for the sequence")
        table = np.full((self.max_batch, self.nmax), NULL_PAGE, np.int32)
        table[0] = self._block_row(rid)
        out = []
        try:
            with self._use_env():
                logits, self.pools = self._prefill(
                    self.params, jnp.asarray(prompt[None]), self.pools,
                    jnp.asarray(self._phys(table[0])))
                out.append(logits[0, 0])
                d_table = jnp.asarray(self._phys(table))
                for i, t in enumerate(forced):
                    # fresh host arrays each step: on the CPU backend
                    # jnp.asarray may alias them while the step runs
                    tokens = np.zeros((self.max_batch, 1), np.int32)
                    pos = np.zeros((self.max_batch,), np.int32)
                    tokens[0, 0] = t
                    pos[0] = prompt.shape[0] + i
                    _, logits, self.pools = self._serve(
                        self.params, jnp.asarray(tokens), self.pools,
                        d_table, jnp.asarray(pos))
                    out.append(logits[0, 0])
            return np.stack([np.asarray(o, np.float32) for o in out])
        finally:
            self.alloc.free(rid)
            self._dirty = True        # slot state was borrowed

    # -- node failure / re-join (the fault plane's engine half) ------------
    def fail_node(self, node: int) -> set:
        """A stripe of the §X-B DSM went dark: quarantine its pages,
        invalidate the prefix-cache subtrees that lived on them, reset
        every RUNNING/PREFILLING request whose block table touches them
        (exact greedy recompute through whatever cache survived), and
        shed requests the shrunken pool can never fit again.  Idempotent
        per down node.  Called by the :mod:`repro.serving.faults`
        watchdog; callable directly by tests and operators."""
        quar = self.alloc.fail_node(node)
        if not quar:
            return quar
        self.node_failures += 1
        self.pages_quarantined_total += len(quar)
        if self.cache is not None:
            # tree-wide: a lost interior page strands its whole subtree
            self.cache.invalidate_pages(quar)
        victims = [r for r in (list(self.sched.running.values())
                               + list(self.sched.prefilling.values()))
                   if not quar.isdisjoint(self.alloc.held.get(r.rid, ()))]
        for req in victims:
            self.tokens_recomputed += len(req.tokens)
            self.sched.fault_reset(req)
        self.requests_recovered += len(victims)
        self.sched.shed_infeasible(self.alloc.allocatable_pages)
        self._assert_no_quarantined()
        return quar

    def join_node(self, node: int) -> int:
        """Elastic re-join: the node's quarantined pages return to the
        striped free lists.  Returns how many pages rejoined."""
        was_down = node in self.alloc.failed_nodes
        restored = self.alloc.restore_node(node)
        if was_down:
            self.node_joins += 1
        return restored

    def _assert_no_quarantined(self) -> None:
        """The never-re-served invariant: after recovery, no live block
        table references a quarantined page."""
        quar = self.alloc.quarantined
        if not quar:
            return
        for req in (list(self.sched.running.values())
                    + list(self.sched.prefilling.values())):
            bad = quar.intersection(self.alloc.held.get(req.rid, ()))
            if bad:
                self.quarantined_served += 1
                self._flight_dump("quarantined-served")
                raise RuntimeError(
                    f"request {req.rid} still references quarantined "
                    f"pages {sorted(bad)} after recovery")

    # -- stripe boundary (logical pages -> physical slab rows) -------------
    def _phys(self, pages):
        """Translate logical page ids to physical slab rows at the device
        boundary.  The host side — allocator, scheduler, prefix cache,
        fault plane — reasons entirely in logical ids (``striped_owner``
        accounting); arrays crossing to the device carry slab rows so the
        NamedSharding over the page axis places every page on its owner
        node.  Identity on a single stripe, and NULL_PAGE (0) maps to
        row 0 on node 0 always — no special case anywhere."""
        if self._stripe == 1:
            return pages
        return stripe_slab_index(np.asarray(pages), self._stripe,
                                 self.alloc.n_pages)

    def _use_env(self):
        """The sharding context every device dispatch runs under: the
        engine's mesh when the pools are striped (so the traced steps see
        the "pages" rule and take the shard_map decode path), else a
        no-op."""
        if self._stripe > 1:
            from repro.parallel.sharding import SERVING_RULES, use_sharding
            return use_sharding(self.mesh, SERVING_RULES)
        return contextlib.nullcontext()

    # -- host mirror maintenance -------------------------------------------
    def _block_row(self, rid: str) -> np.ndarray:
        row = np.full((self.nmax,), NULL_PAGE, np.int32)
        pages = self.alloc.held[rid]
        if self.alloc.quarantined \
                and not self.alloc.quarantined.isdisjoint(pages):
            # a quarantined page about to be served is a recovery bug,
            # never a runtime condition: fail fast, count the hit
            self.quarantined_served += 1
            bad = sorted(self.alloc.quarantined.intersection(pages))
            self._flight_dump("stale-block-row")
            raise RuntimeError(
                f"block row for {rid} references quarantined pages {bad}")
        row[:len(pages)] = pages
        return row

    def _sig(self, req: Request) -> tuple:
        return (req.rid, req.preemptions, len(self.alloc.held[req.rid]))

    def _clear_slot(self, slot: int):
        self.block_tables[slot] = NULL_PAGE
        self.tokens[slot] = 0
        self.pos[slot] = 0
        self.active[slot] = 0
        self._slot_sig[slot] = None
        self._hist_state[slot] = None
        self._dirty = True
        self._dirty_block = True

    def _occupy_slot(self, req: Request, row: np.ndarray, token: int):
        self.block_tables[req.slot] = row
        self.tokens[req.slot] = token
        self.pos[req.slot] = req.pos
        self.active[req.slot] = 1
        self._slot_sig[req.slot] = self._sig(req)
        self.block_row_writes += 1
        self._dirty = True
        self._dirty_block = True

    def _refresh_slots(self):
        """Re-sync the mirror with scheduler state, rewriting only block
        rows whose page set changed (admission/growth/preemption) —
        dirty-tracked, not rebuilt per slot per step."""
        for slot, req in self.sched.running.items():
            sig = self._sig(req)
            if self._slot_sig[slot] != sig:
                self.block_tables[slot] = self._block_row(req.rid)
                self._slot_sig[slot] = sig
                self.block_row_writes += 1
                self._dirty = True
                self._dirty_block = True
            last = req.tokens[-1] if req.tokens else 0
            if self.tokens[slot, 0] != last:
                self.tokens[slot, 0] = last
                self._dirty = True
            if self.pos[slot] != req.pos:
                self.pos[slot] = req.pos
                self._dirty = True
            if not self.active[slot]:
                self.active[slot] = 1
                self._dirty = True

    def _push(self, force: bool = False):
        """One host->device sync event covering the whole slot-state
        bundle (tokens, positions, block tables, active mask)."""
        if not (self._dirty or force):
            return
        jnp = self._jnp
        self.d_tokens = jnp.asarray(self.tokens)
        self.d_pos = jnp.asarray(self.pos)
        self.d_block = jnp.asarray(self._phys(self.block_tables))
        self.d_active = jnp.asarray(self.active)
        self.h2d_syncs += 1
        self._dirty = False
        self._dirty_block = False

    def _push_block(self):
        """Push only the block tables (the one array a pure-verify
        window reads) — the scan bundle can stay dirty host-side, so
        steady-state speculation syncs nothing but page growth."""
        if not self._dirty_block:
            return
        self.d_block = self._jnp.asarray(self._phys(self.block_tables))
        self.h2d_syncs += 1
        self._dirty_block = False

    def _sync_hist(self, slot: int, req: Request):
        """Ensure the slot's device history row covers the request's
        current ``pos + 1`` tokens (prompt + emitted so far; ``pos`` is
        the next KV write position, so the last emitted token is
        history's tail).  The fused draft+verify appends emissions on
        device, so in steady state this is a no-op; a push happens only
        when the row is behind — slot reuse, preemption/recompute, or a
        scan window having advanced the request host-side."""
        need = req.pos + 1
        key = (req.rid, req.preemptions)
        st = self._hist_state[slot]
        if st is not None and st[0] == key and st[1] >= need:
            return
        row = np.zeros((self.max_len,), np.int32)
        hist = list(int(t) for t in req.prompt) + \
            [int(t) for t in req.tokens]
        row[:len(hist)] = hist
        self.d_hist = self.d_hist.at[slot].set(self._jnp.asarray(row))
        self.h2d_syncs += 1
        self._hist_state[slot] = (key, len(hist))

    # -- fused-window warmup ----------------------------------------------
    def window_sizes(self) -> List[int]:
        """The power-of-two window buckets this engine will dispatch."""
        if not self.fused:
            return [1]
        sizes, k = [], 1
        while k <= self.max_window:
            sizes.append(k)
            k *= 2
        return sizes

    def warmup_prefix(self, prompt_len: int, shared_prefix: int,
                      seed: int = 424242):
        """Precompile the cache-hit path for prompts of this shape: one
        miss (full prefill) followed by one hit sharing ``shared_prefix``
        tokens, which dispatches the COW page copy and the pow2 suffix
        bucket ``_do_prefill`` will pick for ``prompt_len - match``
        uncached tokens.  Identical prompts (prefix covers everything)
        exercise the capped match's 1-token bucket.  Call before
        ``reset_metrics``/``cache.clear()`` — both warm requests run to
        completion and their pages/stats are the caller's to reset."""
        if self.cache is None or shared_prefix <= 0:
            return
        sp = min(shared_prefix, prompt_len)
        gen = max(1, min(2, self.max_len - prompt_len))
        rng = np.random.default_rng(seed)
        base = rng.integers(2, self.cfg.vocab_size, prompt_len,
                            dtype=np.int32)
        self._n_warm = getattr(self, "_n_warm", 0) + 1
        self.submit(base, gen, rid=f"warmsfx{self._n_warm}a")
        self.run()
        variant = base.copy()
        if sp < prompt_len:
            variant[sp:] = rng.integers(2, self.cfg.vocab_size,
                                        prompt_len - sp, dtype=np.int32)
        self.submit(variant, gen, rid=f"warmsfx{self._n_warm}b")
        self.run()

    def verify_buckets(self) -> List[int]:
        """The pow2 verify widths speculation will dispatch — derived
        from the same ``_pow2_ceil`` rule the runtime uses for drafts of
        1..spec_k tokens plus the last emitted token, so warmup can
        never compile a different width set than the decode loop
        requests."""
        if self.spec is None:
            return []
        return sorted({self._pow2_ceil(m + 1)
                       for m in range(1, self.spec.k + 1)})

    def warmup_windows(self):
        """Compile every scan bucket (and, with speculation on, every
        verify bucket) against inactive slots / null rows — null-page
        writes are masked by design — so trace timing is steady-state.
        Null rows need no stripe translation: pi(0) == 0."""
        with self._use_env():
            self._warmup_windows_impl()

    def _warmup_windows_impl(self):
        jnp = self._jnp
        if self.fused or self.spec is not None:
            zeros_tok = jnp.zeros((self.max_batch, 1), jnp.int32)
            zeros_pos = jnp.zeros((self.max_batch,), jnp.int32)
            null_rows = jnp.full((self.max_batch, self.nmax), NULL_PAGE,
                                 jnp.int32)
            inactive = jnp.zeros((self.max_batch,), jnp.int32)
            for k in self.window_sizes():
                toks, _, _, self.pools, *_ = self._scan(
                    self.params, zeros_tok, self.pools, null_rows,
                    zeros_pos, inactive, k=k)
                np.asarray(toks)
            self._dirty = True        # device state was clobbered
        if self.spec is not None and not self._spec_host:
            # warm the fused draft+verify chain, one compile per pow2
            # verify width, against null rows (writes masked by design);
            # the warmup clobbers d_hist rows, so mark them all stale
            null_rows = jnp.full((self.max_batch, self.nmax), NULL_PAGE,
                                 jnp.int32)
            for w in self.verify_buckets():
                emit, _, _, self.d_hist, self.pools = self._spec_step(
                    self.params, self.d_hist, self.pools, null_rows,
                    jnp.int32(0), jnp.int32(0), jnp.int32(w - 1),
                    W=w, max_n=self.spec.max_n, min_n=self.spec.min_n)
                np.asarray(emit)
            # warm the history row-set scatter _sync_hist dispatches
            self.d_hist = self.d_hist.at[0].set(
                jnp.zeros((self.max_len,), jnp.int32))
            self._hist_state = [None] * self.max_batch
        else:
            null_row = jnp.full((self.nmax,), NULL_PAGE, jnp.int32)
            for w in self.verify_buckets():
                logits, self.pools = self._verify(
                    self.params, jnp.zeros((1, w), jnp.int32), self.pools,
                    null_row, jnp.int32(0), jnp.int32(1))
                np.asarray(logits)

    # -- prefill (full, or cached-prefix COW + suffix) ---------------------
    def _cow_copy(self, req: Request):
        """On a prefix-cache hit that ends inside a page, copy that shared
        page into the request's private page on device before any write
        can touch it (both the monolithic and the chunked admission)."""
        match = req.prefix_match
        if self.cache is None or match is None or match.cow_src is None:
            return
        jnp = self._jnp
        dst = self.alloc.held[req.rid][req.cached_tokens // self.page_size]
        with self._span("cow_copy", self._predict_cow, rid=req.rid):
            self.pools = self._copy_page(
                self.pools, jnp.int32(self._phys(match.cow_src)),
                jnp.int32(self._phys(dst)))
            self.cache.stats.cow_copies += 1
            self.cache.release_cow(match)

    def _prefill_admitted(self, req: Request) -> List[Request]:
        """Monolithic admission: dispatch the prefill, pull the first
        token and seat the request.  Returns ``[req]`` when it finished
        at prefill (``gen == 1``)."""
        cached = req.cached_tokens if self.cache is not None else 0
        n = req.prompt_len - cached
        width = self._pow2_ceil(n) if cached > 0 else n
        t0 = time.perf_counter()
        with self._span("prefill"):
            row = self._block_row(req.rid)
            logits = self._do_prefill(req, row)
        tok = self._pull_first_token(
            logits, "prefill", t0, lambda: self._predict_prefill(width),
            rid=req.rid, tokens=n, cached=cached)
        with self._span("complete"):
            if self._seat(req, row, tok, self.sched.note_first_token):
                return [req]               # gen == 1: finished at prefill
        return []

    def _do_prefill(self, req: Request, row: np.ndarray):
        """Dispatch the admitted request's prefill and return its logits.
        On a prefix-cache hit the cached prefix is skipped: the block row
        already points at the shared pages (the divergence page was
        COW-copied by :meth:`_cow_copy`) and only the uncached suffix
        runs — through the teacher-forced decode scan, no kernel
        change."""
        jnp = self._jnp
        L = req.cached_tokens
        if self.cache is None or L <= 0:
            logits, self.pools = self._prefill(
                self.params, jnp.asarray(req.prompt[None]), self.pools,
                jnp.asarray(self._phys(row)))
            self.h2d_syncs += 1        # prompt + block row push
            self.model_passes += 1
            self.prefill_tokens += req.prompt_len
            return logits
        suffix = np.asarray(req.prompt[L:], np.int32)
        slen = int(suffix.shape[0])
        k = self._pow2_ceil(slen)
        padded = np.zeros((1, k), np.int32)
        padded[0, :slen] = suffix
        logits, self.pools = self._suffix(
            self.params, jnp.asarray(padded), self.pools,
            jnp.asarray(self._phys(row)), jnp.int32(L), jnp.int32(slen))
        self.h2d_syncs += 1            # suffix + block row push
        self.model_passes += 1
        self.prefill_tokens += slen
        return logits

    def _pull_first_token(self, logits, program: str, since: float,
                          predfn, **extra) -> int:
        """The blocking pull of a prefill's first greedy token: a
        ``pull`` phase like a decode window's, so ``engine.pull_s`` holds
        every device wait; with tracing on it closes ``program``'s
        dispatch span, measured from ``since``."""
        with self._span("pull", predfn, program=program, since=since,
                        **extra):
            tok = int(self._jnp.argmax(logits, -1)[0, 0])
        self.d2h_syncs += 1
        return tok

    def _seat(self, req: Request, row: np.ndarray, tok: int,
              note_first) -> bool:
        """After a prefill's first token: graft the prompt's pages into
        the prefix cache (they are immutable from this moment, decode
        writes land past them, so later arrivals share instead of
        re-prefilling), record the token through the scheduler's
        ``note_first`` and seat the request.  Returns True when it
        finished at prefill (``gen == 1``)."""
        if self.cache is not None:
            self.cache.insert(req.prompt_key, self.alloc.held[req.rid],
                              req.prompt_len)
        note_first(req, tok)
        self.tokens_emitted += 1
        if req.state == "running":
            self._occupy_slot(req, row, tok)
            return False
        return True

    # -- chunked prefill (page-aligned slices between decode windows) ------
    def _do_chunk(self, req: Request, row: np.ndarray, start: int, n: int):
        """Dispatch ONE page-aligned prefill chunk — positions ``start ..
        start+n-1`` — padded to a pow2 bucket (same rule as the suffix
        path, so a heavy-tailed length distribution compiles O(log)
        kernels), and return its logits.  Only the final chunk's are
        pulled (one d2h per request, not per chunk)."""
        jnp = self._jnp
        seg = np.asarray(req.prompt[start:start + n], np.int32)
        padded = np.zeros((1, self._pow2_ceil(n)), np.int32)
        padded[0, :n] = seg
        logits, self.pools = self._chunk(
            self.params, jnp.asarray(padded), self.pools,
            jnp.asarray(self._phys(row)), jnp.int32(start), jnp.int32(n))
        self.h2d_syncs += 1            # chunk + block row push
        self.model_passes += 1
        self.chunk_dispatches += 1
        self.prefill_tokens += n
        return logits

    def _chunk_round(self, max_window: Optional[int]) -> List[Request]:
        """One chunk round: ask the scheduler for this window's budgeted
        page-aligned slices and dispatch them before decode, so a
        request whose final chunk lands here joins the very next decode
        window."""
        k_budget = self.max_window if max_window is None \
            else max(1, min(self.max_window, max_window))
        if not self.fused:
            k_budget = 1
        with self._span("plan"):
            tasks = self.sched.plan_chunks(k_budget)
        finished: List[Request] = []
        for req, start, n in tasks:
            w = self._pow2_ceil(n)
            predict = lambda: self._predict_prefill(w)   # noqa: E731
            final = start + n == req.prompt_len
            t0 = time.perf_counter()
            # an intermediate chunk's span ends at its dispatch, the final
            # chunk's at its first token
            with self._span("chunk", None if final else predict,
                            program="chunk_prefill", rid=req.rid, tokens=n,
                            start=start):
                row = self._block_row(req.rid)
                logits = self._do_chunk(req, row, start, n)
            if not final:
                continue
            tok = self._pull_first_token(logits, "chunk_prefill", t0,
                                         predict, rid=req.rid, tokens=n,
                                         start=start)
            with self._span("complete"):
                if self._seat(req, row, tok, self.sched.finish_prefill):
                    finished.append(req)
        return finished

    # -- one engine step (a window of >= 1 scheduler steps) ----------------
    @staticmethod
    def _pow2_floor(k: int) -> int:
        # bucket to the largest power of two <= k: at most
        # log2(max_window)+1 scan shapes ever compile
        return 1 << (max(k, 1).bit_length() - 1)

    @staticmethod
    def _pow2_ceil(n: int) -> int:
        # smallest power of two >= n: the ONE bucket rule shared by the
        # suffix-prefill widths, the verify widths and verify warmup
        return 1 << max(n - 1, 0).bit_length()

    def _pick_window(self, max_window: Optional[int]) -> int:
        cap = self.max_window if max_window is None \
            else max(1, min(self.max_window, max_window))
        # quantizing inside safe_horizon keeps page reservation exact:
        # only the dispatched window's pages are grabbed ahead of need
        return self.sched.safe_horizon(cap, quantize=self._pow2_floor)

    def _spec_gate(self, active: Dict[int, Request],
                   ks: Dict[int, int], kk_est: int) -> Dict[int, int]:
        """The priced worth-it gate: a verify pass is bought only where
        the tokens it is *expected* to emit — ``e = 1 + accept_EWMA *
        K`` — beat the fused scan it displaces, on the cost engine's own
        seconds (``sched.decode_cost_s`` / ``sched.prefill_cost_s`` both
        come from :func:`repro.core.costs.estimate`).  Two regimes,
        compared in product form (no divisions):

        * pure speculation — every slot drafts, so the B verifies
          replace the scan outright: worth it iff the expected emission
          rate beats the scan's, ``sum(e) * t_scan > B * kk *
          sum(t_verify)``;
        * mixed — the scan runs anyway for the other slots, so a
          drafting slot pays its verify *on top* of the ``kk`` tokens
          the window would hand it for free, and must clear the
          marginal bar ``(e_s - kk) * t_scan > t_verify_s * B * kk``.
          Shallow drafts against a wide free window are priced out —
          the regime where PR 5's heuristic gate lost wall-clock.
        """
        if not ks:
            return ks
        price = getattr(self.sched, "prefill_cost_s", None)
        scan_s = kk_est * float(self.sched.decode_cost_s or 0.0)
        if price is None or scan_s <= 0.0:
            return dict(ks)            # unpriced scheduler: keep drafts
        n = len(active)
        e = {s: 1.0 + self.spec.rate_for(active[s].tenant) * K
             for s, K in ks.items()}
        tv = {s: float(price(self._pow2_ceil(K + 1)))
              for s, K in ks.items()}
        if len(ks) == n and sum(e.values()) * scan_s \
                > n * kk_est * sum(tv.values()):
            return dict(ks)
        return {s: K for s, K in ks.items()
                if (e[s] - kk_est) * scan_s > tv[s] * n * kk_est}

    def _spec_window(self, max_window: Optional[int]) -> List[Request]:
        """One speculative decode window: draft -> verify -> accept as a
        device-resident dispatch chain.

        Depth: each running slot asks its per-tenant controller
        (:meth:`repro.serving.spec_decode.NGramSpec.draft_k` — the
        acceptance-EWMA adaptive target, or the fixed ``spec_k``) for a
        draft depth K clamped to the scheduler's ``safe_horizon`` and
        snapped to the pow2 verify buckets; the priced gate
        (:meth:`_spec_gate`) then keeps only the verifies the cost model
        expects to beat the scan they displace.  Rejected slots ride the
        normal fused scan with speculating slots masked to null rows
        (their in-scan writes land on the null page, masked by design).

        Dispatch (``spec_proposer="device"``, the default): ONE jitted
        chain per slot — ``device_propose`` over the slot's
        device-resident history row, ``verify_window_paged`` over the
        draft, greedy acceptance, history append — with only ``(emitted,
        n_emit, m)`` pulled back; no draft ever materializes on the
        host, and steady-state windows push nothing but page growth
        (``_push_block``).  ``spec_proposer="host"`` keeps the PR-5
        reference path (host n-gram propose + padded ``_verify``): the
        middle rung of the differential oracle ladder and the hook
        adversarial tests monkeypatch.

        Speculation depth is capped by the scheduler's ``safe_horizon``
        — no scheduling event can land inside the window, and every
        write position is page-reserved up front (exact reservation, no
        pow2 quantize: a verify may write any horizon position).
        Rejected drafts roll their whole pages back via
        ``PageAllocator.truncate_to`` and forget the slot signature
        (pop-then-regrow can alias page counts).  Emitted tokens are
        bit-identical to the plain path in every mode by the acceptance
        rule (:meth:`repro.serving.spec_decode.NGramSpec.accept`)."""
        jnp = self._jnp
        finished: List[Request] = []
        with self._span("plan"):
            cap = max(self.max_window, self.spec.k + 1)
            if max_window is not None:
                cap = max(1, min(cap, max_window))
            k = self.sched.safe_horizon(cap)
            active = dict(self.sched.running)
            ks: Dict[int, int] = {}
            host_drafts: Dict[int, List[int]] = {}
            for slot, req in active.items():
                K = self.spec.draft_k(req.tenant, k)
                if K < 1:
                    continue
                if self._spec_host:
                    d = self.spec.propose(req.prompt, req.tokens, K)
                    if not d:
                        continue      # no match: ride the scan
                    host_drafts[slot] = d
                    ks[slot] = len(d)
                else:
                    ks[slot] = K      # draft length discovered on device
            kk_est = self._pow2_floor(min(k, self.max_window)) \
                if self.fused else 1
            ks = self._spec_gate(active, ks, kk_est)
            host_drafts = {s: d for s, d in host_drafts.items() if s in ks}
            scan_slots = [s for s in active if s not in ks]
        kk = kk_est
        with self._span("push"):
            self._refresh_slots()
            t_dec = time.perf_counter()
            if scan_slots and ks:
                # ONE sync event: canonical tokens/pos plus this window's
                # masked rows/mask (speculating slots write the null
                # page); the canonical d_block/d_active stay host-side —
                # the _dirty fold below re-pushes them next plain window
                bt = self.block_tables.copy()
                act = self.active.copy()
                for s in ks:
                    bt[s] = NULL_PAGE
                    act[s] = 0
                self.d_tokens = jnp.asarray(self.tokens)
                self.d_pos = jnp.asarray(self.pos)
                d_bt, d_act = jnp.asarray(self._phys(bt)), jnp.asarray(act)
                self.h2d_syncs += 1
            elif scan_slots:
                self._push(force=not self.fused)
                d_bt, d_act = self.d_block, self.d_active
            if ks and not self._spec_host:
                # device chain inputs: history rows for any slot that fell
                # behind (slot reuse / preemption / scan advance), plus
                # page growth — in steady state only the latter moves.
                # The scan neither reads them nor advances these slots
                for slot in sorted(ks):
                    self._sync_hist(slot, active[slot])
                self._push_block()
        advanced = 0          # scheduler-clock steps complete_step took
        emitted_max = 0       # largest per-slot emission this window
        tok_np = None
        if scan_slots:
            t_scan = time.perf_counter()
            with self._span("dispatch"):
                toks, d_tok, d_pos, self.pools, *counts = self._scan(
                    self.params, self.d_tokens, self.pools, d_bt,
                    self.d_pos, d_act, k=kk)
            with self._span("pull", lambda: self._predict_scan(kk),
                            program="scan", since=t_scan, k=kk,
                            slots=len(scan_slots)):
                tok_np = self._pull_window(toks, counts, kk)
            with self._span("complete"):
                self.d2h_syncs += 1
                self.decode_steps += kk
                self.model_passes += kk
                self.windows_run += 1
                for j in range(kk):
                    emitted: Dict[int, int] = {s: int(tok_np[s, j])
                                               for s in scan_slots}
                    self.decode_tokens += len(emitted)
                    self.tokens_emitted += len(emitted)
                    finished += self.sched.complete_step(emitted)
                advanced = emitted_max = kk
                if not ks:
                    # pure scan window: adopt the device carry, exactly
                    # like the plain fused path
                    self.d_tokens, self.d_pos = d_tok, d_pos
        st = self.spec.stats
        for slot in sorted(ks):
            req = active[slot]
            K = ks[slot]
            t_sp = time.perf_counter()
            if self._spec_host:
                d = host_drafts[slot]
                m = len(d)
                W = self._pow2_ceil(m + 1)
                padded = np.zeros((1, W), np.int32)
                padded[0, 0] = req.tokens[-1]
                padded[0, 1:m + 1] = d
                with self._span("dispatch"):
                    logits, self.pools = self._verify(
                        self.params, jnp.asarray(padded), self.pools,
                        jnp.asarray(self._phys(self.block_tables[slot])),
                        jnp.int32(req.pos), jnp.int32(m + 1))
                    self.h2d_syncs += 1   # draft + block row push
                with self._span("pull", lambda: self._predict_prefill(W),
                                program="draft_verify", since=t_sp,
                                rid=req.rid, k=K, width=W):
                    greedy = np.asarray(jnp.argmax(logits[0, :m + 1], -1),
                                        np.int32)
                    self.d2h_syncs += 1   # blocking verdict pull
                out = self.spec.accept(d, greedy)   # updates stats
            else:
                W = self._pow2_ceil(K + 1)
                with self._span("dispatch"):
                    (emit_d, n_emit_d, m_d, self.d_hist,
                     self.pools) = self._spec_step(
                        self.params, self.d_hist, self.pools, self.d_block,
                        jnp.int32(slot), jnp.int32(req.pos), jnp.int32(K),
                        W=W, max_n=self.spec.max_n,
                        min_n=self.spec.min_n)
                with self._span("pull", lambda: self._predict_prefill(W),
                                program="draft_verify", since=t_sp,
                                rid=req.rid, k=K, width=W):
                    emit_np = np.asarray(emit_d)   # blocking verdict pull
                    n_emit, m = int(n_emit_d), int(m_d)
                    self.d2h_syncs += 1
                out = [int(t) for t in emit_np[:n_emit]]
                st.drafted += m
                st.accepted += n_emit - 1
                st.verifies += 1
            self.spec_time_s += time.perf_counter() - t_sp
            with self._span("complete"):
                st.k_requested += K
                self.spec.observe(req.tenant, m, len(out) - 1)
                self.decode_steps += 1
                self.model_passes += 1
                self.windows_run += 1     # a verify IS a device dispatch
                self.decode_tokens += len(out)
                self.tokens_emitted += len(out)
                finished += self.sched.complete_spec(req, out)
                if req.state == "running" and len(out) <= m:
                    # rejected drafts: release their whole pages (the kept
                    # tail page's stale slots are masked by position and
                    # overwritten before the write position reaches them)
                    if self.alloc.truncate_to(req.rid, req.pos):
                        st.rollbacks += 1
                    # pop-then-regrow can restore the same page COUNT with
                    # different physical pages — invisible to the (rid,
                    # preemptions, len) signature — so forget it: the
                    # next refresh must rewrite the device block row
                    self._slot_sig[req.slot] = None
                if not self._spec_host:
                    # the fused step appended the emission on device, so
                    # the row now covers exactly pos+1 tokens again — the
                    # history holds only verified tokens, so rollback
                    # never touches it
                    self._hist_state[slot] = ((req.rid, req.preemptions),
                                              req.pos + 1)
                emitted_max = max(emitted_max, len(out))
        with self._span("complete"):
            if ks:
                # the device carry is stale for speculating slots (and the
                # scan saw masked rows): fold the mirror, re-push next
                # window
                for slot, req in self.sched.running.items():
                    self.tokens[slot, 0] = req.tokens[-1] if req.tokens \
                        else 0
                    self.pos[slot] = req.pos
                self._dirty = True
            elif tok_np is not None:
                for slot, req in self.sched.running.items():
                    self.tokens[slot, 0] = int(tok_np[slot, advanced - 1])
                    self.pos[slot] = req.pos
        self.decode_time_s += time.perf_counter() - t_dec
        # the window consumed max(scan depth, deepest verified emission)
        # scheduler-clock steps; complete_step already advanced `advanced`
        self.sched.step_idx += max(emitted_max - advanced, 0)
        self.steps_run += max(emitted_max, 1)
        # adaptive state is keyed by tenant, not rid: acceptance
        # statistics are a workload property, so a tenant's next request
        # starts at the learned depth instead of re-ramping from the
        # prior (state is bounded by the tenant count — never forgotten)
        return finished

    def _decode_window(self, max_window: Optional[int]) -> List[Request]:
        """One fused decode window (one step when ``fused=False``): push
        the dirty slot state, dispatch, pull the window's tokens (its
        one device wait) and record them."""
        with self._span("plan"):
            k = self._pick_window(max_window) if self.fused else 1
        with self._span("push"):
            self._refresh_slots()
            active = dict(self.sched.running)
            t_dec = time.perf_counter()
            self._push(force=not self.fused)
        counts = ()
        with self._span("dispatch"):
            if self.fused:
                toks, self.d_tokens, self.d_pos, self.pools, *counts = \
                    self._scan(self.params, self.d_tokens, self.pools,
                               self.d_block, self.d_pos, self.d_active, k=k)
            else:
                # legacy per-step path: push the whole bundle and pull one
                # token per scheduler step — O(1 syncs per token)
                toks, _, self.pools = self._serve(
                    self.params, self.d_tokens, self.pools, self.d_block,
                    self.d_pos)
        with self._span("pull", lambda: self._predict_scan(k),
                        program="scan", since=t_dec, k=k, slots=len(active)):
            tok_np = self._pull_window(toks, counts, k)
        self.d2h_syncs += 1
        self.decode_time_s += time.perf_counter() - t_dec
        finished: List[Request] = []
        with self._span("complete"):
            self.decode_steps += k
            self.model_passes += k
            self.windows_run += 1
            for j in range(k):
                emitted: Dict[int, int] = {s: int(tok_np[s, j])
                                           for s in active}
                self.decode_tokens += len(emitted)
                self.tokens_emitted += len(emitted)
                finished += self.sched.complete_step(emitted)
            # fold the window's results back into the mirror; slots that
            # stayed running now match the device carry exactly, so a
            # quiet boundary pushes nothing next window
            for slot, req in self.sched.running.items():
                self.tokens[slot, 0] = int(tok_np[slot, k - 1])
                self.pos[slot] = req.pos
            self.steps_run += k
        return finished

    def _pull_window(self, toks, counts, k: int) -> np.ndarray:
        """The window's tokens (max_batch, k) and, for a model with
        routed experts, its ``[moe_tokens, moe_pairs_held]`` counts
        (added to the counters), in one device-to-host transfer."""
        import jax
        tok_np, counts = jax.device_get((toks, tuple(counts)))
        for n_tok, n_held in counts:
            self.moe_tokens += int(n_tok)
            self.moe_pairs_held += int(n_held)
        return np.asarray(tok_np).reshape(self.max_batch, k)

    def step(self, max_window: Optional[int] = None) -> List[Request]:
        """Plan, prefill admissions, decode one fused window (or one
        step when ``fused=False``).  ``max_window`` additionally caps
        this window (e.g. to the next trace arrival).  Returns requests
        finished this window.  Counts the call (``step_calls``) and its
        time less its ``pull`` phases, the waits for first tokens and
        for the window's tokens (``host_pass_s``).

        Under a striped mesh the whole step runs inside the sharding
        env, so every dispatch resolves the ``pages`` axis and routes
        paged attention through the shard_map owner-partial merge."""
        t0 = time.perf_counter()
        pulled = self.registry.counter("engine.pull_s")
        with self._use_env():
            finished = self._step_impl(max_window)
        self.step_calls += 1
        self.host_pass_s += time.perf_counter() - t0 \
            - (self.registry.counter("engine.pull_s") - pulled)
        return finished

    def _clear_idle_slots(self):
        """Null the mirror rows of slots no running request holds
        (preempted, finished or never used since their last clear)."""
        for slot in range(self.max_batch):
            if slot not in self.sched.running \
                    and self._slot_sig[slot] is not None:
                self._clear_slot(slot)

    def _step_impl(self, max_window: Optional[int]) -> List[Request]:
        with self._span("plan"):
            if self.faults is not None:
                # watchdog tick BEFORE planning: detections quarantine
                # pages and reset victims, so this step's plan sees the
                # degraded pool and never dispatches against a dead stripe
                self.faults.on_step(self)
            plan = self.sched.plan_step()
            self._clear_idle_slots()
        finished: List[Request] = []
        for req in plan.admitted:
            self._cow_copy(req)
            if not self.sched.chunked:     # chunked: the chunks prefill
                finished += self._prefill_admitted(req)
        if self.sched.chunked and self.sched.prefilling:
            # budgeted chunk round BEFORE the decode window: a prompt
            # finishing its last chunk decodes in this very window, and
            # decoding tenants see at most the budget's interference
            finished += self._chunk_round(max_window)
        if self.sched.running and self.spec is not None:
            finished += self._spec_window(max_window)
        elif self.sched.running:
            finished += self._decode_window(max_window)
        else:
            self.sched.step_idx += 1
            self.steps_run += 1
        with self._span("complete"):
            self._clear_idle_slots()
            self.peak_pages = max(self.peak_pages, self.alloc.pages_in_use)
            if self.tracer is not None:
                # per-node occupancy counter track (Perfetto stacked
                # counters)
                self.tracer.counter_sample(self.sched.step_idx,
                                           self.alloc.occupancy_by_node())
        return finished

    def run(self, max_steps: int = 100_000) -> List[Request]:
        """Step until every submitted request finished."""
        while (self.sched.waiting or self.sched.running
               or self.sched.prefilling) and self.steps_run < max_steps:
            self.step()
        if self.sched.waiting or self.sched.running or self.sched.prefilling:
            raise RuntimeError(
                f"engine wedged: {len(self.sched.waiting)} waiting / "
                f"{len(self.sched.prefilling)} prefilling / "
                f"{len(self.sched.running)} running after {max_steps} steps")
        assert self.sched.conserved(self._n_submitted)
        return self.sched.finished

    # -- observability -----------------------------------------------------
    def metrics(self) -> dict:
        from repro.serving.telemetry import HistogramDigest
        fin = self.sched.finished
        dt = max(time.time() - self.t0, 1e-9)
        ttft_d = HistogramDigest.of(
            r.first_token_step - r.arrived_step for r in fin
            if r.first_token_step is not None)
        emitted = self.tokens_emitted
        out = {
            "finished": len(fin),
            "wall_s": dt,
            "decode_s": self.decode_time_s,
            # emitted counts every token produced (prefill first tokens +
            # decode), including in-flight and preempt-discarded work;
            # finished-only is reported alongside, not silently dropped
            "tokens_out": emitted,
            "tokens_finished": sum(len(r.tokens) for r in fin),
            "steps": self.steps_run,
            "windows": self.windows_run,
            "tok_per_s": emitted / dt,
            "decode_step_s": self.decode_time_s / max(self.decode_steps, 1),
            "decode_tok_per_s": self.decode_tokens
            / max(self.decode_time_s, 1e-9),
            "h2d_syncs": self.h2d_syncs,
            "d2h_syncs": self.d2h_syncs,
            "syncs_per_token": (self.h2d_syncs + self.d2h_syncs)
            / max(emitted, 1),
            "block_row_writes": self.block_row_writes,
            # sequential model executions per emitted token — the
            # dispatch-amortization observable speculation attacks
            # (a fused K-scan is K passes; a K+1-wide verify is ONE)
            "model_passes": self.model_passes,
            "dispatches_per_token": self.model_passes / max(emitted, 1),
            "ttft_steps_mean": ttft_d.mean,
            "ttft_steps_p95": ttft_d.percentile(95),
            "ttft_steps_p99": ttft_d.percentile(99),
            "pages_in_use": self.alloc.pages_in_use,
            "peak_pages": self.peak_pages,
            "page_occupancy": self.peak_pages / max(self.alloc.n_pages - 1,
                                                    1),
            "preemptions": sum(r.preemptions for r in self.sched.all_requests),
            "prefill_tokens": self.prefill_tokens,
        }
        # recovery tail from the registry's streaming digest (observed at
        # note_first_token; same numpy semantics in the exact regime)
        out.update({
            # fault plane (repro.serving.faults): quarantine footprint,
            # recovery work, and the reset -> first-token latency tail
            "node_failures": self.node_failures,
            "node_joins": self.node_joins,
            "pages_quarantined": self.pages_quarantined_total,
            "pages_quarantined_now": self.alloc.pages_quarantined,
            "requests_recovered": self.requests_recovered,
            "requests_shed": len(self.sched.shed),
            "tokens_recomputed": self.tokens_recomputed,
            "transient_rejections": self.sched.transient_rejections,
            "quarantined_served": self.quarantined_served,
            "recovery_steps_p50": self.registry.percentile(
                "recovery_steps", 50),
            "recovery_steps_p99": self.registry.percentile(
                "recovery_steps", 99),
        })
        if self.sched.chunked:
            out.update({
                "chunk_dispatches": self.chunk_dispatches,
                "chunk_rounds": self.sched.chunk_rounds,
                "chunk_tasks": self.sched.chunk_tasks,
                "chunk_preemptions": self.sched.chunk_preemptions,
                "prefilling": len(self.sched.prefilling),
            })
        if self.spec is not None:
            s = self.spec.stats
            out.update({
                "spec_drafted": s.drafted,
                "spec_accepted": s.accepted,
                "spec_verifies": s.verifies,
                "spec_rollbacks": s.rollbacks,
                "accept_rate": s.accept_rate,
                # mean requested draft depth (the adaptive-K gauge) and
                # the draft+verify share of decode wall-clock — the
                # bench-honesty split BENCH_spec reports
                "spec_k_mean": s.k_mean,
                "spec_verify_s": self.spec_time_s,
            })
        if self.cache is not None:
            out.update(self.cache.metrics())
            out["bytes_deduped"] = (self.cache.stats.tokens_cached
                                    * self.kv_bytes_per_token)
        return out
