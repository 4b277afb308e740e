"""Paged-KV serving engine: allocator striping, paged-vs-dense numerics,
scheduler conservation under preemption, trace-replay smoke.

Shared fixtures (tiny model, prompts, dense oracle) live in conftest.py
— docs/TESTING.md documents the oracle ladder they anchor."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import dense_oracle, get_tiny_model, make_engine, \
    seeded_prompts
from repro.core.memory_server import striped_owner
from repro.serving import (ContinuousBatchScheduler, NULL_PAGE,
                           PageAllocator, PagedEngine, Request)

KEY = jax.random.PRNGKey(3)


# --- allocator: the striping rule is core/memory_server's ---------------------
def test_allocator_owner_matches_striped_owner():
    a = PageAllocator(n_pages=33, page_size=8, n_nodes=4)
    for p in range(a.n_pages):
        assert a.owner(p) == striped_owner(p, 4)


def test_allocator_stripes_logical_pages_round_robin():
    a = PageAllocator(n_pages=33, page_size=8, n_nodes=4)
    pages = a.alloc("r0", 8)
    # logical page j lands on node j % n (the paper's address%n rule)
    assert [a.owner(p) for p in pages] == [striped_owner(j, 4)
                                           for j in range(8)]
    assert NULL_PAGE not in pages
    # a second tenant still gets a balanced stripe
    pages2 = a.alloc("r1", 4)
    assert [a.owner(p) for p in pages2] == [0, 1, 2, 3]
    occ = a.occupancy_by_node()
    assert max(occ) - min(occ) <= 1


def test_allocator_alloc_grow_free_roundtrip():
    a = PageAllocator(n_pages=9, page_size=4, n_nodes=2)
    assert a.free_pages == 8
    assert a.alloc("r0", 8) is not None
    assert a.alloc("r1", 1) is None        # all-or-nothing
    assert not a.grow("r0")
    assert a.free("r0") == 8
    assert a.free_pages == 8
    assert a.alloc("r1", 3) is not None and a.grow("r1", 2)
    assert len(a.held["r1"]) == 5


def test_allocator_rejects_degenerate_stripe():
    """A stripe wider than the allocatable pool leaves some node owning
    zero pages — its controller starves and conservation accounting
    skews — so construction must fail loudly, not limp along."""
    with pytest.raises(ValueError, match="at least one page"):
        PageAllocator(n_pages=3, page_size=4, n_nodes=3)
    with pytest.raises(ValueError, match="at least one page"):
        PageAllocator(n_pages=2, page_size=4, n_nodes=8)
    # boundary: n_nodes == n_pages - 1 is the thinnest legal stripe —
    # every node owns exactly one allocatable page
    a = PageAllocator(n_pages=4, page_size=4, n_nodes=3)
    pages = a.alloc("r", 3)
    assert sorted(a.owner(p) for p in pages) == [0, 1, 2]
    assert a.check_conservation()


def test_pages_for_zero_tokens_is_zero():
    a = PageAllocator(n_pages=9, page_size=4, n_nodes=2)
    assert a.pages_for(0) == 0
    assert a.pages_for(-3) == 0
    assert a.pages_for(1) == 1
    assert a.pages_for(4) == 1
    assert a.pages_for(5) == 2


def test_engine_rejects_empty_prompt_at_submit():
    """Zero-length (and non-1-D) prompts are rejected AT SUBMIT with a
    ValueError — not deep in prefill — and the rejection leaves the
    engine fully serviceable."""
    cfg, params = get_tiny_model()
    eng = make_engine(cfg, params)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit(np.zeros((0,), np.int32), 4)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((2, 3), np.int32), 4)
    # the failed submits left no residue: a real request still serves
    p = seeded_prompts(cfg, 1, 8, seed=5)[0]
    eng.submit(p, 3, rid="ok")
    fin = eng.run()
    assert len(fin) == 1 and len(fin[0].tokens) == 3
    assert eng.alloc.check_conservation()


def test_serve_cli_exits_2_on_empty_prompt():
    """--prompt-len 0 must exit with status 2 (CLI usage error) before
    any engine work, with the reason on stderr."""
    import os
    import subprocess
    import sys
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--tiny",
         "--engine", "paged", "--prompt-len", "0", "--gen", "4"],
        capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode == 2, (r.returncode, r.stderr)
    assert "--prompt-len" in r.stderr


def test_allocator_reserve_is_best_effort_capacity():
    a = PageAllocator(n_pages=6, page_size=4, n_nodes=1)
    a.alloc("r", 1)
    # covers write positions < 10 -> 3 pages -> 12 token capacity
    assert a.reserve("r", 10) == 12
    assert len(a.held["r"]) == 3
    # pool only has 5 allocatable pages: best-effort, not all-or-nothing
    assert a.reserve("r", 40) == 20
    assert len(a.held["r"]) == 5
    a.free("r")
    assert a.free_pages == 5


# --- paged vs dense decode attention agree numerically ------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("ps,nmax,Kv,G", [(8, 4, 2, 4), (16, 2, 1, 8)])
def test_paged_decode_attention_matches_dense(ps, nmax, Kv, G, dtype):
    from repro.kernels import ref
    B, hd = 3, 64
    H = Kv * G
    T = nmax * ps
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32).astype(dtype)
    # pool with a garbage null page; each sequence owns disjoint pages
    P = 1 + B * nmax
    k_pages = jax.random.normal(ks[1], (P, ps, Kv, hd),
                                jnp.float32).astype(dtype)
    v_pages = jax.random.normal(ks[2], (P, ps, Kv, hd),
                                jnp.float32).astype(dtype)
    bt = (1 + jnp.arange(B * nmax, dtype=jnp.int32)).reshape(B, nmax)
    pos = jnp.array([T - 1, ps + 3, 0], jnp.int32)
    # dense oracle on the gathered contiguous layout, per sequence
    o_paged = ref.paged_decode_attention(q, k_pages, v_pages, bt, pos)
    for b in range(B):
        kc = k_pages[bt[b]].reshape(1, T, Kv, hd)
        vc = v_pages[bt[b]].reshape(1, T, Kv, hd)
        o_dense = ref.decode_attention(q[b:b + 1], kc, vc, int(pos[b]))
        err = jnp.abs(o_paged[b:b + 1].astype(jnp.float32)
                      - o_dense.astype(jnp.float32)).max()
        assert err < (2e-2 if dtype == jnp.bfloat16 else 1e-6), (b, float(err))


def test_paged_decode_kernel_matches_ref():
    from repro.kernels import ops, ref
    B, H, hd, Kv, ps, nmax = 2, 8, 64, 2, 8, 3
    P = 1 + B * nmax
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pages = jax.random.normal(ks[1], (P, ps, Kv, hd))
    v_pages = jax.random.normal(ks[2], (P, ps, Kv, hd))
    bt = (1 + jnp.arange(B * nmax, dtype=jnp.int32)).reshape(B, nmax)
    pos = jnp.array([17, 9], jnp.int32)
    o_ref = ref.paged_decode_attention(q, k_pages, v_pages, bt, pos)
    o = ops.paged_decode_attention(q, k_pages, v_pages, bt, pos)
    assert jnp.abs(o - o_ref).max() < 2e-5


def test_paged_decode_attention_block_t_sweep_matches_ref():
    """The block_t hook sweeps several pages per grid step (padding the
    block table with null pages when nmax doesn't divide) — same output
    as the one-page-per-step schedule and the oracle."""
    from repro.kernels import ops, ref
    B, H, hd, Kv, ps, nmax = 2, 8, 64, 2, 8, 3
    P = 1 + B * nmax
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pages = jax.random.normal(ks[1], (P, ps, Kv, hd))
    v_pages = jax.random.normal(ks[2], (P, ps, Kv, hd))
    bt = (1 + jnp.arange(B * nmax, dtype=jnp.int32)).reshape(B, nmax)
    pos = jnp.array([17, 9], jnp.int32)
    o_ref = ref.paged_decode_attention(q, k_pages, v_pages, bt, pos)
    for block_t in (2 * ps, 4 * ps):     # nmax=3: both need null padding
        o = ops.paged_decode_attention(q, k_pages, v_pages, bt, pos,
                                       block_t=block_t)
        assert jnp.abs(o - o_ref).max() < 2e-5, block_t


def test_paged_decode_ignores_null_page_garbage():
    """Padded block-table slots point at the null page; its contents must
    not leak into the output."""
    from repro.kernels import ref
    B, H, hd, Kv, ps, nmax = 1, 4, 32, 2, 4, 3
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, hd))
    k_pages = jax.random.normal(ks[1], (4, ps, Kv, hd))
    v_pages = jax.random.normal(ks[2], (4, ps, Kv, hd))
    bt = jnp.array([[1, NULL_PAGE, NULL_PAGE]], jnp.int32)
    pos = jnp.array([ps - 1], jnp.int32)   # only page 1 is valid
    o1 = ref.paged_decode_attention(q, k_pages, v_pages, bt, pos)
    k2 = k_pages.at[NULL_PAGE].set(1e6)    # poison the null page
    v2 = v_pages.at[NULL_PAGE].set(-1e6)
    o2 = ref.paged_decode_attention(q, k2, v2, bt, pos)
    assert jnp.array_equal(o1, o2)


# --- the layer loop writes the stacked pools in place -------------------------
def _layer_list(cfg, params, pools):
    """Per-layer (weights, (P, ps, F) pool) pairs, in layer order, cut
    out of the segments' stacks."""
    from repro.models import lm
    out = []
    for seg, seg_p, seg_pool in zip(lm.make_segments(cfg),
                                    params["segments"], pools):
        for c in range(seg.n_cycles):
            cut = (lambda t, c=c: jax.tree.map(lambda a: a[c], t)) \
                if seg.scanned else (lambda t: t)
            out += [(cut(seg_p[j]), cut(seg_pool[j]))
                    for j in range(len(seg.kinds))]
    return out


def _restack(cfg, pools, layer_pools):
    from repro.models import lm
    out, it = [], iter(layer_pools)
    for seg in lm.make_segments(cfg):
        cyc = [[next(it) for _ in seg.kinds] for _ in range(seg.n_cycles)]
        if seg.scanned:
            out.append(tuple(jax.tree.map(lambda *a: jnp.stack(a), *col)
                             for col in zip(*cyc)))
        else:
            out.append(tuple(cyc[0]))
    return out


def _ref_layer(p, cfg, x, pool, angles, kv_step):
    """One layer of the paged programs from the per-layer attention
    functions: ``kv_step(q, k, v, pool) -> (attention out, pool)``."""
    from repro.models import blocks, modules as nn
    from repro.models.attention import _qkv
    h = nn.rmsnorm(x, p["ln1"]["scale"], cfg.norm_eps)
    q, k, v = _qkv(p["attn"], cfg, h, angles)
    o, pool = kv_step(q, k, v, pool)
    x = x + blocks._post(p, cfg, "ln1_post", nn.matmul(o, p["attn"]["wo"]))
    h2 = nn.rmsnorm(x, p["ln2"]["scale"], cfg.norm_eps)
    out2, _ = blocks._ffn_part(p, cfg, h2)
    return x + blocks._post(p, cfg, "ln2_post", out2), pool


def _ref_forward(params, cfg, pools, x, angles, kv_step):
    """Every layer in turn on its own pool.  The barrier rounds x to its
    dtype between layers, as the program's layer scan does: unrolled,
    XLA may carry it across at a higher precision."""
    new = []
    for p, pool in _layer_list(cfg, params, pools):
        x, pool = _ref_layer(p, cfg, x, pool, angles, kv_step)
        x = jax.lax.optimization_barrier(x)
        new.append(pool)
    return x, _restack(cfg, pools, new)


def _ref_window(params, cfg, tokens, pools, bt, pos, active, k):
    """serve_scan's window, layer by layer on per-layer pools, from
    ``paged_cache_update`` and ``attend_decode_paged``."""
    from repro.models import attention as A, lm, modules as nn
    toks = []
    for _ in range(k):
        def kv_step(q, kn, vn, pool):
            pool = A.paged_cache_update(pool, kn, vn, bt, pos)
            return A.attend_decode_paged(
                q, pool, bt, pos, scale=A._scale(cfg),
                softcap=cfg.attn_softcap, n_kv=cfg.n_kv_heads), pool
        x = lm.embed_tokens(params, cfg, tokens)
        angles = lm._angles(cfg, pos[:, None])
        x, pools = _ref_forward(params, cfg, pools, x, angles, kv_step)
        h = nn.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
        nxt = jnp.argmax(lm.head_logits(params, cfg, h), -1).astype(
            jnp.int32)
        tokens = jnp.where(active[:, None] > 0, nxt, tokens)
        pos = pos + active
        toks.append(tokens[:, 0])
    return jnp.stack(toks, 1), pools


def _ref_chunk(params, cfg, tokens, pools, row, start, n_valid):
    """chunk_prefill layer by layer, from ``paged_cache_scatter_suffix``
    and ``attend_prefill_paged``."""
    from repro.models import attention as A, lm, modules as nn
    W = tokens.shape[1]

    def kv_step(q, kn, vn, pool):
        pool = A.paged_cache_scatter_suffix(pool, kn, vn, row, start,
                                            n_valid)
        return A.attend_prefill_paged(
            q, pool, row, start, scale=A._scale(cfg),
            softcap=cfg.attn_softcap, n_kv=cfg.n_kv_heads), pool
    x = lm.embed_tokens(params, cfg, tokens)
    angles = lm._angles(cfg, (start + jnp.arange(W, dtype=jnp.int32))[None])
    x, pools = _ref_forward(params, cfg, pools, x, angles, kv_step)
    h = nn.rmsnorm(jnp.take(x, n_valid - 1, axis=1)[:, None],
                   params["final_norm"]["scale"], cfg.norm_eps)
    return lm.head_logits(params, cfg, h), pools


def _written(cfg, pools, pages, slots):
    """Boolean mask over every pool leaf: True at every layer's
    (page, slot) pairs listed."""
    mask = np.zeros(jax.tree.leaves(pools)[0].shape[-3:-1], bool)
    mask[np.asarray(pages), np.asarray(slots)] = True
    return jax.tree.map(
        lambda a: np.broadcast_to(mask[..., None], a.shape), pools)


def _assert_in_place(cfg, before, after, ref, mask):
    for b, a, r, m in zip(*(jax.tree.leaves(t)
                            for t in (before, after, ref, mask))):
        b, a, r = (np.asarray(t.astype(jnp.float32)) for t in (b, a, r))
        np.testing.assert_array_equal(a[~m], b[~m])   # untouched entries
        np.testing.assert_array_equal(a[m], r[m])     # the writes
        assert not np.array_equal(a[m], b[m])


@pytest.mark.parametrize("n_layers", [2, 1], ids=["scanned", "unscanned"])
def test_paged_programs_write_pools_in_place_like_per_layer_reference(
        n_layers):
    """One ``serve_scan`` window and one ``chunk_prefill`` on random
    pools: every entry outside the written (layer, page, slot) set is
    bitwise unchanged, and the written entries, the emitted tokens and
    the chunk's logits equal a layer-by-layer reference on per-layer
    pools.  Two layers make one scanned segment (the stacked pools ride
    the layer scan's carry); one layer is an unscanned segment."""
    from repro import steps
    from repro.models import lm
    cfg, params = get_tiny_model()
    if n_layers != cfg.n_layers:
        cfg = cfg.replace(n_layers=n_layers)
        params = lm.init_params(jax.random.PRNGKey(1), cfg)
    assert lm.make_segments(cfg)[0].scanned == (n_layers > 1)
    B, ps, nmax, k = 3, 4, 4, 3
    n_pages = B * nmax + 1
    ks = iter(jax.random.split(KEY, 8))
    pools = jax.tree.map(
        lambda a: jax.random.normal(next(ks), a.shape).astype(a.dtype),
        lm.init_paged_caches(cfg, n_pages, ps))
    # slot 2 is idle: a null-page row, held at position 0
    bt = np.zeros((B, nmax), np.int32)
    bt[:2] = 1 + np.random.default_rng(0).permutation(2 * nmax).reshape(
        2, nmax)
    bt = jnp.asarray(bt)
    pos = jnp.array([5, 2, 0], jnp.int32)
    active = jnp.array([1, 1, 0], jnp.int32)
    tokens = jnp.array([[7], [11], [0]], jnp.int32)

    scan = jax.jit(steps.make_paged_serve_scan(cfg), static_argnames=("k",))
    toks, _, _, got = scan(params, tokens, pools, bt, pos, active, k=k)
    want_toks, want = jax.jit(_ref_window, static_argnums=(1, 7))(
        params, cfg, tokens, pools, bt, pos, active, k)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
    p = np.asarray(pos)[:, None] + np.arange(k) * np.asarray(active)[:, None]
    pages = np.take_along_axis(np.asarray(bt), p // ps, 1)
    _assert_in_place(cfg, pools, got, want,
                     _written(cfg, pools, pages, p % ps))

    W, start, n_valid = 8, 6, 5
    row = bt[0]
    chunk_toks = jax.random.randint(next(ks), (1, W), 0, cfg.vocab_size)
    logits, got = jax.jit(steps.make_chunk_prefill(cfg))(
        params, chunk_toks, pools, row, start, n_valid)
    want_logits, want = jax.jit(_ref_chunk, static_argnums=(1,))(
        params, cfg, chunk_toks, pools, row, start, n_valid)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(want_logits))
    t = start + np.arange(W)
    pages = np.where(np.arange(W) < n_valid, np.asarray(row)[t // ps], 0)
    _assert_in_place(cfg, pools, got, want, _written(cfg, pools, pages,
                                                     t % ps))


# --- engine: paged and dense produce identical tokens -------------------------


def test_paged_engine_tokens_match_dense_under_preemption():
    cfg, params = get_tiny_model()
    S, gen, n_req = 12, 6, 6
    max_len = S + gen
    prompts = seeded_prompts(cfg, n_req, S)
    dense = dense_oracle(cfg, params, prompts, gen, max_len)
    # tight pool + unthrottled admission -> preemption must occur
    eng = PagedEngine(cfg, params, max_batch=3, page_size=4, n_pages=14,
                      max_len=max_len, prefill_budget=0.0)
    for p in prompts:
        eng.submit(np.asarray(p), gen)
    finished = eng.run()
    assert len(finished) == n_req
    m = eng.metrics()
    assert m["preemptions"] >= 1, "pool was sized to force preemption"
    for r in finished:
        assert r.tokens == dense[r.rid], (r.rid, r.preemptions)
    assert eng.alloc.pages_in_use == 0     # every page returned


def test_paged_engine_interleaves_arrivals():
    """A request submitted mid-flight is served without disturbing the
    tokens of in-flight requests (continuous batching, not batch swap)."""
    cfg, params = get_tiny_model()
    S, gen = 8, 5
    prompts = seeded_prompts(cfg, 3, S)
    dense = dense_oracle(cfg, params, prompts, gen, S + gen)
    eng = PagedEngine(cfg, params, max_batch=2, page_size=4, n_pages=16,
                      max_len=S + gen)
    eng.submit(np.asarray(prompts[0]), gen, rid="r0")
    eng.step()
    eng.submit(np.asarray(prompts[1]), gen, rid="r1")
    eng.step()
    eng.submit(np.asarray(prompts[2]), gen, rid="r2")
    finished = eng.run()
    assert {r.rid for r in finished} == {"r0", "r1", "r2"}
    for r in finished:
        assert r.tokens == dense[r.rid]


# --- fused multi-token windows ------------------------------------------------
def _request_tokens(finished):
    return {r.rid: list(r.tokens) for r in finished}


def test_fused_windows_match_perstep_and_dense():
    """Fused K-step windows are token-for-token identical to per-step
    decode and to the dense engine — with varied gen lengths so
    completions land mid-trace and windows get cut to the horizon, and
    prompt_len == 2*page_size so windows start exactly on a page
    boundary and cross another one mid-window (pre-reserved)."""
    cfg, params = get_tiny_model()
    S, page = 8, 4
    gens = [3, 5, 8, 2, 6, 4]
    max_len = S + max(gens)
    prompts = seeded_prompts(cfg, len(gens), S)
    dense = dense_oracle(cfg, params, prompts, gens, max_len)

    def run(fused):
        eng = PagedEngine(cfg, params, max_batch=3, page_size=page,
                          n_pages=40, max_len=max_len, fused=fused,
                          max_window=8)
        for i, (p, g) in enumerate(zip(prompts, gens)):
            eng.submit(np.asarray(p), g, rid=f"r{i}")
        return eng, _request_tokens(eng.run())

    eng_f, toks_f = run(True)
    eng_p, toks_p = run(False)
    assert toks_f == toks_p == dense
    # fused actually batched steps into windows
    assert eng_f.windows_run < eng_f.steps_run
    assert eng_p.windows_run == eng_p.decode_steps


def test_fused_windows_match_dense_under_forced_preemption():
    """Same tight-pool trace as the per-step preemption gate, but with
    fused windows: horizon shrinks instead of preempting mid-window,
    and the recompute stays exact."""
    cfg, params = get_tiny_model()
    S, gen, n_req = 12, 6, 6
    max_len = S + gen
    prompts = seeded_prompts(cfg, n_req, S)
    dense = dense_oracle(cfg, params, prompts, gen, max_len)
    eng = PagedEngine(cfg, params, max_batch=3, page_size=4, n_pages=14,
                      max_len=max_len, prefill_budget=0.0, fused=True,
                      max_window=8)
    for p in prompts:
        eng.submit(np.asarray(p), gen)
    finished = eng.run()
    assert len(finished) == n_req
    assert eng.metrics()["preemptions"] >= 1
    for r in finished:
        assert r.tokens == dense[r.rid], (r.rid, r.preemptions)
    assert eng.alloc.pages_in_use == 0


def test_fused_transfer_counters_drop_to_per_window():
    """Host<->device syncs: O(1 per token) per-step vs O(1 per window)
    fused — the transfer counter is the acceptance observable."""
    cfg, params = get_tiny_model()
    S, gen = 8, 9          # first token at prefill + one full 8-window
    prompts = seeded_prompts(cfg, 2, S)

    def run(fused):
        eng = PagedEngine(cfg, params, max_batch=2, page_size=4,
                          n_pages=24, max_len=S + gen, fused=fused,
                          max_window=8, prefill_budget=0.0)
        for i, p in enumerate(prompts):
            eng.submit(np.asarray(p), gen, rid=f"r{i}")
        return eng, _request_tokens(eng.run())

    eng_f, toks_f = run(True)
    eng_p, toks_p = run(False)
    assert toks_f == toks_p
    # per-step: one push + one pull per decode step (8 of them), plus
    # one push + one blocking pull per admitted prefill (2 requests)
    assert eng_p.decode_steps == 8
    assert eng_p.d2h_syncs == 8 + 2
    assert eng_p.h2d_syncs == 8 + 2
    # fused: both requests decode in ONE 8-step window dispatch
    assert eng_f.decode_steps == 8
    assert eng_f.windows_run == 1
    assert eng_f.d2h_syncs == 1 + 2
    assert eng_f.h2d_syncs <= 2 + 2
    m = eng_f.metrics()
    assert m["syncs_per_token"] < eng_p.metrics()["syncs_per_token"]


def test_metrics_count_emitted_tokens_in_flight():
    """tokens_out counts emitted work (prefill first token + decode),
    not just finished requests; finished-only is reported alongside."""
    cfg, params = get_tiny_model()
    [prompt] = seeded_prompts(cfg, 1, 8)
    eng = PagedEngine(cfg, params, max_batch=2, page_size=4, n_pages=16,
                      max_len=16, fused=True, max_window=8)
    eng.submit(np.asarray(prompt), 6)
    eng.step()         # prefill (1 token) + a 4-step window (5 -> pow2 4)
    m = eng.metrics()
    assert m["finished"] == 0 and m["tokens_finished"] == 0
    assert m["tokens_out"] == 5          # in-flight work is visible
    assert m["tok_per_s"] > 0.0
    eng.run()
    m = eng.metrics()
    assert m["tokens_out"] == m["tokens_finished"] == 6


# --- scheduler: safe horizon (host-only) ---------------------------------------
def test_safe_horizon_completion_and_admission_events():
    a = PageAllocator(n_pages=20, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=2)
    assert s.safe_horizon(8) == 0          # nothing running
    s.submit(Request(rid="a", prompt_len=4, gen=10))
    plan = s.plan_step()
    s.note_first_token(plan.admitted[0], 1)
    # remaining 9, no waiting: capped by max_window, pages pre-reserved
    assert s.safe_horizon(8) == 8
    assert len(a.held["a"]) >= a.pages_for(4 + 8)
    # remaining tokens bound the horizon (completion only at window end)
    s.running[0].tokens = [1] * 7          # remaining = 3
    assert s.safe_horizon(8) == 3
    # a waiting request with a free slot + free pages -> horizon 1
    s.running[0].tokens = [1]
    s.submit(Request(rid="b", prompt_len=4, gen=2))
    assert s.safe_horizon(8) == 1


def test_safe_horizon_ignores_budget_blocked_head():
    """A waiting head whose prefill alone busts the interference budget
    cannot be admitted while anything runs — it must not collapse every
    fused window to K=1."""
    a = PageAllocator(n_pages=20, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=2,
                                 prefill_cost_s=lambda n: 10.0 if n > 4
                                 else 0.1,
                                 decode_cost_s=1.0, prefill_budget=2.0)
    s.submit(Request(rid="a", prompt_len=4, gen=10))
    plan = s.plan_step()
    s.note_first_token(plan.admitted[0], 1)
    s.submit(Request(rid="big", prompt_len=8, gen=2))
    assert s.safe_horizon(8) == 8      # head is budget-blocked: no event
    # an admissible head (cost within budget) still caps the window
    s.submit(Request(rid="small", prompt_len=4, gen=2))
    s.waiting.sort(key=lambda r: r.prompt_len)   # make it the head
    assert s.safe_horizon(8) == 1


def test_safe_horizon_shrinks_under_page_pressure():
    a = PageAllocator(n_pages=7, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=2)
    for rid in ("a", "b"):
        s.submit(Request(rid=rid, prompt_len=8, gen=8))
    plan = s.plan_step()
    assert len(plan.admitted) == 2         # 3 pages each, pool is dry
    for req in plan.admitted:
        s.note_first_token(req, 1)
    # remaining 7, but reserve() cannot grow past the held 12-token
    # capacity: horizon shrinks to 12 - 8 = 4 instead of preempting
    assert s.safe_horizon(8) == 4
    assert a.free_pages == 0


# --- scheduler: conservation under preemption (host-only) ---------------------
def _drive(sched, max_steps=500):
    """Drive the scheduler with fake tokens until it drains."""
    steps = 0
    while (sched.waiting or sched.running) and steps < max_steps:
        plan = sched.plan_step()
        for req in plan.admitted:
            sched.note_first_token(req, token=1)
        sched.complete_step({s: 1 for s in list(sched.running)})
        steps += 1
    return steps


def test_scheduler_conserves_requests_under_pressure():
    a = PageAllocator(n_pages=10, page_size=4, n_nodes=2)
    s = ContinuousBatchScheduler(a, max_batch=4)
    n = 8
    for i in range(n):
        s.submit(Request(rid=f"q{i}", prompt_len=6, gen=10))
    steps = _drive(s)
    assert steps < 500, "scheduler wedged"
    assert s.conserved(n)
    assert len(s.finished) == n
    for r in s.finished:
        assert len(r.tokens) == r.gen      # no dropped/duplicated tokens
    assert sum(r.preemptions for r in s.finished) >= 1
    assert a.pages_in_use == 0 and a.free_pages == a.n_pages - 1


def test_scheduler_rejects_request_larger_than_pool():
    a = PageAllocator(n_pages=4, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=2)
    with pytest.raises(ValueError):
        s.submit(Request(rid="big", prompt_len=10, gen=10))


def test_scheduler_prices_admission_with_cost_engine():
    """A tight prefill budget staggers admissions; budget 0 disables
    pricing and admits as fast as slots allow."""
    def throttled(budget):
        a = PageAllocator(n_pages=64, page_size=4, n_nodes=1)
        s = ContinuousBatchScheduler(a, max_batch=4,
                                     prefill_cost_s=lambda n: 1.0,
                                     decode_cost_s=1.0,
                                     prefill_budget=budget)
        for i in range(4):
            s.submit(Request(rid=f"q{i}", prompt_len=4, gen=4))
        return len(s.plan_step().admitted)
    assert throttled(0.0) == 4         # pricing off
    assert throttled(1.0) == 1         # one prefill-step per step
    assert throttled(2.0) == 2


# --- chunked prefill: bit-identity + SLO scheduling ---------------------------
def test_chunked_prefill_tokens_match_monolithic_and_dense():
    """Chunked prefill is a KV-composition transform: any chunk size —
    page-multiple, page-sized, or deliberately misaligned — must emit
    tokens identical to the monolithic engine and the dense oracle,
    including prompts whose final chunk is a partial page."""
    cfg, params = get_tiny_model()
    gens = [5, 6, 4, 7]
    lens = [13, 10, 16, 9]           # non-page-aligned tails included
    max_len = max(s + g for s, g in zip(lens, gens))
    prompts = [seeded_prompts(cfg, 1, s, seed=50 + i)[0]
               for i, s in enumerate(lens)]
    dense = dense_oracle(cfg, params, prompts, gens, max_len)

    def run(chunked, chunk_tokens=0):
        eng = PagedEngine(cfg, params, max_batch=3, page_size=4,
                          n_pages=40, max_len=max_len, fused=True,
                          max_window=4, chunked_prefill=chunked,
                          chunk_tokens=chunk_tokens)
        for i, (p, g) in enumerate(zip(prompts, gens)):
            eng.submit(np.asarray(p), g, rid=f"r{i}", slo="interactive")
        toks = {r.rid: list(r.tokens) for r in eng.run()}
        assert eng.alloc.pages_in_use == 0
        return eng, toks

    _, mono = run(False)
    assert mono == dense
    for ct in (8, 4, 5):             # 2 pages, 1 page, misaligned
        eng, toks = run(True, ct)
        assert toks == dense, f"chunk_tokens={ct}"
        m = eng.metrics()
        assert m["chunk_dispatches"] >= len(prompts)
        assert m["chunk_tasks"] >= len(prompts)


def test_chunked_admission_is_edf_not_fifo():
    """Chunked admission orders the waiting queue by SLO deadline, not
    arrival: an interactive request submitted AFTER a batch request (same
    step) is admitted first.  The monolithic scheduler keeps FIFO."""
    a = PageAllocator(n_pages=64, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=1, chunked=True)
    s.submit(Request(rid="slow", prompt_len=8, gen=2, slo="batch"))
    s.submit(Request(rid="fast", prompt_len=8, gen=2, slo="interactive"))
    plan = s.plan_step()
    assert [r.rid for r in plan.admitted] == ["fast"]
    assert s.prefilling and s.waiting[0].rid == "slow"


def test_plan_chunks_strict_progress_and_page_alignment():
    """Every prefilling request advances by at least one chunk per round
    (starvation-freedom), every non-final chunk boundary is page-aligned,
    and a throttled budget still drains the queue."""
    a = PageAllocator(n_pages=64, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=4, chunked=True,
                                 chunk_tokens=4,
                                 prefill_cost_s=lambda n: float(n),
                                 decode_cost_s=1.0)
    for i, plen in enumerate((13, 9, 11)):
        s.submit(Request(rid=f"q{i}", prompt_len=plen, gen=3,
                         slo="interactive"))
    # park a decoding request so the budget is active (priced, tight)
    s.submit(Request(rid="dec", prompt_len=4, gen=30, slo="interactive"))
    plan = s.plan_step()
    dec = next(r for r in plan.admitted if r.rid == "dec")
    # promote dec to running so plan_chunks prices against its stall_frac
    dec.prefilled = dec.prompt_len
    s.finish_prefill(dec, token=1)
    rounds = 0
    while s.prefilling and rounds < 50:
        before = {r.rid: r.prefilled for r in s.prefilling.values()}
        tasks = s.plan_chunks(window=1)
        seen = set()
        for req, start, n in tasks:
            assert n >= 1 and start + n <= req.prompt_len
            if start + n < req.prompt_len:
                assert (start + n) % a.page_size == 0, \
                    "non-final chunk boundary off the page grid"
            seen.add(req.rid)
        # strict progress: every prefilling request got >= 1 chunk
        assert seen == set(before)
        for req in list(s.prefilling.values()):
            if req.prefilled == req.prompt_len:
                s.finish_prefill(req, token=1)
        rounds += 1
    assert not s.prefilling, "chunk rounds starved a request"
    assert rounds >= 2, "budget never throttled (all drained in one round)"
    assert s.chunk_tasks >= 3


def test_plan_chunks_drains_at_full_speed_when_idle():
    """With nothing decoding, the budget is unbounded: a whole prompt
    drains in ONE round (the monolithic fast path recovered)."""
    a = PageAllocator(n_pages=64, page_size=4, n_nodes=1)
    s = ContinuousBatchScheduler(a, max_batch=2, chunked=True,
                                 chunk_tokens=4,
                                 prefill_cost_s=lambda n: float(n),
                                 decode_cost_s=1.0)
    s.submit(Request(rid="solo", prompt_len=17, gen=2, slo="batch"))
    s.plan_step()
    tasks = s.plan_chunks(window=8)
    req = s.prefilling[next(iter(s.prefilling))]
    assert req.prefilled == req.prompt_len
    assert len(tasks) == 5           # 17 tokens / 4-token chunks


def test_chunked_requests_carry_wall_and_deadline_stamps():
    cfg, params = get_tiny_model()
    [p] = seeded_prompts(cfg, 1, 10, seed=91)
    eng = PagedEngine(cfg, params, max_batch=2, page_size=4, n_pages=16,
                      max_len=16, chunked_prefill=True)
    req = eng.submit(np.asarray(p), 4, slo="interactive")
    from repro.serving import get_slo
    assert req.deadline_step == req.arrived_step \
        + get_slo("interactive").ttft_steps
    eng.run()
    assert req.submitted_at is not None
    assert req.admitted_at >= req.submitted_at
    assert req.first_token_at >= req.admitted_at
    assert req.finished_at >= req.first_token_at


def test_get_slo_rejects_unknown_class():
    from repro.serving import get_slo
    with pytest.raises(KeyError, match="interactive"):
        get_slo("platinum")


# --- trace replay smoke -------------------------------------------------------
def test_serve_trace_smoke():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import serve_trace
    eng, rows, totals = serve_trace.replay(
        serve_trace.default_tenants(quick=True), max_batch=2, page_size=4)
    assert totals["tokens"] > 0 and totals["steps"] > 0
    assert 0 < totals["occupancy_peak"] <= 1.0
    by_tenant = {r["tenant"]: r for r in rows}
    assert by_tenant["chat"]["requests"] == 6
    assert by_tenant["burst"]["requests"] == 4
    table = serve_trace.format_table(rows, totals)
    assert "chat" in table and "burst" in table
    fleet = serve_trace.fleet_view(eng)
    assert "chat" in fleet


def test_replay_accepts_trace_names_and_validates_tenants():
    """replay() called programmatically with a bad trace name or a
    malformed tenants list must fail fast with exit code 2 listing the
    valid traces — not deep inside prompt_for (mirrors run.py --only)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks import serve_trace
    for bad in ("definitely-not-a-trace", [], ["not-a-tenant"],
                [serve_trace.Tenant("t", 0, 0.0, 8, 4)],
                [serve_trace.Tenant("t", 2, 0.0, 8, 4, slo="platinum")],
                object()):
        with pytest.raises(SystemExit) as exc:
            serve_trace.resolve_tenants(bad)
        assert exc.value.code == 2, bad
    # valid names resolve to the registered factories
    for name, factory in serve_trace.TRACES.items():
        got = serve_trace.resolve_tenants(name, quick=True)
        assert got == factory(True), name


def test_replay_bad_trace_exits_2_in_subprocess():
    """End-to-end contract: the process exits 2 and stderr names the
    valid traces (same shape as run.py --only's unknown-pattern error)."""
    import os
    import subprocess
    import sys
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    code = ("import sys; sys.path[:0] = ['src', '.'];\n"
            "from benchmarks.serve_trace import replay\n"
            "replay('definitely-not-a-trace')\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "valid traces:" in proc.stderr
    for name in ("mixed", "overload", "shared-prefix", "repetitive"):
        assert name in proc.stderr
