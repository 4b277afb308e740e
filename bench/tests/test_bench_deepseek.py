"""DeepSeek-V3's family module, configuration, reference, traffic and
metric readers, at the cell's size where nothing runs and at a tiny size
through a whole run on the CPU."""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchkit import cell, engine, harness, traffic  # noqa: E402

SPEC = cell.load_json(ROOT / "BENCHMARK.json")
CFG = cell.load_json(BENCH / "configs" / "deepseek-v3.json")
TINY = DATA / "tiny-deepseek-v3.json"
CATALOG = {  # the catalog's DeepSeek-V3 config.json, as published
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v3", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}


def test_the_file_differs_from_the_catalog_only_where_it_says():
    entry = next(c for c in SPEC["configs"] if c["name"] == "deepseek-v3")
    changed = {k for k, v in CATALOG.items() if CFG[k] != v}
    assert changed == set(entry["reduced"])
    assert {k: CFG[k] for k in changed} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 8, "vocab_size": 16160}
    assert CFG["deployment"]["routed_experts"] == 256
    assert CFG["deployment"]["published"] == {
        k: CATALOG[k] for k in CFG["deployment"]["published"]}


def test_work_integers_are_pinned():
    """The decode step reads 3.28 GB of fixed weights (latent attention
    of 5 layers, the dense FFN, 4 shared experts, the float32 routers and
    the 16,160-row head) and, where tokens route to them, 2.82 GB of held
    experts; a token stores 5 x 1,152 bytes of latent rows."""
    w = engine.work(CFG)
    assert (w.mla_params, w.expert_params, w.router_params,
            w.head_params) == (187105280, 44040192, 1835008, 115834880)
    assert w.weight_bytes == 3277127680
    assert w.expert_bytes == 2818572288
    assert w.kv_bytes_per_token == 5760
    assert w.absorbed_per_key == 278528 and w.upprojected_per_key == 81920
    assert w.token_flops(1) == 3120250880
    assert w.token_flops(24000) == 36542218240
    assert w.head_flops == 231669760
    assert w.prefill_flops(24000, 64) == 2341598658560
    assert w.expert_read_share(16) == pytest.approx(0.39829, abs=1e-5)
    assert w.prefill_flops(0, 3) == sum(w.prefill_flops(p, 1)
                                        for p in range(3))


def test_prompts_count_in_the_programs_slices_and_paths():
    """A prompt counts in the engine's 512-token slices, each on the
    path the program compiles for its width: a 64-token question over a
    24,000-token document absorbed, a 200-token one (compiled 256 wide)
    and a document's chunks up-projected."""
    from repro.models import mla
    w = engine.work(CFG)
    mcfg = engine.model_config(CFG)
    assert [w.absorbed(1 << i) for i in range(10)] == [
        mla.absorbed_cheaper(mcfg, 1 << i) for i in range(10)]
    assert w.absorbed(128) and not w.absorbed(256)
    assert w.prefill_flops(24000, 200) == 6624616448000
    assert w.prefill_flops(16000, 1024) == w.slice_flops(16000, 512) \
        + w.slice_flops(16512, 512) == 15574163783680
    # a prompt's last slice may end inside a page; the others may not
    assert w.prefill_flops(16000, 520) == w.slice_flops(16000, 512) \
        + w.slice_flops(16512, 8)
    assert w.prefill_flops(16004, 520) == w.slice_flops(16004, 508) \
        + w.slice_flops(16512, 12)


def test_a_settled_choice_holds_under_every_router_output_in_the_box():
    """Where the reference judges a row settled, the held experts the
    rule chooses are the same for every router output within
    ``TIE`` times the row's rms output, at the cell's routing
    (256 experts, 8 groups, top-4 groups, top-8, 8 held)."""
    import jax.numpy as jnp
    import numpy as np
    ref = cell.load_module(BENCH / "configs" / CFG["reference"])
    rng = np.random.default_rng(0)
    S, E, tie = 256, 256, ref.TIE
    cfg = {k: CFG[k] for k in ("n_group", "topk_group",
                               "num_experts_per_tok", "norm_topk_prob",
                               "routed_scaling_factor")}
    held = jnp.arange(8)
    x = rng.normal(0, 1.69, (S, E)).astype(np.float32)
    bias = jnp.asarray(rng.normal(0, 0.02, E).astype(np.float32))
    eye = jnp.eye(E, dtype=jnp.float32)     # the router: h is x itself

    def chosen(xs):
        share, settled = ref._route(jnp.asarray(xs), eye, bias, held, cfg)
        return np.asarray(share) > 0, np.asarray(settled)
    want, settled = chosen(x)
    assert 0.2 < settled.mean() < 0.9
    rms = np.sqrt((x * x).mean(-1, keepdims=True))
    for i in range(40):
        edge = rng.choice([-1.0, 1.0], (S, E)) if i % 2 else \
            rng.uniform(-1, 1, (S, E))
        got, _ = chosen(x + (tie * rms * edge).astype(np.float32))
        assert (got[settled] == want[settled]).all()


def test_program_tree_at_the_cells_size():
    """The family's tree of the reference's weights is the program's,
    leaf for leaf (``engine.program_params`` checks it), with the router
    at its published width and 8 experts held."""
    mcfg = engine.model_config(CFG)
    ref = cell.load_module(BENCH / "configs" / CFG["reference"])
    params = jax.eval_shape(lambda: engine.program_params(
        CFG, ref.make_weights(CFG, 3), mcfg))
    dense, experts = params["segments"]
    assert dense[0]["ffn"]["w_up"].shape == (7168, 18432)
    moe = experts[0]["moe"]
    assert moe["router_w"].shape == (4, 7168, 256)
    assert moe["e_up"].shape == (4, 8, 7168, 2048)
    assert experts[0]["mla"]["kv_b"].shape == (4, 512, 128 * 256)
    assert params["head"]["head_w"].shape == (7168, 16160)
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == pytest.approx(3.16e9, rel=0.01)


def test_the_pool_and_the_documents():
    """A full batch at the mix's longest request (a 31,500-token document,
    a 256-token question and a 512-token answer) plus the null page; no
    document fills whole pages, so every question's prefix hit ends
    inside a page and copies it."""
    mix = cell.load_json(BENCH / "traffic" / "docqa.json")
    sched = traffic.build(mix, 5, 10, 1000)
    ps = CFG["engine"]["page_size"]
    assert sched.max_tokens == 32268
    assert engine.pool_pages(CFG["engine"], -(-32268 // ps) * ps) == 32273
    assert all(len(p) % ps for p in sched.prefixes)
    assert 16000 <= min(map(len, sched.prefixes)) \
        <= max(map(len, sched.prefixes)) <= 32000


def rehearse(seed=2 ** 31 + 99, trace=False, **kw):
    seen = {}
    run = harness.Loop.run

    def keep(self, *a, **k):
        seen["out"] = run(self, *a, **k)
        return seen["out"]
    harness.Loop.run = keep
    try:
        res = cell.run(SPEC, SPEC["workloads"][0]["name"], seed, 1.0, trace,
                       t_start=time.perf_counter(), platform="cpu",
                       compile_cache=False, config_file=TINY,
                       mix=cell.load_json(DATA / "prefix.json"),
                       log=lambda m: None, **kw)
    finally:
        harness.Loop.run = run
    return res, seen["out"]


@pytest.mark.parametrize("seed", [2 ** 31 + 99, 3])
def test_a_tiny_deepseek_cell_runs_correct_and_the_control_does_not(seed):
    """Found by its ``model_type``, the family serves a whole run of a
    tiny share (4 of 16 experts, YaRN, the latent pool) in bfloat16 with
    prefix hits that copy pages, compiles nothing in its window and
    passes its check; the float8 control fails it.  (At seed 3 bfloat16
    rounding flips a held expert, and a served logit reads 1.07 below
    the reference's best where no tie tolerance is set.)"""
    res, out = rehearse(seed, control=True)
    assert res["correct"], res["check"]
    assert out.compiles_in_window == 0
    assert out.counters["prefill_tokens_cached"] > 0
    assert res["control"]["logit_gap"] > res["check"]["logit_gap"]["limit"]


def _broken(monkeypatch, module, name, fn):
    """A run of the tiny cell with ``module.name`` replaced by ``fn`` in
    the program, its step programs traced anew (and again after)."""
    from repro.serving import engine as engine_mod
    monkeypatch.setattr(module, name, fn)
    engine_mod._jitted_steps.cache_clear()
    try:
        res, _ = rehearse()
    finally:
        monkeypatch.undo()
        engine_mod._jitted_steps.cache_clear()
    return res


def test_the_check_fails_without_the_yarn_scale(monkeypatch):
    from repro.models import mla
    res = _broken(monkeypatch, mla, "_scale", lambda cfg: (
        cfg.mla.qk_nope_head_dim + cfg.mla.qk_rope_head_dim) ** -0.5)
    assert not res["correct"]


def test_the_check_fails_without_a_held_expert(monkeypatch):
    from repro.models import moe
    dropless = moe.apply_dropless

    def three_of_four(p, cfg, x, *a):
        return dropless(dict(p, e_down=p["e_down"].at[0].set(0)), cfg, x,
                        *a)
    res = _broken(monkeypatch, moe, "apply_dropless", three_of_four)
    assert not res["correct"]


def _ctx(trace=True, shape=None, **counters):
    work = harness.Work(decode_flops=4e12, decode_kv_bytes=2.0e9,
                        decode_steps=100, prefill_flops=3e13)
    c = dict(decode_steps=400, decode_tokens=4000, decode_time_s=10.0,
             prefill_tokens=0, prefill_tokens_cached=0, **counters)
    t = {"program_s": {"serve_scan": 2.0, "chunk_prefill": 0.5,
                       "copy_page": 0.01},
         "busy_s": 3.0, "window_s": 3.1} if trace else None
    return SimpleNamespace(
        out=SimpleNamespace(work=work, counters=c), trace=t,
        shape=shape or engine.work(CFG), chips=1, engine=CFG["engine"],
        peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})


def test_the_latent_readers():
    """``latent_decode_roofline``: per step the fixed weights, the held
    experts at the share a step of b = 10 tokens reaches, the b latent
    rows written; plus the traced live rows; bandwidth-bound here.
    ``latent_prefill_mfu_pct``: counted prefill operations over the
    prefill programs' device time at peak.  Neither reads anything
    without a trace, nor the roofline for a family without routed
    experts (a qwen3 cell, or a program that lacks them)."""
    roof = cell.load_module(BENCH / "metrics" / "latent_decode_roofline.py")
    mfu = cell.load_module(BENCH / "metrics" / "latent_prefill_mfu_pct.py")
    w = engine.work(CFG)
    per_step = (w.weight_bytes + w.expert_bytes * w.expert_read_share(10)
                + 10 * w.kv_bytes_per_token)
    want = 100 * (100 * per_step + 2.0e9) / 819e9 / 2.0
    assert roof.read(_ctx()) == pytest.approx(want)
    assert mfu.read(_ctx()) == pytest.approx(100 * 3e13 / (0.51 * 197e12))
    assert roof.read(_ctx(trace=False)) is None
    assert mfu.read(_ctx(trace=False)) is None
    qwen = engine.work(cell.load_json(BENCH / "configs" / "qwen3-1.7b.json"))
    assert roof.read(_ctx(shape=qwen)) is None


def test_the_cell_entry():
    w = cell.find_cell(SPEC, "deepseek-v3.docqa")
    assert (w["config"], w["traffic"], w["chips"]) == (
        "deepseek-v3", "docqa", 1)
    names = {m["name"] for m in cell.cell_metrics(SPEC, w["name"], True)}
    assert names == {"latent_decode_roofline", "latent_prefill_mfu_pct"}
    json.dumps(SPEC)
