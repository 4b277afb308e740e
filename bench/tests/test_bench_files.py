"""Every file the benchmark names loads, and BENCHMARK.json keeps to the
benchmark's contract (names, units, bounds, cells, paths)."""
import dataclasses
import json
import re
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

from benchkit import cell, counting, engine, traffic  # noqa: E402
from benchkit import models  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_experts_per_tok")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_loads(c):
    assert NAME.match(c["name"])
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert c["file"].startswith("bench/configs/")
    cfg = cell.load_json(ROOT / c["file"])
    assert cfg["name"] == c["name"]
    assert not any(k in WIDTHS or k.endswith(("_dim", "_rank"))
                   for k in c["reduced"])
    ref = cell.load_module((ROOT / c["file"]).parent / cfg["reference"])
    assert callable(ref.make_weights) and callable(ref.hidden)
    family = engine.adapter(cfg)
    assert family.__name__ == f"benchkit.models.{cfg['model_type']}"
    mcfg = engine.model_config(cfg)
    assert mcfg.d_model == cfg["hidden_size"]
    assert mcfg.n_layers == cfg["num_hidden_layers"]
    assert cfg["check"]["logit_gap_limit"] > 0
    work = engine.work(cfg)
    assert work.weight_bytes > 0 and work.kv_bytes_per_token > 0
    assert work.head_flops > 0 and work.token_flops(2) > work.token_flops(1)
    assert work.prefill_flops(0, 2) == work.prefill_flops(0, 1) \
        + work.prefill_flops(1, 1)
    params = jax.eval_shape(lambda: engine.program_params(
        cfg, ref.make_weights(cfg, 3), mcfg))
    assert jax.tree.leaves(params)


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_files_load(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["config"] in {c["name"] for c in SPEC["configs"]}
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200
    mix = cell.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    sched = traffic.build(mix, 2 ** 31 + 12345, SPEC["run_seconds"], 1000)
    assert sched.items and sched.max_tokens > 0
    assert any(m["name"] == "setup_s" for m in
               cell.cell_metrics(SPEC, w["name"], trace=False))
    assert cell.cell_metrics(SPEC, w["name"], trace=True)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if "bound" in m:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        reader = cell.load_module(BENCH / "metrics" / f"{m['name']}.py")
        assert callable(reader.read)
    for w in m.get("workloads", []):
        assert w in {c["name"] for c in SPEC["workloads"]}


def test_every_metric_has_a_reader_and_no_reader_is_orphaned():
    readers = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert readers == {m["name"] for m in SPEC["per_layer"]}


def test_every_traffic_file_is_used():
    used = {w["traffic"] for w in SPEC["workloads"]}
    assert {p.stem for p in (BENCH / "traffic").glob("*.json")} == used


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_seed_never_changes_the_histogram(name):
    """The run's seed draws the token ids; the mix's ``order_seed`` the
    sizes, their order and the arrival times."""
    mix = cell.load_json(BENCH / "traffic" / f"{name}.json")
    a = traffic.build(mix, 1, 30, 1000)
    b = traffic.build(mix, 2 ** 33 + 7, 30, 1000)

    def sizes(s):
        pre = [len(s.prefixes[int(i.tenant[1:])]) if s.prefixes else 0
               for i in s.items]
        turn = [len(i.prompt) - p for i, p in zip(s.items, pre)]
        return pre, turn, [i.gen for i in s.items]
    assert sizes(a) == sizes(b)
    assert [i.due_s for i in a.items] == [i.due_s for i in b.items]
    other = traffic.build(dict(mix, order_seed=mix["order_seed"] + 1), 1,
                          30, 1000)
    assert sorted(sizes(other)[1]) == sorted(sizes(a)[1])
    assert sizes(other)[1] != sizes(a)[1]
    assert [i.prompt[-1] for i in a.items] != [i.prompt[-1] for i in b.items]


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (BENCH / "traffic").glob("*.json")))
def test_every_block_offers_the_stated_rate_and_mix(name):
    """Each block of requests holds one draw from every stratum, so its
    span stays near ``block / rate`` and its longest turn in the top
    stratum, whatever the order drawn."""
    mix = cell.load_json(BENCH / "traffic" / f"{name}.json")
    k, rate = mix["block"], mix["arrivals"]["rate_per_s"]

    def turns(s):
        return np.array([len(i.prompt) - (len(s.prefixes[int(i.tenant[1:])])
                                          if s.prefixes else 0)
                         for i in s.items])
    top = np.sort(turns(traffic.build(mix, 3, 60, 1000)))
    for order_seed in (mix["order_seed"], 2 ** 31 + 5):
        s = traffic.build(dict(mix, order_seed=order_seed), 3, 60, 1000)
        due = np.array([i.due_s for i in s.items])
        spans = np.diff(np.concatenate([[0.0], due])).reshape(-1, k).sum(1)
        assert np.all(np.abs(spans * rate / k - 1) < 0.35), spans
        assert np.all(turns(s).reshape(-1, k).max(1)
                      >= top[-len(top) // k])


def test_blocked_holds_one_value_of_each_stratum_per_block():
    rng = np.random.default_rng(5)
    v = traffic.blocked(np.arange(40), 4, rng)
    assert sorted(v) == list(range(40))
    for blk in v.reshape(10, 4):
        assert sorted(x // 10 for x in blk) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        traffic.blocked(np.arange(7), 4, rng)


@pytest.mark.parametrize("kind", sorted(
    p.stem for p in (BENCH / "benchkit" / "arrivals").glob("[a-z]*.py")))
def test_each_arrival_process_loads_by_name(kind):
    proc = traffic.arrival_process(kind)
    spec = {"kind": kind, "rate_per_s": 2.0, "clients": 3}
    n = proc.count(spec, 10.0, 4)
    assert n > 0 and n % 4 == 0
    due, after = proc.due(spec, n, 4, np.random.default_rng(1))
    assert len(due) == len(after) == n
    assert np.all(after < np.arange(n))


@pytest.mark.parametrize("dist", sorted(
    p.stem for p in (BENCH / "benchkit" / "dists").glob("[a-z]*.py")))
def test_each_distribution_loads_by_name(dist):
    spec = {"dist": dist, "median": 10, "sigma": 0.5, "mean": 10,
            "min": 1, "max": 30}
    x = traffic.stratified(spec, 9)
    assert np.all(np.diff(x) >= 0) and 1 <= x[0] and x[-1] <= 30


def test_stratified_quantiles():
    x = traffic.stratified({"dist": "lognormal", "median": 100,
                            "sigma": 0.5}, 101)
    assert x[50] == pytest.approx(100)
    u = traffic.stratified({"dist": "uniform", "min": 0, "max": 10}, 5)
    assert np.allclose(u, [1, 3, 5, 7, 9])
    assert traffic.zipf_counts(100, 4, 1.1).sum() == 100


@pytest.mark.parametrize("options", [
    {"qk_norm": True, "gated_ffn": True, "act": "silu"},
    {"qk_norm": False, "gated_ffn": False, "act": "gelu"}],
    ids=["qwen3", "plain-mlp"])
def test_model_options_set_the_program_tree(options):
    """A model family is a configuration file: its ``model_options``
    choose the program's mechanisms and the parameter leaves handed to
    it, with no change to the adapter."""
    cfg = dict(cell.load_json(BENCH / "tests" / "data" / "tiny-qwen3.json"),
               model_options=options)
    ref = cell.load_module(BENCH / "configs" / "qwen3-1.7b.ref.py")
    assert engine.adapter(cfg).__name__ == "benchkit.models.qwen3"
    mcfg = engine.model_config(cfg)
    assert (mcfg.qk_norm, mcfg.gated_ffn, mcfg.act) == (
        options["qk_norm"], options["gated_ffn"], options["act"])
    params = engine.program_params(cfg, ref.make_weights(cfg, 3), mcfg)
    block = params["segments"][0][0]
    assert ("q_norm" in block["attn"]) == options["qk_norm"]
    assert ("w_gate" in block["ffn"]) == options["gated_ffn"]
    assert engine.work(cfg).gated == options["gated_ffn"]


def test_pool_pages_per_cell():
    """The pool is the exact worst case of a full batch at the traffic's
    longest request, plus the null page, rounded up to a multiple of the
    chips the pages are striped over."""
    pages = {}
    for w in SPEC["workloads"]:
        cfg = cell.load_json(cell.config_path(SPEC, w["config"]))
        mix = cell.load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        ps = cfg["engine"]["page_size"]
        sched = traffic.build(mix, 5, 10, 1000)
        max_len = -(-sched.max_tokens // ps) * ps
        pages[w["name"]] = engine.pool_pages(cfg["engine"], max_len,
                                             w["chips"])
        assert pages[w["name"]] % w["chips"] == 0
    assert pages == {"qwen3-1.7b.chat": 2049, "qwen3-1.7b.sessions": 2289}


@pytest.mark.parametrize("chips,pages", [(1, 2049), (2, 2050), (4, 2052)])
def test_striped_pools_round_up_to_the_chips(chips, pages):
    """On several chips the one-chip rule's pages (16 slots of 128 pages
    of 16 tokens, plus the null page) are rounded up to a multiple of the
    chips, so that each chip holds as many."""
    opts = {"max_batch": 16, "page_size": 16}
    assert engine.pool_pages(opts, 2048, chips) == pages


@pytest.mark.parametrize("chips,before,after", [
    (1, "tpu", "tpu"), (4, "tpu", "tpu,cpu"), (4, "tpu,cpu", "tpu,cpu"),
    (4, "", "")])
def test_striped_cells_stage_the_pools_on_the_host(monkeypatch, chips,
                                                   before, after):
    """A cell on several chips adds the host's platform after the chips'
    (the striped pools are made in host memory first); a one-chip cell,
    a platform list that has it, or none at all is left as it is."""
    from types import SimpleNamespace
    fake = SimpleNamespace(jax_platforms=before)
    fake.update = lambda key, value: setattr(fake, key, value)
    monkeypatch.setattr(jax, "config", fake)
    engine.stage_on_host(chips)
    assert fake.jax_platforms == after


def test_a_family_is_found_by_its_model_type(monkeypatch):
    """A configuration of a family the benchmark does not run loads
    through its own module, found by the file's ``model_type``, with no
    edit to the harness: here a module beside the tests, on the family
    package's search path."""
    monkeypatch.setattr(models, "__path__", [*models.__path__, str(DATA)])
    cfg = cell.load_json(DATA / "tiny-dense.json")
    ref = cell.load_module(DATA / cfg["reference"])
    family = engine.adapter(cfg)
    assert family.__file__ == str(DATA / "tinydense.py")
    mcfg = engine.model_config(cfg)
    assert not mcfg.tie_embeddings and mcfg.qk_norm
    params = engine.program_params(cfg, ref.make_weights(cfg, 3), mcfg)
    assert params["head"]["head_w"].shape == (128, 4096)
    work = engine.work(cfg)
    assert isinstance(work, family.Work)
    # the counting reads only the work count's members
    f, b = counting.decode_step(work, [10])
    assert f == work.token_flops(10) + work.head_flops
    assert b == work.weight_bytes + 10 * work.kv_bytes_per_token
    assert counting.prefill(work, 0, 4, logits=True) == \
        work.prefill_flops(0, 4) + work.head_flops
    # a tree the program would not build is refused
    with pytest.raises(ValueError, match="parameter tree has changed"):
        engine.program_params(cfg, ref.make_weights(cfg, 3),
                              dataclasses.replace(mcfg, tie_embeddings=True))


QWEN3_TREE = [
    ("['embed']['embed_table']", (151936, 2048), "bfloat16"),
    ("['final_norm']['scale']", (2048,), "float32"),
    ("['segments'][0][0]['attn']['k_norm']", (28, 128), "float32"),
    ("['segments'][0][0]['attn']['q_norm']", (28, 128), "float32"),
    ("['segments'][0][0]['attn']['wk']", (28, 2048, 1024), "bfloat16"),
    ("['segments'][0][0]['attn']['wo']", (28, 2048, 2048), "bfloat16"),
    ("['segments'][0][0]['attn']['wq']", (28, 2048, 2048), "bfloat16"),
    ("['segments'][0][0]['attn']['wv']", (28, 2048, 1024), "bfloat16"),
    ("['segments'][0][0]['ffn']['w_down']", (28, 6144, 2048), "bfloat16"),
    ("['segments'][0][0]['ffn']['w_gate']", (28, 2048, 6144), "bfloat16"),
    ("['segments'][0][0]['ffn']['w_up']", (28, 2048, 6144), "bfloat16"),
    ("['segments'][0][0]['ln1']['scale']", (28, 2048), "float32"),
    ("['segments'][0][0]['ln2']['scale']", (28, 2048), "float32"),
]


def test_qwen3_program_tree_is_pinned():
    """qwen3-1.7b's parameter tree, leaf by leaf, as the benchmark handed
    it to the program before model families became modules of their
    own; every matrix is the reference's own array."""
    cfg = cell.load_json(BENCH / "configs" / "qwen3-1.7b.json")
    ref = cell.load_module(BENCH / "configs" / cfg["reference"])
    mcfg = engine.model_config(cfg)
    tree = jax.eval_shape(lambda: engine.program_params(
        cfg, ref.make_weights(cfg, 3), mcfg))
    assert [(jax.tree_util.keystr(p), leaf.shape, str(leaf.dtype))
            for p, leaf in jax.tree_util.tree_leaves_with_path(tree)] \
        == QWEN3_TREE
    tiny = cell.load_json(DATA / "tiny-qwen3.json")
    weights = ref.make_weights(tiny, 3)
    params = engine.program_params(tiny, weights, engine.model_config(tiny))
    assert params["segments"][0][0]["attn"]["wq"] is weights["wq"]
    assert params["embed"]["embed_table"] is weights["embed"]
