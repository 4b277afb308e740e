"""The one adapter between the benchmark and the system under test.

It turns a configuration file's published keys into the program's
``ModelConfig``, hands the benchmark's weights to the program in the
program's own parameter layout, and builds ``PagedEngine`` with only
the options the configuration file's ``engine`` group names.  Nothing
else in the benchmark imports the program.
"""
from __future__ import annotations

import numpy as np


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    published keys, and the mechanisms they imply that the program names
    as options (qk-norm, a gated MLP, the activation), from the file's
    ``model_options`` group."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["torch_dtype"], activation_dtype=cfg["torch_dtype"],
        **cfg["model_options"])


def program_params(weights: dict, mcfg):
    """The benchmark's layer-stacked weights in the program's parameter
    tree (matrices shared, not copied; norm scales in float32 as the
    program keeps them), holding only the leaves the model options ask
    for, and checked leaf by leaf against the tree the program's own
    ``init_params`` would build."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    f32 = lambda a: a.astype(jnp.float32)   # noqa: E731
    attn = {k: weights[k] for k in ("wq", "wk", "wv", "wo")}
    if mcfg.qk_norm:
        attn.update(q_norm=f32(weights["q_norm"]),
                    k_norm=f32(weights["k_norm"]))
    ffn = {k: weights[k] for k in (("w_gate",) if mcfg.gated_ffn else ())
           + ("w_up", "w_down")}
    block = {"ln1": {"scale": f32(weights["ln1"])}, "attn": attn,
             "ln2": {"scale": f32(weights["ln2"])}, "ffn": ffn}
    params = {"embed": {"embed_table": weights["embed"]},
              "segments": [[block]],
              "final_norm": {"scale": f32(weights["final_norm"])}}
    if not mcfg.tie_embeddings:
        params["head"] = {"head_w": weights["head"]}
    want = jax.eval_shape(lambda k: lm.init_params(k, mcfg),
                          jax.random.PRNGKey(0))
    if jax.tree.structure(want) != jax.tree.structure(params):
        raise ValueError("the program's parameter tree has changed: "
                         f"{jax.tree.structure(want)}")
    for w, p in zip(jax.tree.leaves(want), jax.tree.leaves(params)):
        if (w.shape, w.dtype) != (p.shape, p.dtype):
            raise ValueError(f"parameter leaf {p.shape} {p.dtype} where "
                             f"the program has {w.shape} {w.dtype}")
    return params


def pool_pages(opts: dict, max_len: int) -> int:
    """The serving CLI's rule: the exact worst case of a full batch, plus
    the null page."""
    return opts["max_batch"] * -(-max_len // opts["page_size"]) + 1


def build(cfg: dict, mcfg, params, max_tokens: int):
    """``PagedEngine`` with the configuration's engine options; the
    sequence bound is the traffic's longest request, rounded up to a
    page."""
    from repro.serving.engine import PagedEngine
    opts = dict(cfg["engine"])
    ps = opts["page_size"]
    max_len = -(-max_tokens // ps) * ps
    return PagedEngine(mcfg, params, max_len=max_len,
                       n_pages=pool_pages(opts, max_len), **opts)


def smallest_chunk(schedule, chunk_tokens: int, page_size: int) -> int:
    """The shortest prefill chunk the schedule's prompts make, cut as the
    scheduler cuts them: page-aligned slices of at most ``chunk_tokens``
    from the first uncached token (0, or the end of a cached prefix)."""
    least = chunk_tokens
    for item in schedule.items:
        prefix = len(schedule.prefixes[int(item.tenant[1:])]) \
            if schedule.prefixes else 0
        for start in {0, prefix}:
            while start < len(item.prompt):
                end = min(len(item.prompt), start + chunk_tokens)
                if end < len(item.prompt) and end - end % page_size > start:
                    end -= end % page_size
                least = min(least, end - start)
                start = end
    return least


def warm(eng, schedule, vocab: int, seed: int = 7) -> None:
    """Compile, through the engine's own calls, every program the window
    can dispatch: each power-of-two chunk bucket from the schedule's
    shortest chunk up to the chunk size (a prompt of that length is one
    chunk), the page copy a prefix hit that ends inside a page makes,
    and every decode-window bucket.  The prefix cache and the counters
    are cleared afterwards."""
    rng = np.random.default_rng(seed)
    b = 1 << (smallest_chunk(schedule, eng.sched.chunk_tokens,
                             eng.page_size) - 1).bit_length()
    while b <= eng.sched.chunk_tokens:
        eng.submit(rng.integers(0, vocab, b, dtype=np.int32), 2)
        b *= 2
    eng.run()
    if eng.cache is not None:
        ps = eng.page_size
        base = rng.integers(0, vocab, 3 * ps, dtype=np.int32)
        eng.submit(base, 2)
        eng.run()
        hit = np.concatenate([base[:ps + ps // 2],
                              rng.integers(0, vocab, ps, dtype=np.int32)])
        eng.submit(hit, 2)
        eng.run()
        eng.cache.clear()
    eng.warmup_windows()
    eng.reset_metrics()
