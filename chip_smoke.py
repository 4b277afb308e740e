"""Chip smoke test: the paged serving path, end to end, on a TPU.

    python chip_smoke.py               # one chip: phases A and B
    python chip_smoke.py --four-chips  # KV pools striped over a 1x4 mesh

Serves ``qwen3-1.7b`` at its full published width (28 layers, float32
parameters as the config states; random weights from a seed) through the
serving CLI's own functions (``repro.launch.serve.run_paged``, with
``run_dense`` as the reference path), all in this one process.

* Phase A: 16 requests of 512 prompt tokens and 64 generated tokens,
  batch 8, page 16, fused decode windows.
* Phase B: 8 requests sharing a 256-token prefix, batch 2, with the
  prefix cache, chunked prefill and speculative decoding on.
* ``--four-chips``, and nothing else: the Phase A prompts on a 1x4 mesh
  (pools striped over the ``model`` axis) and on a 1x1 engine on device 0.

Checks: every request finishes with every token in the vocabulary; the
paged path's logits agree with the reference's after prefill and through
a few decode steps within ``LOGIT_RTOL``; every logit is finite.  The
share of greedy tokens on which the two paths agree is printed but not
gated: with random weights, near-ties flip on rounding.

Lines before the last are smoke output, not benchmark figures.  On
success the last line is one JSON object naming the device; the exit code
is nonzero when JAX finds no TPU, or when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "qwen3-1.7b"
SEED = 0
PHASE_A = SimpleNamespace(requests=16, prompt_len=512, gen=64, batch=8,
                          page_size=16, shared_prefix=0)
# Batch 2: requests past the first batch find the shared prefix cached
# (a batch that admits all 8 at once prefills them side by side, so none
# hits), and the engine's priced gate buys verify passes only at small
# batch, where decode is latency-bound (at batch 4 and up, on this model,
# it prices every verify out and speculation never runs).
PHASE_B = SimpleNamespace(requests=8, prompt_len=512, gen=64, batch=2,
                          page_size=16, shared_prefix=256)
LOGIT_STEPS = 4       # decode steps compared after the prefill
# Logit tolerance, relative to the reference's largest |logit|.  Both
# paths carry bf16 activations (8 significant bits: one rounding is up to
# 2^-9 of the value) and round in different orders — page gather against
# contiguous cache, a different XLA fusion per program, and under a
# stripe a psum merge of per-device softmax partials — so 28 layers can
# move a logit by a few bf16 steps of the range.  A wrong page, position,
# mask or merge moves it by a large fraction of the range.
LOGIT_RTOL = 2e-2


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def serve_args(size, **flags) -> argparse.Namespace:
    """The serving CLI's own options for one phase: ``size`` fields plus
    ``flags`` given as ``--flag value`` pairs."""
    from repro.launch.serve import build_parser
    argv = ["--engine", "paged", "--batch", str(size.batch),
            "--prompt-len", str(size.prompt_len), "--gen", str(size.gen),
            "--requests", str(size.requests),
            "--page-size", str(size.page_size),
            "--shared-prefix", str(size.shared_prefix)]
    for k, v in flags.items():
        argv += [f"--{k.replace('_', '-')}", str(v)]
    return build_parser().parse_args(argv)


def check_tokens(outputs, n_requests: int, gen: int, vocab: int) -> int:
    _check(sorted(outputs) == list(range(n_requests)),
           f"finished requests {sorted(outputs)} != {n_requests}")
    for rid, toks in outputs.items():
        _check(len(toks) == gen, f"request {rid}: {len(toks)} tokens, "
               f"want {gen}")
        _check(all(0 <= t < vocab for t in toks),
               f"request {rid}: a token outside the vocabulary [0, {vocab})")
    return n_requests * gen


def agreement(a, b) -> float:
    """Share of greedy tokens on which two runs agree (same rid, same
    position)."""
    pairs = [(x, y) for rid in a for x, y in zip(a[rid], b[rid])]
    return sum(x == y for x, y in pairs) / max(len(pairs), 1)


def dense_logits(cfg, params, prompt, n_steps: int, max_len: int):
    """Reference logits of one prompt through the dense path: the
    prefill's next-token logits, then ``n_steps`` greedy decode steps.
    Returns (float32 (1 + n_steps, V), the greedy tokens fed)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import steps

    prefill = jax.jit(steps.make_prefill_step(cfg, max_len=max_len))
    serve = jax.jit(steps.make_serve_step(cfg), donate_argnums=(2,))
    logits, caches = prefill(params, jnp.asarray(prompt)[None])
    rows, fed = [logits[0, 0]], []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for i in range(n_steps):
        fed.append(int(tok[0, 0]))
        tok, logits, caches = serve(params, tok, caches,
                                    jnp.int32(len(prompt) + i))
        rows.append(logits[0, 0])
    return np.stack([np.asarray(r, np.float32) for r in rows]), fed


def compare_logits(name: str, got, ref) -> float:
    import numpy as np
    _check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    _check(bool(np.isfinite(ref).all()), f"{name}: non-finite reference "
           "logits")
    err = float(np.abs(got - ref).max() / np.abs(ref).max())
    print(f"[smoke] {name}: max |logit diff| / max |ref logit| = {err!r} "
          f"over {got.shape[0]} positions (limit {LOGIT_RTOL})")
    _check(err <= LOGIT_RTOL, f"{name}: logits differ by {err} of the "
           f"reference range (limit {LOGIT_RTOL})")
    return err


def peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def phase_a(cfg, params, size=PHASE_A):
    """Plain paged serving with fused windows, checked against the dense
    path's logits."""
    import numpy as np
    from repro.launch.serve import _stream_prompts, run_dense, run_paged

    args = serve_args(size)
    t0 = time.time()
    out_p, m, eng = run_paged(args, cfg, params=params)
    setup = time.time() - t0 - m["seconds"]
    n = check_tokens(out_p, size.requests, size.gen, cfg.vocab_size)
    prompt = np.asarray(_stream_prompts(args, cfg)[0])
    max_len = size.prompt_len + size.gen
    ref, fed = dense_logits(cfg, params, prompt, LOGIT_STEPS, max_len)
    got = eng.score(prompt, fed)
    del eng
    compare_logits("phase A paged vs dense", got, ref)
    out_d, _ = run_dense(args, cfg, None, params=params)
    print(f"[smoke] phase A: served {n} tokens for {m['finished']} "
          f"requests in {m['windows']} dispatches; set-up and compile "
          f"{setup!r} s; greedy tokens agreeing with dense "
          f"{agreement(out_p, out_d)!r}")


def phase_b(cfg, params, size=PHASE_B):
    """The rest of the normal path: prefix cache, chunked prefill and
    speculative decoding together, on prompts sharing a prefix."""
    from repro.launch.serve import run_dense, run_paged

    args = serve_args(size, prefix_cache="on", chunk_prefill="on",
                      spec_decode="on")
    t0 = time.time()
    out_p, m, eng = run_paged(args, cfg, params=params)
    setup = time.time() - t0 - m["seconds"]
    del eng
    n = check_tokens(out_p, size.requests, size.gen, cfg.vocab_size)
    _check(m["prefix_hits"] > 0, "phase B: no prefix-cache hit")
    _check(m["chunk_tasks"] > 0, "phase B: no prefill chunk ran")
    _check(m["spec_verifies"] > 0, "phase B: no speculative verify ran")
    out_d, _ = run_dense(args, cfg, None, params=params)
    print(f"[smoke] phase B: served {n} tokens for {m['finished']} "
          f"requests; prefix hits {m['prefix_hits']}, "
          f"{m['prefill_tokens_cached']} prompt tokens from shared pages, "
          f"{m['chunk_tasks']} prefill chunks, {m['spec_verifies']} "
          f"verifies (accept rate {m['accept_rate']!r}); set-up and "
          f"compile {setup!r} s; greedy tokens agreeing with dense "
          f"{agreement(out_p, out_d)!r}")


def four_chips(cfg, params, rep, devices, size=PHASE_A):
    """The Phase A prompts on a 1x4 mesh (KV pools striped over the
    ``model`` axis, parameters ``rep`` replicated) against a 1x1 engine
    on ``devices[0]`` (parameters ``params``)."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_test_mesh
    from repro.launch.serve import _stream_prompts, run_paged

    args = serve_args(size)
    prompt = np.asarray(_stream_prompts(args, cfg)[0])
    fed = None
    rows = {}
    outs = {}
    for label, model, p in (("1x1", 1, params), ("1x4", 4, rep)):
        mesh = make_test_mesh(1, model, devices=devices) if model > 1 \
            else None
        t0 = time.time()
        out, m, eng = run_paged(args, cfg, n_nodes=model, params=p,
                                mesh=mesh)
        setup = time.time() - t0 - m["seconds"]
        check_tokens(out, size.requests, size.gen, cfg.vocab_size)
        if model > 1:
            for leaf in jax.tree.leaves(eng.pools):
                placed = {s.device for s in leaf.addressable_shards}
                _check(placed == set(devices[:model]),
                       f"a pool leaf sits on {len(placed)} devices, not "
                       f"{model}")
                _check(leaf.addressable_shards[0].data.shape[-3]
                       == leaf.shape[-3] // model,
                       "a pool leaf is not split over its page axis")
        if fed is None:
            fed = [int(t) for t in out[0][:LOGIT_STEPS]]
        rows[label] = eng.score(prompt, fed)
        outs[label] = out
        del eng
        print(f"[smoke] {label}: served {size.requests * size.gen} tokens "
              f"for {m['finished']} requests; set-up and compile {setup!r} "
              "s")
    compare_logits("1x4 striped vs 1x1", rows["1x4"], rows["1x1"])
    print(f"[smoke] greedy tokens agreeing 1x4 vs 1x1 "
          f"{agreement(outs['1x4'], outs['1x1'])!r}")


def replicated_params(cfg, devices):
    """Random parameters from ``SEED``, made on the device side: one copy
    on ``devices[0]`` when there is one device, else one replica per
    device (and the first device's replica for the 1x1 engine, so no
    device holds two)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import make_test_mesh
    from repro.models import lm

    init = jax.jit(lm.init_params, static_argnums=1)
    if len(devices) == 1:
        return init(jax.random.PRNGKey(SEED), cfg), None
    mesh = make_test_mesh(1, len(devices), devices=devices)
    rep = jax.jit(lm.init_params, static_argnums=1,
                  out_shardings=NamedSharding(mesh, PartitionSpec()))(
        jax.random.PRNGKey(SEED), cfg)
    first = jax.tree.map(
        lambda a: next(s.data for s in a.addressable_shards
                       if s.device == devices[0]), rep)
    return first, rep


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 1x4 striped-pool check against 1x1")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package under {SRC}; run it from "
                 "a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {platform!r} "
                 f"({len(devices)} device(s)); this smoke test needs a TPU")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: needs {want} TPU devices, found "
                 f"{len(devices)}")
    kind = devices[0].device_kind
    print(f"[smoke] device_kind {kind!r}, {len(devices)} device(s), "
          f"compile cache {cache_dir}")

    from repro.configs import get_config
    cfg = get_config(ARCH)
    t0 = time.time()
    params, rep = replicated_params(cfg, devices[:want])
    jax.block_until_ready(params)
    print(f"[smoke] {ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.param_dtype} parameters made in {time.time() - t0!r} s")
    if args.four_chips:
        four_chips(cfg, params, rep, devices[:4])
    else:
        phase_a(cfg, params)
        phase_b(cfg, params)
    del rep
    for d in devices[:want]:
        print(f"[smoke] {d}: peak_bytes_in_use {peak_bytes(d)}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
