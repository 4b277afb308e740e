"""CLI batched-serving driver: dense fixed batches or the paged engine.

  PYTHONPATH=src python -m repro.launch.serve --arch tiny-100m \
      --batch 4 --prompt-len 64 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --tiny --engine paged \
      --requests 16 --devices 4

``--engine dense`` (default) is the original fixed-size-batch loop: a
request queue feeds whole batches, finished batches are replaced
wholesale.  ``--engine paged`` routes through :mod:`repro.serving` — the
paged-KV continuous-batching engine (Swallow §III farmer-worker over the
§X-B striped store); both engines decode greedily and produce identical
tokens on the same prompts (pinned by tests/test_serving.py).
``--chunk-prefill on`` slices paged prefills into page-aligned chunks
co-scheduled with decode windows, with ``--slo`` stamping every request's
class (TTFT deadline + tolerable stall — docs/SERVING.md).
``--fault-plan chaos`` arms a seeded deterministic fault schedule (node
failures, transient admission errors, a straggler) against the paged
run and prints the recovery report — docs/FAULT_TOLERANCE.md; needs a
striped pool, i.e. ``--model N`` or ``--layout auto`` with model > 1.

``--layout auto`` asks the cost engine for the fastest (data, model)
mesh for the decode shape and reports predicted vs measured per-token
time.  Timing excludes the first (compile) step — a warmup prefill +
decode runs before the clock starts, so the predicted-vs-measured ratio
reflects steady state, not XLA compilation.

``--trace-out trace.json`` arms the step-clock flight recorder on the
paged engine and exports the run as Chrome trace-event JSON (load in
Perfetto or chrome://tracing — docs/OBSERVABILITY.md); every dispatch
span carries cost-engine predicted seconds and §VI energy alongside
measured wall time, rolled up into the per-phase model-error table.
``--metrics-out metrics.json`` dumps the unified metrics registry
snapshot (counters, gauges, percentile digests).
"""
import argparse
import os
import time


def make_prompts(n_requests: int, prompt_len: int, vocab_size: int,
                 shared_prefix: int = 0):
    """The shared request stream: request i is PRNGKey(i) — both engines
    see byte-identical prompts, which is what makes the token-equality
    acceptance check meaningful.  ``shared_prefix`` gives every request
    the same leading tokens (a system prompt) so ``--prefix-cache on``
    has something to share."""
    import jax
    import jax.numpy as jnp
    # clamp so an over-long system prompt never yields a negative tail
    shared_prefix = max(0, min(shared_prefix, prompt_len))
    base = jax.random.randint(jax.random.PRNGKey(757575),
                              (shared_prefix,), 2, vocab_size)
    return [jnp.concatenate([
        base, jax.random.randint(jax.random.PRNGKey(i),
                                 (prompt_len - shared_prefix,), 2,
                                 vocab_size)])
            for i in range(n_requests)]


def _stream_prompts(args, cfg):
    """The one prompt stream both engines consume — keep construction in
    one place so dense and paged always see byte-identical prompts."""
    return make_prompts(args.requests, args.prompt_len, cfg.vocab_size,
                        shared_prefix=getattr(args, "shared_prefix", 0))


def run_dense(args, cfg, mesh, params=None):
    """Fixed-batch loop.  Returns (per-request token lists, stats dict)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro import steps as steps_mod
    from repro.parallel.sharding import use_sharding

    max_len = args.prompt_len + args.gen
    with use_sharding(mesh):
        if params is None:
            params = lm.init_params(jax.random.PRNGKey(0), cfg)
        prefill = jax.jit(steps_mod.make_prefill_step(cfg, max_len=max_len))
        serve = jax.jit(steps_mod.make_serve_step(cfg), donate_argnums=(2,))

        prompts = _stream_prompts(args, cfg)
        # warmup: compile prefill + decode outside the timed region
        wl, wc = prefill(params, jnp.stack([prompts[0]] * args.batch))
        wt = jnp.argmax(wl, -1).astype(jnp.int32)
        wt, _, wc = serve(params, wt, wc, jnp.int32(args.prompt_len))
        jax.block_until_ready(wt)

        pending = list(enumerate(prompts))
        outputs = {}
        t0 = time.time()
        decode_steps = 0
        decode_s = 0.0
        tokens_out = 0
        while pending:
            batch = [pending.pop(0) for _ in
                     range(min(args.batch, len(pending)))]
            pad = len(batch)
            while len(batch) < args.batch:      # pad the worker pool
                batch.append(batch[-1])
            logits, caches = prefill(params, jnp.stack([p for _, p in batch]))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs = [tok]
            jax.block_until_ready(tok)          # decode-only timing below
            td = time.time()
            for i in range(args.gen - 1):
                tok, logits, caches = serve(params, tok, caches,
                                            jnp.int32(args.prompt_len + i))
                outs.append(tok)
                decode_steps += 1
            jax.block_until_ready(tok)
            decode_s += time.time() - td
            seq = jnp.concatenate(outs, -1)     # (batch, gen)
            for row, (rid, _) in enumerate(batch[:pad]):
                outputs[rid] = [int(t) for t in seq[row]]
                tokens_out += args.gen
        dt = time.time() - t0
    stats = dict(requests=len(outputs), tokens=tokens_out, seconds=dt,
                 decode_steps=decode_steps,
                 step_s=decode_s / max(decode_steps, 1))
    return outputs, stats


def run_paged(args, cfg, n_nodes: int = 1, params=None, mesh=None):
    """Paged continuous-batching path.  Returns (tokens, stats, engine).

    ``n_nodes`` is the page-striping width (the model-axis extent the
    cost engine prices and the allocator stripes over).  With ``mesh``
    the striping is literal: the engine places each KV page pool over
    the mesh's model axis (NamedSharding on the page axis) and decode
    runs the shard_map owner-partials merge."""
    import jax
    import numpy as np
    from repro.models import lm
    from repro.serving import PagedEngine

    if params is None:
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
    max_len = args.prompt_len + args.gen
    # auto pool: exact worst-case demand of a full batch + the null page
    n_pages = args.pages or (
        args.batch * (-(-max_len // args.page_size)) + 1)
    if n_nodes > 1 and n_pages % n_nodes:
        n_pages += n_nodes - n_pages % n_nodes   # stripe divisibility
    eng = PagedEngine(cfg, params, max_batch=args.batch,
                      page_size=args.page_size, n_pages=n_pages,
                      max_len=max_len, n_nodes=n_nodes, mesh=mesh,
                      link_mode=args.link_mode,
                      prefill_budget=args.prefill_budget,
                      fused=args.fused, max_window=args.window,
                      prefix_cache=args.prefix_cache == "on",
                      spec_decode=args.spec_decode == "on",
                      spec_k=args.spec_k,
                      chunked_prefill=args.chunk_prefill == "on",
                      chunk_tokens=args.chunk_tokens,
                      trace=bool(getattr(args, "trace_out", None)),
                      trace_capacity=getattr(args, "trace_capacity", 4096))
    prompts = _stream_prompts(args, cfg)
    # warmup both jitted paths (prefill + every fused-window bucket),
    # then reset clocks
    eng.warmup_windows()
    eng.submit(np.asarray(prompts[0]), min(2, args.gen), rid="warmup")
    eng.run()
    # compile the COW-copy + suffix-prefill bucket the measured hits will
    # use (no-op with the cache off or no shared prefix)
    eng.warmup_prefix(args.prompt_len, args.shared_prefix)
    eng.reset_metrics()
    if eng.cache is not None:
        eng.cache.clear()      # the measured run starts with a cold tree
    if getattr(args, "fault_plan", "off") == "chaos":
        from repro.serving import FaultPlan
        if n_nodes < 2:
            raise SystemExit("--fault-plan chaos needs a striped pool "
                             "(--model N >= 2 or --layout auto): node 0 "
                             "never fails, so a 1-node pool has nothing "
                             "to quarantine")
        # armed AFTER warmup/reset: plan step 0 is the first measured step
        eng.install_faults(FaultPlan.seeded(
            args.fault_seed, n_nodes=n_nodes, horizon=args.fault_horizon))

    for i, p in enumerate(prompts):
        eng.submit(np.asarray(p), args.gen, rid=f"req{i}", slo=args.slo)
    t0 = time.time()
    finished = eng.run()
    dt = time.time() - t0
    outputs = {int(r.rid[3:]): r.tokens for r in finished}
    m = eng.metrics()
    m.update(seconds=dt, step_s=m["decode_step_s"])
    return outputs, m, eng


def report_fleet(args, cfg, eng, tokens_out: int):
    """Register the serve job with the cost-aware nOS and print the
    fleet serving view (per-job pages, energy, queue latency)."""
    from repro.configs.base import ShapeConfig
    from repro.core import nos as nos_mod

    pod = nos_mod.NOS(data_rows=4, model_cols=max(args.devices or 1, 1))
    shape = ShapeConfig("serve", args.prompt_len + args.gen, args.batch,
                        "decode")
    pod.submit(cfg, name="serve", shape=shape, steps=eng.steps_run,
               mode=args.link_mode, max_rows=1)
    est = pod.jobs["serve"].estimate
    m = eng.metrics()
    from repro.serving.slo import get_slo
    slo = get_slo(args.slo)
    fin = eng.sched.finished
    met_tokens = sum(len(r.tokens) for r in fin
                     if r.first_token_step <= r.deadline_step)
    # measured on the requests' own lifecycle stamps (perf_counter)
    from repro.serving.telemetry import HistogramDigest
    queue_wait = HistogramDigest.of(r.admitted_at - r.submitted_at
                                    for r in fin)
    ttft = HistogramDigest.of(r.first_token_at - r.submitted_at for r in fin)
    # predicted-vs-measured attribution from the flight recorder (when
    # the run was traced): per-phase rollup feeds the fleet-level table
    model_error = None
    pred_s = meas_s = pred_j = 0.0
    if eng.tracer is not None:
        model_error = eng.tracer.model_error_report()
        pred_s = sum(r["predicted_s"] for r in model_error.values())
        meas_s = sum(r["measured_s"] for r in model_error.values())
        pred_j = sum(r["predicted_j"] for r in model_error.values())
    pod.update_serving(
        "serve", pages_held=eng.alloc.pages_in_use,
        peak_pages=m["peak_pages"],
        tokens_out=tokens_out,
        queue_latency_s=queue_wait.mean,
        preemptions=m["preemptions"],
        energy_j=eng.steps_run * est.energy.total_j * est.layout.n_chips,
        shared_pages=m.get("shared_pages"),
        prefix_hit_rate=m.get("prefix_hit_rate"),
        bytes_deduped=m.get("bytes_deduped"),
        accept_rate=m.get("accept_rate"),
        dispatches_per_token=m.get("dispatches_per_token"),
        spec_k=m.get("spec_k_mean"),
        ttft_p99_s=ttft.percentile(99),
        ttft_target_s=slo.ttft_steps * est.step_time_s,
        goodput_frac=met_tokens / max(tokens_out, 1),
        pages_quarantined=m.get("pages_quarantined"),
        requests_recovered=m.get("requests_recovered"),
        tokens_recomputed=m.get("tokens_recomputed"),
        recovery_steps_p99=m.get("recovery_steps_p99"),
        predicted_s=pred_s, measured_s=meas_s, predicted_j=pred_j,
        model_error=model_error)
    print("[nOS] fleet serving view:")
    print(pod.serving_table())
    if model_error:
        print("[nOS] predicted-vs-measured attribution:")
        print(pod.attribution_table())


def build_parser() -> argparse.ArgumentParser:
    """The CLI's options and defaults (``chip_smoke.py`` parses its
    phases through this too)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-100m")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--engine", default="dense", choices=["dense", "paged"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--layout", default="manual", choices=["manual", "auto"],
                    help="auto: let the cost engine pick (data, model)")
    ap.add_argument("--link-mode", default="circuit",
                    choices=["circuit", "packet"])
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged engine: tokens per KV page")
    ap.add_argument("--pages", type=int, default=0,
                    help="paged engine: pool size incl. null page (0=auto)")
    ap.add_argument("--prefill-budget", type=float, default=2.0,
                    help="prefill seconds admitted per step, in units of "
                         "one decode step (cost-engine priced)")
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="paged engine: fused multi-token decode windows "
                         "(--no-fused = legacy per-step host loop)")
    ap.add_argument("--window", type=int, default=8,
                    help="paged engine: max fused window (tokens per "
                         "device dispatch)")
    ap.add_argument("--prefix-cache", default="off", choices=["on", "off"],
                    help="paged engine: radix-tree prefix sharing with "
                         "copy-on-write on the striped page store "
                         "(docs/PREFIX_CACHE.md)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="give every request the same leading N tokens "
                         "(a system prompt) so the prefix cache has "
                         "something to share")
    ap.add_argument("--spec-decode", default="off", choices=["on", "off"],
                    help="paged engine: n-gram speculative decoding — "
                         "draft from each sequence's own history, verify "
                         "K+1 positions in one dispatch, roll back "
                         "rejected pages (docs/SERVING.md)")
    ap.add_argument("--spec-k", default="auto",
                    help="max draft tokens per verification dispatch: an "
                         "integer for a fixed depth, or 'auto' (default) "
                         "for the per-tenant acceptance-EWMA adaptive "
                         "controller (AdaptiveK)")
    ap.add_argument("--chunk-prefill", default="off", choices=["on", "off"],
                    help="paged engine: split prefills into page-aligned "
                         "chunks co-scheduled with decode windows under "
                         "SLO-aware EDF admission (docs/SERVING.md; off = "
                         "monolithic priced-FIFO prefill)")
    ap.add_argument("--chunk-tokens", type=int, default=0,
                    help="tokens per prefill chunk (0 = 2 pages)")
    ap.add_argument("--slo", default="standard",
                    choices=["interactive", "standard", "batch"],
                    help="SLO class stamped on every submitted request "
                         "(TTFT deadline + tolerable prefill stall; "
                         "drives the chunked scheduler)")
    ap.add_argument("--fault-plan", default="off", choices=["off", "chaos"],
                    help="paged engine: arm a seeded deterministic fault "
                         "schedule — node failures quarantining their "
                         "page stripe, transient admission rejections "
                         "under capped backoff, a straggler slowdown — "
                         "and print the recovery report "
                         "(docs/FAULT_TOLERANCE.md)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="seed for the chaos fault schedule")
    ap.add_argument("--fault-horizon", type=int, default=48,
                    help="steps the chaos fault schedule spans")
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="paged engine: arm the step-clock flight "
                         "recorder and export the run as Chrome "
                         "trace-event JSON (Perfetto-loadable; "
                         "docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-out", default=None, metavar="METRICS.json",
                    help="paged engine: dump the unified metrics "
                         "registry snapshot (counters, gauges, "
                         "percentile digests) as JSON")
    ap.add_argument("--trace-capacity", type=int, default=4096,
                    help="flight-recorder ring size (spans kept; "
                         "oldest evicted first)")
    return ap


def main():
    args = build_parser().parse_args()
    if args.prompt_len < 1:
        import sys
        print(f"error: --prompt-len must be >= 1 (got {args.prompt_len}): "
              "an empty prompt has no KV to prefill and no position to "
              "decode from", file=sys.stderr)
        raise SystemExit(2)
    if args.spec_k != "auto":
        args.spec_k = int(args.spec_k)

    if args.devices:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={args.devices}"

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config, get_tiny_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.sharding import autotune_layout, make_layout_mesh

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    predicted = None
    mesh = None
    if args.layout == "auto":
        decode_shape = ShapeConfig("serve", args.prompt_len + args.gen,
                                   args.batch, "decode")
        # serving=True prices the striped-KV traffic (§V link model on
        # the (n-1)/n remote write fraction + decode stats merge) on top
        # of the transformer collectives
        best, ranked = autotune_layout(cfg, decode_shape,
                                       mode=args.link_mode,
                                       serving=args.engine == "paged")
        predicted = best
        print(f"[cost-engine] {len(ranked)} candidate layouts for "
              f"{best.layout.n_chips} chips ({args.link_mode} mode):")
        for est in ranked:
            tag = " <= chosen" if est is ranked[0] else ""
            print(f"[cost-engine]   {est.describe()}{tag}")
        print(f"[cost-engine] predicted decode step "
              f"{best.step_time_s * 1e3:.3f} ms "
              f"({best.tokens_per_s:.0f} tok/s)")
        mesh = make_layout_mesh(best.layout)
    elif args.data * args.model > 1:
        # raises when fewer devices exist than the mesh asks for
        mesh = make_test_mesh(args.data, args.model)

    if args.engine == "paged":
        n_nodes = (predicted.layout.model if predicted is not None
                   else max(args.model, 1))
        outputs, m, eng = run_paged(args, cfg, n_nodes=n_nodes, mesh=mesh)
        tokens = sum(len(t) for t in outputs.values())
        print(f"[paged] served {m['finished']} requests, {tokens} tokens "
              f"in {m['seconds']:.2f}s "
              f"({tokens / max(m['seconds'], 1e-9):.1f} tok/s, "
              f"{m['steps']} engine steps)")
        print(f"[paged] TTFT mean {m['ttft_steps_mean']:.1f} / p95 "
              f"{m['ttft_steps_p95']:.1f} steps; peak pages "
              f"{m['peak_pages']} ({m['page_occupancy'] * 100:.0f}% of pool);"
              f" {m['preemptions']} preemptions")
        mode = "fused" if args.fused else "per-step"
        print(f"[paged] {mode}: {m['windows']} device dispatches for "
              f"{m['steps']} scheduler steps; host<->device syncs "
              f"{m['h2d_syncs']} h2d + {m['d2h_syncs']} d2h "
              f"({m['syncs_per_token']:.2f} per token); decode "
              f"{m['decode_tok_per_s']:.1f} tok/s")
        if eng.spec is not None:
            print(f"[paged] spec decode: {m['model_passes']} model passes "
                  f"for {m['tokens_out']} tokens "
                  f"({m['dispatches_per_token']:.2f} dispatches/token); "
                  f"accept rate {m['accept_rate'] * 100:.0f}% "
                  f"({m['spec_accepted']}/{m['spec_drafted']} drafts over "
                  f"{m['spec_verifies']} verifies, "
                  f"{m['spec_rollbacks']} page rollbacks)")
            if eng.spec.adaptive:
                print(f"[paged] spec depth: adaptive, mean K "
                      f"{m['spec_k_mean']:.1f}; draft+verify "
                      f"{m['spec_verify_s']:.3f}s of {m['decode_s']:.3f}s "
                      f"decode")
        if eng.sched.chunked:
            print(f"[paged] chunked prefill: {m['chunk_tasks']} chunks in "
                  f"{m['chunk_rounds']} rounds "
                  f"({m['chunk_dispatches']} dispatches, "
                  f"{m['chunk_preemptions']} mid-prefill preemptions); "
                  f"SLO class {args.slo}, p99 TTFT "
                  f"{m['ttft_steps_p99']:.1f} steps")
        if eng.cache is not None:
            print(f"[paged] prefix cache: {m['prefix_hit_rate'] * 100:.0f}%"
                  f" hit rate ({m['prefix_hits']}/{m['prefix_lookups']}), "
                  f"{m['prefill_tokens_cached']} prefill tokens served "
                  f"from shared pages ({m['prefill_tokens']} computed), "
                  f"{m['cow_copies']} COW copies, {m['shared_pages']} tree "
                  f"pages, {m['prefix_evictions']} evictions, "
                  f"{m['bytes_deduped'] / 1024:.0f} KiB deduped")
        if eng.faults is not None:
            print(f"[paged] fault plane: {m['node_failures']} node "
                  f"failures / {m['node_joins']} re-joins, "
                  f"{m['pages_quarantined']} pages quarantined, "
                  f"{m['requests_recovered']} requests recovered "
                  f"({m['tokens_recomputed']} tokens recomputed), "
                  f"{m['requests_shed']} shed, "
                  f"{m['transient_rejections']} transient rejections; "
                  f"recovery p99 {m['recovery_steps_p99']:.1f} steps, "
                  f"{m['quarantined_served']} stale reads")
        if eng.tracer is not None:
            from repro.serving.telemetry import format_model_error
            eng.tracer.finalize(eng.sched.step_idx)
            report = eng.tracer.model_error_report()
            if report:
                print("[trace] per-phase model error "
                      "(cost-engine predicted vs measured wall):")
                print(format_model_error(report))
            if args.trace_out:
                eng.tracer.write_chrome(args.trace_out)
                n = len(eng.tracer.chrome_trace()["traceEvents"])
                print(f"[trace] wrote {n} trace events to "
                      f"{args.trace_out} (load in Perfetto / "
                      f"chrome://tracing; {eng.tracer.recorded} spans "
                      f"recorded, {eng.tracer.dropped} evicted)")
        if args.metrics_out:
            import json
            with open(args.metrics_out, "w") as f:
                json.dump(eng.registry.snapshot(), f, indent=2,
                          sort_keys=True)
            print(f"[metrics] wrote registry snapshot to "
                  f"{args.metrics_out}")
        report_fleet(args, cfg, eng, tokens)
        measured = m["step_s"]
    else:
        outputs, stats = run_dense(args, cfg, mesh)
        print(f"served {stats['requests']} requests, {stats['tokens']} "
              f"tokens in {stats['seconds']:.2f}s "
              f"({stats['tokens'] / max(stats['seconds'], 1e-9):.1f} tok/s)")
        measured = stats["step_s"]
    if predicted is not None:
        # warmup ran before the clock: this ratio is steady-state only
        print(f"[cost-engine] predicted {predicted.step_time_s * 1e3:.3f}"
              f" ms vs measured {measured * 1e3:.3f} ms per decode step "
              f"(warmup excluded; ratio "
              f"{measured / predicted.step_time_s:.2f}x — the engine "
              f"models v5e-class chips, not this host)")


if __name__ == "__main__":
    main()
