"""CLI trainer.

  PYTHONPATH=src python -m repro.launch.train --arch tiny-100m \
      --seq 512 --batch 8 --steps 200 --ckpt-dir /tmp/ck

Use --tiny to run the reduced smoke config of any assigned arch, and
--devices N (with --data D --model M) to train on N fake CPU devices.
With ``--layout auto`` the cost engine enumerates every (data, model)
factorization of the device count, prints the fastest, and the run
reports predicted vs measured step time.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny-100m")
    ap.add_argument("--tiny", action="store_true",
                    help="use the reduced smoke config of --arch")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--impl", default=None)
    ap.add_argument("--devices", type=int, default=0,
                    help="fake CPU device count (0 = real devices)")
    ap.add_argument("--layout", default="manual", choices=["manual", "auto"],
                    help="auto: let the cost engine pick (data, model)")
    ap.add_argument("--link-mode", default="circuit",
                    choices=["circuit", "packet"],
                    help="interconnect model used by --layout auto")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
            f" --xla_force_host_platform_device_count={args.devices}"

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from repro.configs import get_config, get_tiny_config
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_test_mesh
    from repro.parallel.sharding import autotune_layout, make_layout_mesh
    from repro.runtime import train_loop

    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    predicted = None
    if args.layout == "auto":
        best, ranked = autotune_layout(cfg, shape, mode=args.link_mode)
        predicted = best
        print(f"[cost-engine] {len(ranked)} candidate layouts for "
              f"{best.layout.n_chips} chips ({args.link_mode} mode):")
        for est in ranked:
            tag = " <= chosen" if est is ranked[0] else ""
            print(f"[cost-engine]   {est.describe()}{tag}")
        print(f"[cost-engine] predicted step time "
              f"{best.step_time_s * 1e3:.3f} ms "
              f"({best.tokens_per_s:.0f} tok/s)")
        mesh = make_layout_mesh(best.layout)
    else:
        mesh = None
        if args.data * args.model > 1:
            mesh = make_test_mesh(args.data, args.model)

    job = train_loop.TrainJobConfig(
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, peak_lr=args.lr,
        metrics_path=args.metrics)
    out = train_loop.run(cfg, shape, mesh=mesh, job=job, impl=args.impl)
    print("final:", {k: v for k, v in out["final_metrics"].items()})
    if predicted is not None:
        measured = out["final_metrics"].get("sec_per_step")
        if measured:
            print(f"[cost-engine] predicted {predicted.step_time_s:.4f}s "
                  f"vs measured {measured:.4f}s per step "
                  f"(ratio {measured / predicted.step_time_s:.2f}x; the "
                  f"engine models v5e-class chips, not this host)")


if __name__ == "__main__":
    main()
