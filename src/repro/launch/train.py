"""Training launcher.

Single-process entry point that composes config -> mesh -> data -> loop.
On the CPU container it runs reduced configs on the real device (or a
forced-host smoke mesh); on a real TPU slice the same file launches the
full config against the production mesh — only ``--mesh`` changes.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
      --reduced --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch rwkv6-1.6b \
      --reduced --steps 50 --mesh smoke   # 8 forced host devices
"""

from __future__ import annotations

import argparse
import dataclasses
import os


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--compress", action="store_true",
                    help="int8 gradient compression + error feedback")
    ap.add_argument("--mesh", default="none",
                    choices=("none", "smoke", "single", "multi"))
    args = ap.parse_args(argv)

    if args.mesh == "smoke":
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    elif args.mesh in ("single", "multi"):
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=512")

    # import after XLA_FLAGS so the device count sticks
    from repro.launch import compile_cache
    compile_cache.configure()
    from repro import configs
    from repro.data.lm import DataConfig, TokenStream
    from repro.launch import mesh as mesh_lib
    from repro.optim import AdamWConfig
    from repro.sharding import configure
    from repro.train.loop import LoopConfig, train

    cfg = (configs.reduced_config(args.arch) if args.reduced
           else configs.get_config(args.arch))
    if cfg.input_mode != "tokens":
        raise SystemExit(
            f"{args.arch} takes precomputed embeddings (modality stub); "
            "use examples/train_lm.py which wires the embedding stub")

    mesh = None
    if args.mesh == "smoke":
        mesh = mesh_lib.make_smoke_mesh()
    elif args.mesh != "none":
        mesh = mesh_lib.make_production_mesh(multi_pod=args.mesh == "multi")
    configure(mesh)

    ds = TokenStream(DataConfig(vocab=cfg.vocab, batch=args.batch,
                                seq_len=args.seq, seed=args.seed))
    loop_cfg = LoopConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every,
                          log_every=args.log_every)
    opt_cfg = AdamWConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                          decay_steps=max(args.steps, args.warmup + 1))

    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        res = train(cfg, ds.batch, loop_cfg, opt_cfg,
                    ckpt_dir=args.ckpt_dir, mesh=mesh, seed=args.seed,
                    compress=args.compress)
    first = res.losses[0] if res.losses else float("nan")
    last = res.losses[-1] if res.losses else float("nan")
    print(f"[train] done: {res.final_step} steps, loss {first:.4f} -> "
          f"{last:.4f}, {len(res.straggler_events)} straggler events, "
          f"{res.restarts} restarts")
    return 0


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    raise SystemExit(main())
