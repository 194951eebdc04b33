"""End-to-end LM training driver: train a ~100M-parameter model.

Trains an RWKV6-family model (the paper-technique core path: every
recurrent layer runs the chunked Squire scan) on the deterministic
synthetic LM stream, with checkpointing, resume, straggler watchdog and
the full loop machinery. Loss decreases from ~ln(V) toward the stream's
conditional entropy.

Presets:
  * ``--preset 100m`` — 12L/768d/~105M params (the brief's end-to-end
    driver; a few hundred steps; hours on CPU, minutes on accelerators).
  * ``--preset 20m``  — 6L/384d/~20M params (CPU-friendly default).
  * ``--preset 3m``   — 4L/128d (CI smoke).

    PYTHONPATH=src python examples/train_lm.py --preset 20m --steps 300
"""

import argparse

import jax

from repro.configs.base import LayerSpec, ModelConfig
from repro.data.lm import DataConfig, TokenStream
from repro.models import transformer as T
from repro.optim import AdamWConfig
from repro.train.loop import LoopConfig, train

PRESETS = {
    "100m": dict(num_layers=12, d_model=768, d_ff=2688, vocab=8192,
                 batch=8, seq=256),
    "20m": dict(num_layers=6, d_model=384, d_ff=1344, vocab=1024,
                batch=8, seq=128),
    "3m": dict(num_layers=4, d_model=128, d_ff=448, vocab=256,
               batch=8, seq=64),
}


def make_config(p) -> ModelConfig:
    return ModelConfig(
        name=f"rwkv6-train-{p['d_model']}d", family="ssm",
        num_layers=p["num_layers"], d_model=p["d_model"],
        num_heads=p["d_model"] // 64, num_kv_heads=p["d_model"] // 64,
        head_dim=64, d_ff=p["d_ff"], vocab=p["vocab"],
        pattern=(LayerSpec(mixer="rwkv", mlp="rwkv_ffn"),),
        rwkv_head_dim=64, subquadratic=True, remat=False)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = make_config(p)
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    n_params = T.param_count(params)
    del params
    print(f"[train_lm] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"batch={p['batch']} seq={p['seq']} vocab={p['vocab']}")

    ds = TokenStream(DataConfig(vocab=cfg.vocab, batch=p["batch"],
                                seq_len=p["seq"], seed=args.seed))
    res = train(
        cfg, ds.batch,
        LoopConfig(total_steps=args.steps, ckpt_every=max(args.steps // 4, 1),
                   log_every=args.log_every),
        AdamWConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 10),
                    decay_steps=args.steps),
        ckpt_dir=args.ckpt_dir, seed=args.seed)

    first, last = res.losses[0], res.losses[-1]
    print(f"\n[train_lm] loss {first:.4f} -> {last:.4f} over "
          f"{res.final_step} steps "
          f"({(first - last):.3f} nats improvement)")
    if args.steps >= 100:
        assert last < first - 0.2, "training did not reduce loss"
        print("[train_lm] OK: loss decreased")
    else:
        print("[train_lm] (short run: loss-decrease assertion needs "
              ">=100 steps)")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    main()
