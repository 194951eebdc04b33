"""Serving launcher: batched prefill + decode over static-shape caches.

Runs the published config (or, with --reduced, the smoke-scale one on the
CPU) through the serve engine: a batch of prompts is prefilled once, then
decoded token-by-token — the decode loop is the 1-D dependency-bound
recurrence of serving (the global-counter pattern at request scale). SSM/hybrid archs
decode with O(1) state; attention archs with ring-buffer KV caches.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --reduced \
      --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config (CPU-sized)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--cache-slots", type=int, default=0,
                    help="KV slots (0 = prompt+gen)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    compile_cache.configure()

    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.models import transformer as T
    from repro.serve import engine

    cfg = (configs.reduced_config(args.arch) if args.reduced
           else configs.get_config(args.arch))
    slots = args.cache_slots or (args.prompt_len + args.gen)

    key = jax.random.PRNGKey(args.seed)
    kp, kt, ks = jax.random.split(key, 3)
    params = T.init_model(kp, cfg)

    b, s = args.batch, args.prompt_len
    if cfg.input_mode == "embeds":
        batch = {"embeds": jax.random.normal(kt, (b, s, cfg.d_model),
                                             jnp.bfloat16)}
        step_inp = lambda tok: {"embeds": jax.random.normal(
            jax.random.fold_in(ks, 0), (b, 1, cfg.d_model), jnp.bfloat16)}
    else:
        batch = {"tokens": jax.random.randint(kt, (b, s), 0, cfg.vocab)}
        step_inp = lambda tok: {"tokens": tok[:, None]}

    prefill = jax.jit(engine.make_prefill_step(cfg, cache_slots=slots))
    decode = jax.jit(engine.make_decode_step(cfg, args.temperature))

    t0 = time.time()
    logits, caches = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0
    tok = engine.sample_token(logits)

    out_tokens = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        pos = jnp.asarray(s + i, jnp.int32)
        tok, logits, caches = decode(params, caches, step_inp(tok), pos)
        out_tokens.append(tok)
    jax.block_until_ready(out_tokens[-1])
    t_decode = time.time() - t0

    gen = jnp.stack(out_tokens, axis=1)
    print(f"[serve] arch={cfg.name} batch={b} prompt={s} gen={args.gen}")
    print(f"[serve] prefill: {t_prefill*1e3:.1f} ms "
          f"({b*s/max(t_prefill,1e-9):.0f} tok/s)")
    print(f"[serve] decode:  {t_decode*1e3:.1f} ms "
          f"({b*(args.gen-1)/max(t_decode,1e-9):.1f} tok/s)")
    print(f"[serve] sample row 0: {gen[0].tolist()}")
    assert bool(jnp.all(jnp.isfinite(logits))), "non-finite logits"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
