"""Batched serving example: continuous decode over a mixed request batch.

Serves a small RWKV6 model (O(1)-state decode — the long_500k story at
example scale): requests arrive with different prompt lengths, get bucketed
and prefilled, then decode proceeds as one fused batch with per-request
stop handling. Demonstrates the serve engine the dry-run lowers at
(prefill_32k / decode_32k / long_500k) scale.

    PYTHONPATH=src python examples/serve_lm.py --requests 6 --gen 24
"""

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T
from repro.serve import engine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-prompt", type=int, default=48)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.reduced_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    kp, kr = jax.random.split(key)
    params = T.init_model(kp, cfg)

    # mixed-length request batch: pad prompts left-aligned into one batch
    rng = np.random.default_rng(args.seed)
    lens = rng.integers(args.max_prompt // 3, args.max_prompt + 1,
                        args.requests)
    b = args.requests
    s = args.max_prompt
    toks = np.zeros((b, s), np.int32)
    for i, ln in enumerate(lens):
        toks[i, s - ln:] = rng.integers(0, cfg.vocab, ln)  # right-aligned

    slots = s + args.gen
    prefill = jax.jit(engine.make_prefill_step(cfg, cache_slots=slots))
    decode = jax.jit(engine.make_decode_step(cfg, args.temperature))

    print(f"[serve_lm] {cfg.name}: {b} requests, prompt lens {lens.tolist()}")
    t0 = time.time()
    logits, caches = prefill(params, {"tokens": jnp.asarray(toks)})
    tok = engine.sample_token(logits, kr, args.temperature)
    t_prefill = time.time() - t0

    outs = [tok]
    eos = cfg.vocab - 1
    done = np.zeros(b, bool)
    t0 = time.time()
    for i in range(args.gen - 1):
        kr, ks = jax.random.split(kr)
        pos = jnp.asarray(s + i, jnp.int32)
        tok, logits, caches = decode(params, caches, {"tokens": tok[:, None]},
                                     pos, ks)
        done |= np.asarray(tok) == eos        # per-request stop bookkeeping
        outs.append(tok)
        if done.all():
            break
    jax.block_until_ready(outs[-1])
    t_decode = time.time() - t0

    gen = np.stack([np.asarray(t) for t in outs], axis=1)
    n_steps = gen.shape[1]
    print(f"[serve_lm] prefill {t_prefill*1e3:.0f} ms; decode {n_steps} "
          f"steps in {t_decode*1e3:.0f} ms "
          f"({b*n_steps/max(t_decode,1e-9):.1f} tok/s batch throughput)")
    for i in range(min(b, 3)):
        print(f"  req {i} (prompt {lens[i]}): {gen[i, :10].tolist()}...")
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    print("[serve_lm] OK")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    main()
