"""Serving driver: batched autoregressive generation (the sampler's decode
loop as a standalone service — WALL-E experience collection in isolation).

CPU-runnable with reduced archs:
  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b-reduced \
      --batch 4 --prompt-len 16 --gen-len 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.envs import lm_env
from repro.launch import compile_cache
from repro.models import transformer


def generate(cfg, params, prompt, gen_len: int, key, temperature=1.0):
    state, logits = transformer.prefill(cfg, params, prompt,
                                        gen_budget=gen_len)

    def body(carry, key_t):
        state, logits = carry
        tok = jax.random.categorical(key_t, logits / temperature)
        state, logits2 = transformer.decode_step(cfg, params, state,
                                                 tok[:, None])
        return (state, logits2), tok

    keys = jax.random.split(key, gen_len)
    (_, _), toks = jax.lax.scan(body, (state, logits), keys)
    return toks.T                                        # (B, gen_len)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b-reduced")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(cfg, key)
    env = lm_env.make(cfg.vocab_size, episode_len=args.gen_len)
    gen = jax.jit(lambda p, t, k: generate(cfg, p, t, args.gen_len, k))

    for r in range(args.requests):
        key, kp, kg = jax.random.split(key, 3)
        prompt = jax.random.randint(kp, (args.batch, args.prompt_len), 0,
                                    cfg.vocab_size)
        t0 = time.perf_counter()
        toks = jax.block_until_ready(gen(params, prompt, kg))
        dt = time.perf_counter() - t0
        rew = env.token_rewards(toks).sum(axis=1)
        tps = args.batch * args.gen_len / dt
        print(f"request {r}: {toks.shape[1]} tokens x {toks.shape[0]} seqs "
              f"in {dt:.2f}s ({tps:.0f} tok/s), "
              f"mean reward {float(rew.mean()):.2f}")


if __name__ == "__main__":
    main()
