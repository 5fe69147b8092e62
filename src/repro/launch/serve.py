"""Serving driver: batched prefill + decode of an (assembled) model.

  PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
      --reduced --batch 4 --prompt-len 32 --decode-steps 16

Serves the post-training construction [F_C_agg ; F_S] (paper Sec. 3.3):
greedy decode over a batch of requests with a KV/SSM cache.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs import get_config, reduced
from repro.launch.compile_cache import enable_compilation_cache
from repro.models import layers, model as M


def build_serving_fns(cfg, compute_dtype=jnp.float32):
    def prefill(params, tokens):
        b, s = tokens.shape
        cache = M.init_body_cache(cfg, b, s + 512, compute_dtype)
        h = M.embed_tokens(params, tokens, cfg, dtype=compute_dtype)
        positions = layers.positions_from_shape(b, s)
        enc_out = cross_kv = None
        h, cache, _ = M.forward_body(params, h, cfg, positions=positions,
                                     cache=cache, cross_kv=cross_kv,
                                     remat=False)
        logits = M.lm_logits(params, h[:, -1:], cfg)
        return logits, cache

    def decode(params, cache, tokens, positions):
        h = M.embed_tokens(params, tokens, cfg, positions=positions,
                           dtype=compute_dtype)
        h, cache, _ = M.forward_body(params, h, cfg, positions=positions,
                                     cache=cache, remat=False)
        logits = M.lm_logits(params, h, cfg)
        return logits, cache

    return jax.jit(prefill), jax.jit(decode, donate_argnums=(1,))


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="minitron-4b")
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--decode-steps", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--obs-log", default=None,
                   help="write a JSONL telemetry run log to this path")
    args = p.parse_args(argv)
    enable_compilation_cache()

    log = obs.get_logger("serve")
    if args.obs_log:
        obs.configure(args.obs_log,
                      meta={"driver": "serve", "arch": args.arch,
                            "batch": args.batch,
                            "prompt_len": args.prompt_len,
                            "decode_steps": args.decode_steps})

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    key = jax.random.PRNGKey(args.seed)
    params = M.init_lm(key, cfg)

    prefill, decode = build_serving_fns(cfg)
    tokens = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.time()
    logits, cache = prefill(params, tokens)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for i in range(args.decode_steps):
        pos = jnp.full((args.batch, 1), args.prompt_len + i, jnp.int32)
        logits, cache = decode(params, cache, tok, pos)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok)[:, 0])
    jax.block_until_ready(logits)
    t_decode = time.time() - t0

    gen = np.stack(out, axis=1)
    log.info(f"batch={args.batch} prefill({args.prompt_len} tok)="
             f"{t_prefill*1e3:.1f}ms decode={args.decode_steps} steps in "
             f"{t_decode*1e3:.1f}ms "
             f"({t_decode/args.decode_steps*1e3:.1f} ms/tok)",
             prefill_ms=round(t_prefill * 1e3, 2),
             decode_ms=round(t_decode * 1e3, 2),
             ms_per_tok=round(t_decode / args.decode_steps * 1e3, 2))
    log.info(f"sample generations (token ids): {gen[:2].tolist()}")
    if args.obs_log:
        obs.shutdown()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
