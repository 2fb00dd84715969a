"""Serve the (cloud) model: prefill a batch of prompts, then decode with a
KV cache — the serving path the decode/prefill dry-run cells lower.

    PYTHONPATH=src python examples/serve_lm.py --batch 4 --gen 24
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs.paper import LM_100M
from repro.models import transformer


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = dataclasses.replace(
        LM_100M, name="lm-serve", num_layers=4, d_model=256, num_heads=8,
        num_kv_heads=4, d_ff=768, vocab_size=512,
    )
    params = transformer.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    B, P = args.batch, args.prompt_len
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(B, P)), jnp.int32)
    max_len = P + args.gen

    prefill = jax.jit(lambda p, x: transformer.prefill(p, cfg, x, max_len))
    decode = jax.jit(lambda p, c, t, pos: transformer.decode_step(p, cfg, c, t, pos))

    t0 = time.time()
    logits, caches = prefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    key = jax.random.PRNGKey(1)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for t in range(args.gen - 1):
        pos = jnp.full((B,), P + t, jnp.int32)
        logits, caches = decode(params, caches, tok, pos)
        key, sub = jax.random.split(key)
        tok = jax.random.categorical(sub, logits / args.temperature, axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    gen = jnp.stack(out, axis=1)
    print(f"prefill: {B}×{P} tokens in {t_prefill*1e3:.0f} ms "
          f"({B*P/t_prefill:.0f} tok/s)")
    print(f"decode : {B}×{args.gen-1} tokens in {t_decode*1e3:.0f} ms "
          f"({B*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    for b in range(min(B, 2)):
        print(f"request {b}: prompt={np.asarray(prompts[b])[:8]}... -> "
              f"generated={np.asarray(gen[b])[:12]}...")


if __name__ == "__main__":
    main()
