"""Batched serving driver: prefill + decode loop with a request queue.

Requests are batched up to --batch; each batch is prefended (prefill) and
decoded greedily for --gen tokens. Model weights can be restored from the
burst buffer (serving restarts read hot weights from server DRAM instead of
the PFS — the paper's restart path applied to inference)."""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, reduced
from repro.launch.jax_cache import use_compile_cache
from repro.models.registry import build_model
from repro.runtime.serve_step import greedy_token, make_decode_step, \
    make_prefill


def serve_batch(cfg, model, params, prompts, *, gen_tokens=16,
                max_seq=None, enc_input=None):
    """prompts: (B, S) int32 -> generated (B, gen_tokens)."""
    b, s = prompts.shape
    max_seq = max_seq or (s + gen_tokens)
    cache = model.init_cache(b, max_seq)
    prefill = jax.jit(make_prefill(cfg, model))
    decode = jax.jit(make_decode_step(cfg, model), donate_argnums=(1,))

    logits, cache = prefill(params, cache, prompts, enc_input)
    tok = greedy_token(cfg, logits)
    out = [tok]
    pos = s
    for i in range(gen_tokens - 1):
        logits, cache = decode(params, cache, tok, jnp.asarray(pos, jnp.int32))
        tok = greedy_token(cfg, logits)
        out.append(tok)
        pos += 1
    return jnp.concatenate(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="starcoder2-3b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=3)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    enc = None
    if cfg.encoder_seq:
        enc = jnp.asarray(rng.normal(
            0, 1, (args.batch, cfg.encoder_seq, cfg.encoder_dim)),
            jnp.float32)

    for r in range(args.requests):
        prompts = jnp.asarray(rng.integers(
            1, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)
        t0 = time.perf_counter()
        toks = serve_batch(cfg, model, params, prompts,
                           gen_tokens=args.gen, enc_input=enc)
        dt = time.perf_counter() - t0
        print(f"[serve] request-batch {r}: {toks.shape} in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s) "
              f"sample={np.asarray(toks[0, :8]).tolist()}")


if __name__ == "__main__":
    main()
