"""Batched serving demo: prefill a prompt batch, then greedy-decode.

    PYTHONPATH=src python examples/serve_lm.py --arch mamba2-1.3b --smoke \
        --prompt-len 24 --gen 16 --batch 4

Exercises the production serve path (prefill -> cache -> decode_step) for
any of the 10 architectures, including the attention-free SSM/RG-LRU caches.
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import CPU_CTX, init_params, prefill
from repro.train.step import make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    params = init_params(cfg, jax.random.key(0), jnp.float32)
    rng = np.random.default_rng(0)
    B, S = args.batch, args.prompt_len
    max_len = S + args.gen
    if cfg.n_codebooks:
        prompt = rng.integers(0, cfg.vocab, (B, S, cfg.n_codebooks))
    else:
        prompt = rng.integers(0, cfg.vocab, (B, S))
    batch = {"tokens": jnp.asarray(prompt, jnp.int32)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jnp.asarray(
            rng.normal(size=(B, min(cfg.n_img_tokens, S // 2), cfg.d_model)),
            jnp.float32)

    t0 = time.time()
    logits, cache = prefill(params, batch, cfg, CPU_CTX, max_len=max_len)
    print(f"prefill[{B}x{S}] {time.time() - t0:.2f}s")

    serve = jax.jit(make_serve_step(cfg, CPU_CTX), donate_argnums=(1,))
    generated = []
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # greedy
    if cfg.n_codebooks:
        tok = tok.reshape(B, 1, cfg.n_codebooks)
    else:
        tok = tok.reshape(B, 1)
    t0 = time.time()
    for t in range(S, max_len):
        generated.append(np.asarray(tok)[:, 0])
        logits, cache = serve(params, cache, {"tokens": tok}, jnp.int32(t))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        tok = tok.reshape(B, 1, cfg.n_codebooks) if cfg.n_codebooks \
            else tok.reshape(B, 1)
    dt = (time.time() - t0) / args.gen
    print(f"decode: {args.gen} steps, {dt * 1e3:.1f} ms/token/batch")
    gen = np.stack(generated, axis=1)
    print("generated token ids (row 0):", gen[0].reshape(args.gen, -1)[:, 0])


if __name__ == "__main__":
    main()
