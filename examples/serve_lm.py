"""Posterior-predictive serving: batched prefill + decode from a parameter
sample (checkpoint or fresh init).

    PYTHONPATH=src python examples/serve_lm.py --arch chatglm3-6b --reduced
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import ARCHS, reduce_config
from repro.models import decode_step, init_params, prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3-6b", choices=list(ARCHS))
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = reduce_config(cfg)
    print(f"serving {cfg.name}: batch={args.batch} prompt={args.prompt_len} "
          f"gen={args.gen_len}")
    params = init_params(jax.random.key(0), cfg)
    prompts = jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab
    )
    extra = None
    if cfg.family == "audio":
        extra = {"frames": 0.1 * jax.random.normal(
            jax.random.key(2), (args.batch, cfg.n_audio_frames, cfg.d_model),
            jnp.bfloat16)}

    max_len = args.prompt_len + args.gen_len + 8
    jprefill = jax.jit(lambda p, t: prefill(p, t, cfg, max_len, extra))
    jdecode = jax.jit(lambda p, c, t: decode_step(p, c, t, cfg))

    t0 = time.perf_counter()
    cache, logits = jprefill(params, prompts)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    key = jax.random.key(3)
    tokens = []
    tok = jnp.argmax(logits, -1)[:, None]
    t0 = time.perf_counter()
    for i in range(args.gen_len):
        key, sub = jax.random.split(key)
        cache, logits = jdecode(params, cache, tok)
        tok = jax.random.categorical(sub, logits / args.temperature, axis=-1)[:, None]
        tokens.append(np.asarray(tok[:, 0]))
    jax.block_until_ready(logits)
    t_decode = time.perf_counter() - t0

    gen = np.stack(tokens, 1)
    print(f"prefill: {t_prefill:.2f}s  "
          f"({args.batch * args.prompt_len / t_prefill:.0f} tok/s)")
    print(f"decode : {t_decode:.2f}s  "
          f"({args.batch * args.gen_len / t_decode:.0f} tok/s, "
          f"{1e3 * t_decode / args.gen_len:.1f} ms/step)")
    print(f"sample token ids (request 0): {gen[0][:16]}")


if __name__ == "__main__":
    compile_cache.enable()
    main()
