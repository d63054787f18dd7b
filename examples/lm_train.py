"""End-to-end driver: train a small LM, then run Bayesian inference over a
parameter block with subsampled MH (hybrid inference: SGD substrate + MH,
the paper's "interoperates with other general-purpose inference").

Phase 1 — Adam on Markov-chain synthetic data for a few hundred steps
          (loss curve printed).
Phase 2 — subsampled-MH posterior sampling over the final-norm block with
          the trained weights as the likelihood backbone; reports acceptance,
          fraction of the pool evaluated per transition, and the exact-MH
          comparison.

    PYTHONPATH=src python examples/lm_train.py            # ~8M params
    PYTHONPATH=src python examples/lm_train.py --preset 100m --steps 300
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.bayes import TrainConfig, make_exact_step, make_train_step
from repro.checkpoint import manager as ckpt
from repro.data import DataConfig, MarkovStream
from repro.models import init_params
from repro.models.transformer import ModelConfig
from repro.optim import adam_init, adam_step, lm_loss_fn
from repro.runtime import LoopConfig, run_loop

PRESETS = {
    "small": ModelConfig(name="lm-small", family="dense", n_layers=4, d_model=256,
                         n_heads=8, n_kv=4, d_ff=1024, vocab=2048, max_seq=256),
    "100m": ModelConfig(name="lm-100m", family="dense", n_layers=12, d_model=768,
                        n_heads=12, n_kv=12, d_ff=3072, vocab=8192, max_seq=1024),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="small", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--mh-steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="artifacts/lm_train_ckpt")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    print(f"model: {cfg.name} ({cfg.param_count() / 1e6:.1f}M params)")
    params = init_params(jax.random.key(0), cfg)
    data = DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch, seed=0)
    stream = MarkovStream(data, concentration=0.2)

    # ---- Phase 1: Adam substrate ------------------------------------------
    loss_fn = lm_loss_fn(cfg)
    vg = jax.jit(jax.value_and_grad(loss_fn))
    opt = adam_init(params)
    t0 = time.perf_counter()
    for step in range(args.steps):
        loss, grads = vg(params, stream.batch(step))
        params, opt = adam_step(grads, opt, params, lr=2e-3)
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"  adam step {step:4d}: loss/token={float(loss):.4f} "
                  f"t={time.perf_counter() - t0:.0f}s")
    ckpt.save(args.ckpt_dir, args.steps, params)
    print(f"checkpoint saved to {args.ckpt_dir}")

    # ---- Phase 2: subsampled MH over the final-norm block ------------------
    print("\nBayesian block inference (subsampled MH over 'final_norm'):")
    pool_batch = stream.batch(10_001)  # held-out pool of sequences
    for name, maker, tc in [
        ("subsampled", make_train_step,
         TrainConfig(round_batch=4, epsilon=0.05, sigma=5e-3,
                     propose_paths=("final_norm",))),
        ("exact", make_exact_step,
         TrainConfig(round_batch=4, sigma=5e-3, propose_paths=("final_norm",))),
    ]:
        step_fn = jax.jit(maker(cfg, tc))
        th = params
        acc, n_eval, t0 = [], [], time.perf_counter()
        for i in range(args.mh_steps):
            th, info = step_fn(jax.random.fold_in(jax.random.key(7), i), th, pool_batch)
        jax.block_until_ready(jax.tree.leaves(th)[0])
        wall = time.perf_counter() - t0
        # re-run collecting stats (cheap; jit cached)
        th = params
        for i in range(args.mh_steps):
            th, info = step_fn(jax.random.fold_in(jax.random.key(7), i), th, pool_batch)
            acc.append(bool(info.accepted))
            n_eval.append(int(info.n_evaluated))
        print(f"  {name:10s}: acceptance={np.mean(acc):.2f} "
              f"sections/transition={np.mean(n_eval):.1f}/{args.batch} "
              f"wall={wall:.1f}s ({1e3 * wall / args.mh_steps:.0f} ms/transition)")


if __name__ == "__main__":
    compile_cache.enable()
    main()
