"""Quickstart: sublinear-time MH on Bayesian logistic regression.

Runs the paper's core comparison on synthetic data in ~a minute on CPU:
exact MH (O(N) per transition) vs subsampled MH (Alg. 3), plus the Sec-3.3
normality safeguard report.

    python examples/quickstart.py            # full-size (~a minute on CPU)
    python examples/quickstart.py --smoke    # CI-sized
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.core import (
    RandomWalk,
    SubsampledMHConfig,
    run_chain,
    trial_run_report,
)
from repro.experiments import bayeslr


def main(smoke: bool = False):
    n, d, steps = (5_000, 10, 100) if smoke else (50_000, 50, 400)
    data = bayeslr.synth_mnist_like(jax.random.key(0), n_train=n, n_test=1000, d=d)
    target = bayeslr.make_target(data.x_train, data.y_train)
    w0 = jnp.zeros(d)
    prop = RandomWalk(0.03)

    from repro.kernels import ops
    print(ops.dispatch_summary())
    print(f"Bayesian logistic regression, N={n}, D={d} (paper Sec 4.1 scale)")
    print("\n--- Sec 3.3 safeguard (trial run) ---")
    print(trial_run_report(jax.random.key(1), w0, target, prop, num_trials=10))

    results = {}
    m = 200 if smoke else 1000
    for kernel, cfg in [
        ("exact", None),
        ("subsampled", SubsampledMHConfig(batch_size=m, epsilon=0.05, sampler="stream")),
    ]:
        t0 = time.perf_counter()
        _, samples, infos = run_chain(
            jax.random.key(2), w0, target, prop, steps, kernel=kernel, config=cfg
        )
        jax.block_until_ready(samples)
        wall = time.perf_counter() - t0
        w = np.asarray(samples)[steps // 2:]
        results[kernel] = (w, infos, wall)
        print(f"\n--- {kernel} MH ({steps} transitions) ---")
        print(f"  wall time          : {wall:.2f}s ({1e3 * wall / steps:.2f} ms/transition)")
        print(f"  posterior mean w[:4]: {w.mean(0)[:4]}")
        print(f"  acceptance rate    : {np.mean(np.asarray(infos.accepted)):.2f}")
        print(f"  sections evaluated : {np.mean(np.asarray(infos.n_evaluated)):.0f} / {n} "
              f"({np.mean(np.asarray(infos.n_evaluated)) / n:.1%})")

    we, _, te = results["exact"]
    ws, _, ts = results["subsampled"]
    print("\n--- comparison ---")
    print(f"  posterior-mean gap : {np.linalg.norm(we.mean(0) - ws.mean(0)):.4f}")
    print(f"  speedup            : {te / ts:.2f}x wall-clock at equal transitions")


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (seconds instead of minutes)")
    main(smoke=ap.parse_args().smoke)
