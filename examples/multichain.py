"""Multi-chain quickstart: a K-chain ensemble on Bayesian logistic regression.

One jitted program advances all chains; cross-chain split-R-hat and ESS come
out of repro.core.stats. The run uses the adaptive masked-continuation
engine (stepping="masked" + ScheduleConfig): chains whose sequential test
stops early start their next transition inside the same compiled loop, and
each chain tunes its batch-size bucket and epsilon from its own trailing
test statistics. Compare examples/quickstart.py, which runs the same model
one chain at a time, and docs/ARCHITECTURE.md for how the pieces fit.

    python examples/multichain.py            # full-size (~minutes on CPU)
    python examples/multichain.py --smoke    # CI-sized, tens of seconds
"""
import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.core import ScheduleConfig
from repro.experiments import bayeslr


def main(smoke: bool = False):
    if smoke:
        n, d, chains, steps = 2_000, 4, 8, 200
    else:
        n, d, chains, steps = 20_000, 8, 16, 1200
    data = bayeslr.synth_mnist_like(jax.random.key(0), n_train=n, n_test=500, d=d)

    from repro.kernels import ops
    print(ops.dispatch_summary())
    print(f"BayesLR N={n}, D={d}: {chains} subsampled-MH chains x {steps} steps "
          f"(masked-continuation + adaptive scheduling)")
    t0 = time.perf_counter()
    samples, diag = bayeslr.run_posterior_ensemble(
        jax.random.key(1), data, num_chains=chains, num_steps=steps,
        batch_size=500, epsilon=0.05, sigma=0.04, overdisperse=0.2,
        stepping="masked", schedule=ScheduleConfig(),
    )
    wall = time.perf_counter() - t0

    w = samples[:, steps // 2:]  # (K, T/2, D)
    err = bayeslr.test_error(w.reshape(-1, d).mean(0),
                             np.asarray(data.x_test), np.asarray(data.y_test))
    tail = diag["rounds_tail"]
    print(f"  wall time            : {wall:.1f}s "
          f"({chains * steps / wall:.0f} transitions/sec aggregate)")
    print(f"  split R-hat (max dim): {np.max(diag['rhat']):.3f}")
    print(f"  total ESS of w[0]    : {diag['ess_w0']:.0f}")
    print(f"  acceptance per chain : {np.round(diag['accept_rate'], 2)}")
    print(f"  sections evaluated   : {diag['mean_n_evaluated_overall']:.0f} / {n} "
          f"({diag['mean_n_evaluated_overall'] / n:.1%} of data per transition)")
    print(f"  test rounds          : p50={tail['p50']:.0f} p99={tail['p99']:.0f} "
          f"max={tail['max']:.0f} (the lock-step engine would pay the max, per row)")
    print(f"  adapted epsilon      : {np.round(diag['final_epsilon'], 3)}")
    print(f"  adapted batch size   : {np.asarray(diag['final_batch_eff'], int)}")
    print(f"  posterior-mean test error: {err:.3f}")


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (seconds instead of minutes)")
    main(smoke=ap.parse_args().smoke)
