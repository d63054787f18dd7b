"""Joint DP mixture of logistic experts (paper Sec. 4.2) on synthetic data.

CRP Gibbs for assignments + MH for alpha + subsampled MH for each expert's
weights — the inference program of paper Fig. 7 (top), expressed as a
composite cycle and run as K independent replicas on the multi-chain
ensemble engine (one jitted program advances every replica; the w-moves'
dynamic-pool austerity amortizes across replicas).

    PYTHONPATH=src python examples/dpmixture.py            # full size
    PYTHONPATH=src python examples/dpmixture.py --smoke    # CI-sized
"""
import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.experiments import jointdpm


def main(smoke: bool = False):
    cfg = jointdpm.JDPMConfig()
    if smoke:
        n, n_test, replicas, cycles, w_moves = 800, 200, 2, 8, 5
    else:
        n, n_test, replicas, cycles, w_moves = 4000, 1000, 4, 30, 10
    data = jointdpm.synth(jax.random.key(0), n=n, n_test=n_test)

    from repro.kernels import ops
    print(ops.dispatch_summary())
    print(f"jointDPM N={n}: {replicas} replicas x {cycles} cycles of "
          f"(mh-alpha, gibbs-z, {w_moves} subsampled-mh-w moves)")
    t0 = time.perf_counter()
    state, samples, infos, diag = jointdpm.run_posterior_ensemble(
        jax.random.key(2), data, cfg, num_chains=replicas, num_cycles=cycles,
        batch_size=100, epsilon=0.3, sigma_prop=0.3, w_moves=w_moves,
    )
    wall = time.perf_counter() - t0

    # posterior-predictive accuracy of each replica's final state
    accs = []
    for k in range(replicas):
        st_k = jax.tree.map(lambda l: l[k], state.theta)
        prob = jointdpm.predict_proba(st_k, data.x_test, cfg)
        accs.append(jointdpm.accuracy(np.asarray(prob), np.asarray(data.y_test)))
    print(f"  wall time          : {wall:.1f}s "
          f"({replicas * cycles / wall:.1f} cycles/sec aggregate)")
    print(f"  accuracy/replica   : {np.round(accs, 3)}")
    print(f"  active clusters    : {diag['k_active_final']}")
    print(f"  w accept rate      : {np.round(diag['w_accept_rate'], 2)}")
    print(f"  w sections touched : {diag['w_frac_evaluated']:.1%} of each expert's "
          f"members per move")


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (seconds instead of minutes)")
    main(smoke=ap.parse_args().smoke)
