"""Stochastic volatility: joint state + parameter estimation (paper Sec 4.3).

Particle Gibbs (conditional SMC) samples the latent log-volatility paths;
subsampled MH samples (phi, sigma^2) with *dependent* local sections (the
h-transition factors). The whole program — pgibbs sweep cycled with the two
parameter moves — runs as a composite cycle on the multi-chain ensemble
engine: K chains advance inside one jitted program and the parameter moves'
sequential-test rounds evaluate (K, m) blocks through the fused
``gaussian_ar1`` kernel family when dispatch selects it.

    PYTHONPATH=src python examples/stochastic_volatility.py            # full size
    PYTHONPATH=src python examples/stochastic_volatility.py --smoke    # CI-sized
"""
import argparse
import time

import jax
import numpy as np

from repro import compile_cache
from repro.experiments import stochvol


def main(smoke: bool = False):
    true_phi, true_sigma = 0.95, 0.1
    if smoke:
        series, length, chains, iters, particles = 60, 5, 2, 60, 10
    else:
        series, length, chains, iters, particles = 200, 5, 4, 400, 25
    data = stochvol.synth(jax.random.key(0), num_series=series, length=length,
                          phi=true_phi, sigma=true_sigma)
    n = data.obs.size

    from repro.kernels import ops
    print(ops.dispatch_summary()
          + f" sweep={stochvol.resolve_sweep()}")
    print(f"stochvol S={series} T={length} ({n} transition factors): "
          f"{chains} chains x {iters} cycles of (pgibbs, mh-phi, mh-sigma2)")
    t0 = time.perf_counter()
    state, samples, infos, diag = stochvol.run_posterior_ensemble(
        jax.random.key(1), data, num_chains=chains, num_steps=iters,
        batch_size=100, epsilon=0.01, num_particles=particles,
    )
    wall = time.perf_counter() - t0

    burn = iters // 3
    phis = np.asarray(samples["phi"])[:, burn:]
    sigmas = np.sqrt(np.asarray(samples["sigma2"])[:, burn:])
    print(f"  wall time        : {wall:.1f}s "
          f"({chains * iters / wall:.0f} cycles/sec aggregate)")
    print(f"  posterior phi    : {phis.mean():.3f} ± {phis.std():.3f} (true {true_phi})")
    print(f"  posterior sigma  : {sigmas.mean():.3f} ± {sigmas.std():.3f} (true {true_sigma})")
    print(f"  split R-hat      : phi={diag['rhat_phi']:.3f} "
          f"sigma2={diag['rhat_sigma2']:.3f}")
    frac = diag["frac_evaluated"]
    print(f"  sections touched : phi={frac['phi']:.1%} sigma2={frac['sigma2']:.1%} "
          f"of {n} transition factors per move")


if __name__ == "__main__":
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (seconds instead of minutes)")
    main(smoke=ap.parse_args().smoke)
