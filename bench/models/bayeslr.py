"""BayesLR (arXiv 1411.1690 §4.1): the data, the system under test built
on it, and the plain reference that decides ``correct``.

Model: w ~ N(0, prior_var I_D), y_i ~ Logit(y | x_i . w), y in {-1, +1}.
The approximate MH transition of the paper (Alg. 3) with a random-walk
proposal, the sequential Student-t test of Alg. 2 over mini-batches of m
sections, and the ``stream`` sampler, whose transition t evaluates the
sections [0, n_t) in order (the pool is pre-permuted by construction:
its rows are i.i.d.).

The reference imports nothing of the program. It gets the data from this
module (made from the seed, and handed to the program as well), the
configuration's stated constants, and the chains' random inputs from the
documented step-key schedule (step t of chain c uses
``fold_in(split(key(seed), K)[c], t)``, split three ways into the
uniform, the proposal noise and the test key). It then replays each
checked transition in float64 numpy from the state the program held
before it.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy import stats as sstats

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Data: the MNIST-7-vs-9-like feature set of the paper's Fig. 4, synthesized
# at its shape (50 PCA-like dims with decaying variance), made on the device
# in one jitted call. A copy of the shape and scales of
# repro.experiments.bayeslr.synth_mnist_like, with the label matmul at full
# float32 precision so the data does not depend on the chip's matmul mode.
#
# Every seed gets the same data set (from the configuration's
# ``dataset_seed``) with its training rows in an order of its own: the
# stream sampler reads rows in order, so the seed changes which rows each
# test sees, but not the posterior and so not the amount of work.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("n_train", "n_test", "d"))
def _synth(key, order_key, n_train: int, n_test: int, d: int):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scales = 1.0 / jnp.sqrt(1.0 + jnp.arange(d, dtype=jnp.float32))
    w_true = jax.random.normal(k1, (d,)) * scales * 2.0
    x_train = jax.random.normal(k2, (n_train, d)) * scales
    x_test = jax.random.normal(k3, (n_test, d)) * scales
    p_train = jax.nn.sigmoid(jnp.dot(x_train, w_true, precision=HIGHEST))
    u = jax.random.uniform(k4, (n_train,))
    y_train = jnp.where(u < p_train, 1.0, -1.0).astype(jnp.float32)
    order = jax.random.permutation(order_key, n_train)
    return x_train[order], y_train[order], x_test


def make_data(cfg: dict, data_seed: int) -> dict:
    d = cfg["data"]
    x, y, xt = _synth(jax.random.key(d["dataset_seed"]), jax.random.key(data_seed),
                      n_train=d["n_train"], n_test=d["n_test"], d=d["d"])
    jax.block_until_ready(x)
    return {"x_train": x, "y_train": y, "x_test": xt}


# ---------------------------------------------------------------------------
# The system under test: the registered serving workload, over this data.
# ---------------------------------------------------------------------------


def build_pool(cfg: dict, data: dict, program_seed: int):
    """An ``EnsemblePool`` holding the configured workload on ``data``.
    Returns ``(pool, workload_name)``."""
    from repro.experiments.bayeslr import make_target
    from repro.serving import EnsemblePool, FreshnessPolicy, ServingConfig
    from repro.serving.workloads import build_serving_workload

    s = cfg["serving"]
    config = ServingConfig(
        num_chains=s["num_chains"], refresh_steps=s["refresh_steps"],
        window=s["window"], micro_batch=s["micro_batch"],
        max_batch=s["max_batch"],
        freshness=FreshnessPolicy(max_staleness_s=s["max_staleness_s"],
                                  min_draws=s["min_draws"]),
        default_deadline_s=s["default_deadline_s"],
        background_interval_s=s["background_interval_s"],
        seed=program_seed,
    )
    wl = build_serving_workload(cfg["workload"], seed=program_seed,
                                num_chains=s["num_chains"], **cfg["builder"])
    # The program's builder synthesizes its own copy of the data; the
    # benchmark serves the workload on the data it made itself, so that
    # the reference and the program read the same rows.
    target = make_target(data["x_train"], data["y_train"],
                         prior_var=cfg["data"]["prior_var"])
    wl = dataclasses.replace(
        wl, ensemble=dataclasses.replace(wl.ensemble, target=target))
    pool = EnsemblePool(config)
    pool.add_workload(wl)
    return pool, wl.name


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------


def _log_sigmoid(z):
    return -np.logaddexp(0.0, -z)


@functools.partial(jax.jit, static_argnames=("k", "d"))
def _chain_inputs(seed, chains, steps, k: int, d: int):
    """For each (chain, step) pair: the log of the f32 uniform and the
    proposal noise that the step-key schedule gives that transition."""
    chain_keys = jax.random.split(jax.random.key(seed), k)

    def one(c, t):
        k_u, k_prop, _ = jax.random.split(jax.random.fold_in(chain_keys[c], t), 3)
        log_u = jnp.log(jax.random.uniform(k_u, (), jnp.float32, 1e-20, 1.0))
        xi = jax.random.normal(jax.random.split(k_prop, 1)[0], (d,), jnp.float32)
        return log_u, xi

    return jax.vmap(one)(chains, steps)


def _test_pvalue(l_prefix: np.ndarray, mu0: float, n_total: int) -> float:
    """Alg. 2's two-sided t p-value on the first n values, with the
    finite-population correction; 0 when the pool is exhausted."""
    n = l_prefix.size
    sd = l_prefix.std(ddof=1) if n > 1 else 0.0
    corr = min(max(1.0 - (n - 1.0) / max(n_total - 1.0, 1.0), 0.0), 1.0)
    se = sd / np.sqrt(n) * np.sqrt(corr)
    if se <= 0:
        return 0.0
    t = abs(l_prefix.mean() - mu0) / se
    return float(2.0 * sstats.t.sf(t, df=max(n - 1, 1)))


class _Reference:
    """The transition of Alg. 3 in float64 numpy, from a given state."""

    def __init__(self, cfg: dict, host_data: dict, program_seed: int):
        sizes = cfg["data"]
        self.n_total, self.d = sizes["n_train"], sizes["d"]
        self.k = cfg["serving"]["num_chains"]
        self.m = cfg["builder"]["batch_size"]
        self.eps = cfg["builder"]["epsilon"]
        self.sigma = cfg["builder"]["sigma"]
        self.prior_var = sizes["prior_var"]
        self.seed = program_seed
        self.x = np.asarray(host_data["x_train"], np.float64)
        self.y = np.asarray(host_data["y_train"], np.float64)

    def proposals(self, prev, chains, steps):
        """The proposal and the test threshold mu0 of each transition."""
        log_u, xi = _chain_inputs(self.seed, np.asarray(chains, np.int32),
                                  np.asarray(steps, np.uint32), k=self.k, d=self.d)
        prop = prev + self.sigma * np.asarray(xi, np.float64)
        g = (-0.5 / self.prior_var) * (np.sum(prop**2, -1) - np.sum(prev**2, -1))
        mu0 = (np.asarray(log_u, np.float64) - g) / self.n_total
        return prop, mu0

    def deltas(self, prev, prop, n: int) -> np.ndarray:
        """l_i = log sig(y x.w') - log sig(y x.w) over the first n sections
        (the stream sampler's rows)."""
        xs, ys = self.x[:n], self.y[:n]
        return _log_sigmoid(ys * (xs @ prop)) - _log_sigmoid(ys * (xs @ prev))

    def walk(self, prev, prop, mu0) -> tuple[int, bool]:
        """Run the sequential test to its stop: (sections, accept)."""
        n = 0
        while True:
            n = min(n + self.m, self.n_total)
            l = self.deltas(prev, prop, n)
            if n >= self.n_total or _test_pvalue(l, mu0, self.n_total) < self.eps:
                return n, bool(l.mean() > mu0)


def check_refresh(cfg: dict, host_data: dict, rec: dict, program_seed: int,
                  rng: np.random.Generator) -> dict:
    """Replay the window's transitions against the reference.

    ``rec`` holds the window's blocks as the program committed them:
    ``theta_before`` (K, D), ``start_step``, ``draws`` (K, T, D) and the
    per-transition ``accepted``, ``n_evaluated``, ``mu_hat``, ``mu0``
    (K, T). Returns the compared numbers:

    * ``move_gap``: over every transition, the largest gap (in units of
      the proposal scale) between the committed state and the one the
      reference expects from the program's own decision: the reference's
      proposal if accepted, the previous state if not;
    * ``stat_gap``: over a sample of transitions drawn from the seed, the
      largest gap between the program's test statistic mu_hat - mu0 and
      the reference's over the same sections, in standard errors of the
      mean (s / sqrt(n));
    * ``stop_gap``: over the sample, how far (in log p-value) the
      reference's test disagrees with stopping where the program stopped
      and not one round earlier; infinite for a section count the test
      cannot produce;
    * ``decision_flips``: over the sample, transitions whose accept
      decision differs from the reference's mu > mu0 on the same sections.
    """
    ref = _Reference(cfg, host_data, program_seed)
    n_total, m, eps = ref.n_total, ref.m, ref.eps
    draws = np.asarray(rec["draws"], np.float64)  # (K, T, D)
    k, steps = draws.shape[:2]
    prev = np.concatenate([np.asarray(rec["theta_before"], np.float64)[:, None],
                           draws[:, :-1]], axis=1)
    chains = np.repeat(np.arange(k), steps)
    step_ids = rec["start_step"] + np.tile(np.arange(steps), k)
    prop, mu0_ref = ref.proposals(prev.reshape(k * steps, -1), chains, step_ids)
    prop, mu0_ref = prop.reshape(draws.shape), mu0_ref.reshape(k, steps)
    acc = np.asarray(rec["accepted"], bool)
    expected = np.where(acc[..., None], prop, prev)
    move_gap = float(np.max(np.abs(draws - expected)) / ref.sigma)

    n_eval = np.asarray(rec["n_evaluated"], np.int64)
    mu_hat = np.asarray(rec["mu_hat"], np.float64)
    mu0 = np.asarray(rec["mu0"], np.float64)
    n_check = min(int(cfg["check"]["transitions"]), k * steps)
    stat_gap = stop_gap = 0.0
    flips = 0
    for flat in rng.choice(k * steps, size=n_check, replace=False):
        c, t = divmod(int(flat), steps)
        n = int(n_eval[c, t])
        if n < 1 or n > n_total or (n < n_total and n % m):
            stop_gap = float("inf")
            continue
        l = ref.deltas(prev[c, t], prop[c, t], n)
        mean = l.mean()
        se = l.std(ddof=1) / np.sqrt(n) if n > 1 else np.inf
        stat = (mean - mu0_ref[c, t]) - (mu_hat[c, t] - mu0[c, t])
        stat_gap = max(stat_gap, abs(stat) / se if se > 0 else abs(stat))
        if n < n_total:
            p = _test_pvalue(l, mu0_ref[c, t], n_total)
            stop_gap = max(stop_gap, float(np.log(max(p, 1e-300) / eps)))
        if n > m:  # the round before: the test had to go on there
            p_prev = _test_pvalue(l[: (n - 1) // m * m], mu0_ref[c, t], n_total)
            stop_gap = max(stop_gap, float(np.log(eps / max(p_prev, 1e-300))))
        flips += int(bool(acc[c, t]) != bool(mean > mu0_ref[c, t]))
    return {
        "move_gap": move_gap,
        "stat_gap": float(stat_gap),
        "stop_gap": max(stop_gap, 0.0),
        "decision_flips": float(flips),
    }


def check_chains(cfg: dict, host_data: dict, windows: list[tuple[int, np.ndarray]],
                 program_seed: int, rng: np.random.Generator) -> dict:
    """Replay a sample of the transitions inside the snapshots that served
    queries, where only the draws are known: ``windows`` holds
    ``(steps_done, draws (K, W, D))`` per snapshot. A transition moved iff
    the program accepted it.

    * ``chain_move_gap``: the largest gap (in proposal scales) between a
      draw that moved and the reference's proposal;
    * ``chain_decision_flips``: transitions whose accept decision (moved
      or not) differs from the reference's sequential test run from the
      same state to its own stop.
    """
    ref = _Reference(cfg, host_data, program_seed)
    cases = []
    for steps_done, draws in windows:
        k, w = draws.shape[:2]
        cases += [(draws, steps_done - w + t, c, t) for c in range(k) for t in range(1, w)]
    if not cases:
        return {"chain_move_gap": float("inf"), "chain_decision_flips": float("inf")}
    n_check = min(int(cfg["check"]["chain_transitions"]), len(cases))
    picked = [cases[i] for i in rng.choice(len(cases), size=n_check, replace=False)]
    prev = np.stack([d[c, t - 1] for d, _, c, t in picked]).astype(np.float64)
    cur = np.stack([d[c, t] for d, _, c, t in picked]).astype(np.float64)
    prop, mu0 = ref.proposals(prev, [p[2] for p in picked], [p[1] for p in picked])
    moved = np.any(cur != prev, axis=-1)
    gap = np.where(moved[:, None], cur - prop, 0.0)
    flips = sum(int(moved[i] != ref.walk(prev[i], prop[i], mu0[i])[1])
                for i in range(len(picked)))
    return {"chain_move_gap": float(np.max(np.abs(gap)) / ref.sigma),
            "chain_decision_flips": float(flips)}


def reference_values(query_class: str, xs: np.ndarray, draws: np.ndarray,
                     dtype=np.float64) -> np.ndarray:
    """The served functionals over one snapshot's draws (S, D):
    ``predictive`` = mean of sigmoid(x . w), ``vote`` = share of draws with
    x . w > 0. ``dtype`` is the precision of the logits' operands."""
    if dtype is np.float64:
        z = np.asarray(xs, np.float64) @ np.asarray(draws, np.float64).T
    else:  # the control: operands rounded to ``dtype``, float32 accumulation
        z = np.asarray(
            jnp.dot(jnp.asarray(xs, dtype), jnp.asarray(draws, dtype).T,
                    preferred_element_type=jnp.float32), np.float64)
    if query_class == "predictive":
        return (1.0 / (1.0 + np.exp(-z))).mean(axis=1)
    if query_class == "vote":
        return (z > 0).mean(axis=1)
    raise KeyError(f"no reference for query class {query_class!r}")


def check_serve(cfg: dict, served: list[dict], control_dtype=None) -> dict:
    """Compare every served answer with the reference over the snapshot
    that served it. ``served`` holds, per request, ``query_class``, ``xs``,
    ``values`` (None if it never came) and ``draws`` ((S, D) or None if the
    snapshot is unknown). With ``control_dtype`` the reference computed
    in that precision stands in for the program's answers.

    * ``missing``: requests that never came back, failed, or whose
      snapshot is unknown;
    * ``predictive_gap`` / ``vote_gap``: the largest absolute gap between
      a served value and the reference's.
    """
    gaps = {"predictive": 0.0, "vote": 0.0}
    missing = 0
    for r in served:
        if r["values"] is None or r["draws"] is None:
            missing += 1
            continue
        ref = reference_values(r["query_class"], r["xs"], r["draws"])
        got = (reference_values(r["query_class"], r["xs"], r["draws"], control_dtype)
               if control_dtype is not None else np.asarray(r["values"], np.float64))
        if got.shape != ref.shape or not np.all(np.isfinite(got)):
            missing += 1
            continue
        gaps[r["query_class"]] = max(gaps[r["query_class"]],
                                     float(np.max(np.abs(got - ref))))
    return {"missing": float(missing), "predictive_gap": gaps["predictive"],
            "vote_gap": gaps["vote"]}
