"""A composite-cycle model for the tests of ``bench/harness/refresh.py``: the stochastic
volatility program of arXiv 1411.1690 §4.3 as the program serves it (a
particle-Gibbs sweep over the latent paths h, then per-variable
subsampled-MH moves on phi and sigma^2 whose sections are the S x T
transition factors of the current paths), its data, and a plain
reference of the two MH moves. Test-only: no cell names it.

    x_t = exp(h_t / 2) eps_t,  h_t ~ N(phi h_{t-1}, sigma^2),  h_0 = 0
    phi ~ Beta(5, 1),          sigma^2 ~ InvGamma(5, 0.05)

The reference imports nothing of the program. It takes the configuration's
constants, the documented key schedule (step t of chain c uses
``fold_in(split(key(seed), K)[c], t)``, split once per component of the
cycle; an MH move's key splits three ways into the uniform, the proposal
noise and the test key; round r of the move splits the test key in two,
goes on with the first half and draws the round's m sections with the
second) and the states the program committed: the collected (phi,
sigma^2) draws, and from ``after_block`` the state at both ends of
seed-chosen blocks. The paths h at a block's end are what its last phi
and sigma^2 moves read. Their sections, in order, are the reference's
own: for ``stream`` the first n of the one-time permutation, for ``fy``
the partial Fisher-Yates walk of every move of the block, replayed from
the key schedule on the index array at the block's start (which has to
be a permutation of the sections). The check replays in float64 numpy;
the particle-Gibbs sweep itself is not checked.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy import special
from scipy import stats as sstats

#: The cycle's components, in order; the MH moves and their proposal scales.
COMPONENTS = ("pgibbs", "phi", "sigma2")
MOVES = {"phi": "sigma_phi", "sigma2": "sigma_sig"}
#: ``after_block`` keeps the state at both ends of two of the window's
#: blocks 1..AMONG, so the check can replay those blocks.
KEEP, AMONG = 2, 3


# ---------------------------------------------------------------------------
# Data: S series of length T from the model at (phi, sigma), one jitted call.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("s", "t"))
def _synth(key, phi, sigma, s: int, t: int):
    k1, k2 = jax.random.split(key)
    eps_h = jax.random.normal(k1, (t, s)) * sigma

    def step(h_prev, e):
        h = phi * h_prev + e
        return h, h

    _, h = jax.lax.scan(step, jnp.zeros((s,)), eps_h)
    return jnp.exp(h.T / 2.0) * jax.random.normal(k2, (s, t))


def make_data(cfg: dict, data_seed: int) -> dict:
    d = cfg["data"]
    obs = _synth(jax.random.key(data_seed), d["phi"], d["sigma"],
                 s=d["num_series"], t=d["length"])
    jax.block_until_ready(obs)
    return {"obs": obs}


def _permute_key(program_seed: int):
    """The key of the ``stream`` sampler's one-time pool order."""
    return jax.random.fold_in(jax.random.key(program_seed), 1)


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_pool(cfg: dict, data: dict, program_seed: int):
    """An ``EnsemblePool`` serving the program's ``stochvol`` workload, its
    cycle rebuilt on this data with the configured sampler."""
    from repro.experiments import stochvol
    from repro.serving import EnsemblePool, FreshnessPolicy, ServingConfig
    from repro.serving.workloads import build_serving_workload

    s, b, d = cfg["serving"], cfg["builder"], cfg["data"]
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
    wl = build_serving_workload(
        "stochvol", seed=program_seed, num_chains=s["num_chains"],
        num_series=d["num_series"], length=d["length"],
        num_particles=b["num_particles"], batch_size=b["batch_size"],
        epsilon=b["epsilon"])
    stream = b["sampler"] == "stream"
    cyc = stochvol.make_inference_cycle(
        data["obs"], batch_size=b["batch_size"], epsilon=b["epsilon"],
        sigma_phi=b["sigma_phi"], sigma_sig=b["sigma_sig"],
        num_particles=b["num_particles"], sampler=b["sampler"],
        permute_key=_permute_key(program_seed) if stream else None)
    wl = dataclasses.replace(
        wl, ensemble=dataclasses.replace(wl.ensemble, transition=cyc),
        theta0=stochvol.init_theta(data["obs"]))
    pool = EnsemblePool(config)
    pool.add_workload(wl)
    return pool, wl.name


def sizes(cfg: dict) -> dict:
    """Each MH move's sections (the S x T transition factors) and the
    float32 values one section reads (h_t and h_{t-1})."""
    n = cfg["data"]["num_series"] * cfg["data"]["length"]
    return {"ops": {op: {"num_sections": n, "width": 2} for op in MOVES}}


def draw_scale(cfg: dict) -> dict:
    """Each collected leaf's proposal scale (the ``altered_draw`` fault's
    unit)."""
    return {op: cfg["builder"][key] for op, key in MOVES.items()}


def after_block(resident, block_index: int, rng: np.random.Generator):
    """The ensemble's state after the block, where the block ends or
    starts one of two seed-chosen blocks among 1..AMONG (device arrays,
    not pulled)."""
    ends = 1 + rng.choice(AMONG, size=KEEP, replace=False)
    if block_index in set(ends) | set(ends - 1):
        return resident.state
    return None


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------


def _log_prior(phi, s2):
    """Beta(5, 1) on phi plus InvGamma(5, 0.05) on sigma^2, float64."""
    if not (0.0 < phi < 1.0) or s2 <= 0.0:
        return -np.inf
    a, b = 5.0, 0.05
    return (4.0 * np.log(phi) + np.log(5.0)
            + a * np.log(b) - special.gammaln(a) - (a + 1) * np.log(s2) - b / s2)


@functools.partial(jax.jit, static_argnames=("k",))
def _move_inputs(seed, chains, steps, k: int):
    """For each (chain, step): the log-uniform and the proposal noise of
    the phi and sigma^2 moves, shape (n, 2)."""
    chain_keys = jax.random.split(jax.random.key(seed), k)

    def one(c, t):
        subs = jax.random.split(jax.random.fold_in(chain_keys[c], t), len(COMPONENTS))

        def move(key):
            k_u, k_prop, _ = jax.random.split(key, 3)
            log_u = jnp.log(jax.random.uniform(k_u, (), jnp.float32, 1e-20, 1.0))
            return log_u, jax.random.normal(k_prop, (), jnp.float32)

        return jax.vmap(move)(subs[1:])

    return jax.vmap(one)(chains, steps)


@functools.partial(jax.jit, static_argnames=("k", "rounds", "m", "n"))
def _fy_targets(seed, chains, steps, k: int, rounds: int, m: int, n: int):
    """For each (chain, step): the swap target of every position of the
    first ``rounds`` rounds of the phi and sigma^2 moves' Fisher-Yates
    walks, shape (2, rounds, m). Round r fills positions r m .. r m + m - 1
    (clamped to n - 1), each swapped with a uniform pick from itself to
    the pool's end."""
    chain_keys = jax.random.split(jax.random.key(seed), k)
    pos = jnp.minimum(jnp.arange(rounds)[:, None] * m + jnp.arange(m)[None], n - 1)
    span = jnp.maximum(n - pos, 1)

    def one(c, t):
        subs = jax.random.split(jax.random.fold_in(chain_keys[c], t), len(COMPONENTS))

        def move(key):
            def round_(tk, _):
                tkey, sub = jax.random.split(tk)
                return tkey, jax.random.split(sub, m)

            _, keys = jax.lax.scan(round_, jax.random.split(key, 3)[2], None, length=rounds)
            pick = jax.vmap(jax.vmap(lambda kk, sp: jax.random.randint(
                kk, (), 0, sp, dtype=jnp.int32)))(keys, span)
            return jnp.minimum(pos + pick, n - 1)

        return jax.vmap(move)(subs[1:])

    return jax.vmap(one)(chains, steps)


def fy_walk(seed: int, idx0: np.ndarray, first_step: int, rounds: np.ndarray,
            m: int) -> list[np.ndarray]:
    """Replay the Fisher-Yates walks of the phi and sigma^2 moves of steps
    ``first_step`` .. ``first_step + T - 1`` from each chain's index array
    ``idx0`` (K, 2, N) at the start, ``rounds`` (K, T, 2) rounds per move.
    Returns, per step, the (K, 2, N) arrays after the step's moves: the
    first n entries of a move's array are the sections it evaluated, in
    order."""
    k, _, n = idx0.shape
    t_len = rounds.shape[1]
    r_max = max(int(rounds.max()), 1)
    chains = np.repeat(np.arange(k), t_len).astype(np.int32)
    steps = (first_step + np.tile(np.arange(t_len), k)).astype(np.uint32)
    targets = np.asarray(_fy_targets(seed, chains, steps, k=k, rounds=r_max, m=m, n=n))
    targets = targets.reshape(k, t_len, 2, r_max, m)
    pos = np.minimum(np.arange(r_max)[:, None] * m + np.arange(m)[None], n - 1)
    walk = [[list(idx0[c, j]) for j in range(2)] for c in range(k)]
    out = []
    for t in range(t_len):
        for c in range(k):
            for j in range(2):
                a = walk[c][j]
                for p, q in zip(pos[: rounds[c, t, j]].ravel().tolist(),
                                targets[c, t, j, : rounds[c, t, j]].ravel().tolist()):
                    a[p], a[q] = a[q], a[p]
        out.append(np.array(walk, np.int64))
    return out


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


def _ar1(ht, hp, phi, s2):
    s2 = max(s2, 1e-12)
    return -0.5 * ((ht - phi * hp) ** 2 / s2 + np.log(s2))


def _replay(sections, cur, prop, n_eval, rounds, info, m, eps, n_total) -> dict:
    """One MH move from ``cur`` to ``prop`` ((phi, sigma^2) pairs) over the
    (h_t, h_{t-1}) pairs ``sections`` in the sampler's order, against what
    the program reported for it (``n_eval`` sections in ``rounds``
    rounds)."""
    log_u, mu0, mu_hat, accepted = info
    g = _log_prior(*prop) - _log_prior(*cur)
    if not np.isfinite(g):  # out of support: mu0 is infinite, a sure reject
        ok = not accepted and np.isinf(mu0)
        return {"stat": 0.0 if ok else np.inf, "stop": 0.0, "flip": 0}
    mu0_ref = (log_u - g) / n_total
    if n_eval < 1 or n_eval > n_total or n_eval != min(rounds * m, n_total):
        return {"stat": 0.0, "stop": np.inf, "flip": 0}
    ht, hp = sections[:, :n_eval]
    l = _ar1(ht, hp, *prop) - _ar1(ht, hp, *cur)
    mean = l.mean()
    se = l.std(ddof=1) / np.sqrt(n_eval) if n_eval > 1 else np.inf
    stat = (mean - mu0_ref) - (mu_hat - mu0)
    stop = 0.0
    if n_eval < n_total:
        stop = np.log(max(_test_pvalue(l, mu0_ref, n_total), 1e-300) / eps)
    if n_eval > m:  # the round before: the test had to go on there
        p_prev = _test_pvalue(l[: (n_eval - 1) // m * m], mu0_ref, n_total)
        stop = max(stop, np.log(eps / max(p_prev, 1e-300)))
    return {"stat": abs(stat) / se if se > 0 else abs(stat), "stop": stop,
            "flip": int(bool(accepted) != bool(mean > mu0_ref))}


def check_refresh(cfg: dict, host_data: dict, rec: dict, program_seed: int,
                  rng: np.random.Generator) -> dict:
    """Replay the window's MH moves against the reference.

    * ``move_gap``: over every transition of the window and both moves,
      the largest gap (in units of the move's proposal scale) between the
      committed phi or sigma^2 and the one the reference expects from the
      program's own decision: its proposal if accepted, else the value
      before;
    * ``stat_gap``, ``stop_gap``, ``decision_flips``: the phi and sigma^2
      moves of the last cycle of each block kept at both ends, replayed
      from the paths h at its end and the sections the sampler draws, as
      for BayesLR's refresh; infinite ``stat_gap`` when no block was kept
      at both ends, or when an ``fy`` index array at a block's start is no
      permutation of the sections.
    """
    del host_data, rng  # the kept paths are what the moves read
    b = cfg["builder"]
    k = cfg["serving"]["num_chains"]
    steps = cfg["serving"]["refresh_steps"]
    n_total = cfg["data"]["num_series"] * cfg["data"]["length"]
    m, eps = b["batch_size"], b["epsilon"]
    scale = {op: b[key] for op, key in MOVES.items()}
    now = {op: np.asarray(rec["draws"][op], np.float64) for op in MOVES}
    before = {op: np.concatenate([np.asarray(rec["theta_before"][op], np.float64)[:, None],
                                  now[op][:, :-1]], axis=1) for op in MOVES}
    t_len = now["phi"].shape[1]
    chains = np.repeat(np.arange(k), t_len).astype(np.int32)
    step_ids = (rec["start_step"] + np.tile(np.arange(t_len), k)).astype(np.uint32)
    log_u, xi = (np.asarray(a, np.float64).reshape(k, t_len, 2)
                 for a in _move_inputs(program_seed, chains, step_ids, k=k))
    move_gap = 0.0
    for j, op in enumerate(MOVES):
        acc = np.asarray(rec["ops"][op]["accepted"], bool)
        expected = np.where(acc, before[op] + scale[op] * xi[..., j], before[op])
        move_gap = max(move_gap, float(np.max(np.abs(now[op] - expected)) / scale[op]))

    perm = None
    if b["sampler"] == "stream":
        perm = np.asarray(jax.random.permutation(_permute_key(program_seed), n_total))
    kept = dict(rec["kept"])
    ends = [blk for blk in sorted(kept) if blk - 1 in kept]
    stat_gap = np.inf if not ends else 0.0
    stop_gap, flips = 0.0, 0
    mh = [COMPONENTS.index(op) for op in MOVES]
    for block in ends:
        first, t = block * steps, (block + 1) * steps - 1  # t: the block's last cycle
        rounds = np.stack([np.asarray(rec["ops"][op]["rounds"])[:, first:t + 1]
                           for op in MOVES], axis=-1)
        if perm is None:
            idx0 = np.stack([np.asarray(kept[block - 1].sampler_state[i].idx) for i in mh], 1)
            if not (np.sort(idx0, axis=-1) == np.arange(n_total)).all():
                stat_gap = np.inf
                continue
            walked = fy_walk(program_seed, idx0, rec["start_step"] + first, rounds, m)[-1]
        h = np.asarray(kept[block].theta["h"], np.float64)  # (K, S, T)
        h_prev = np.concatenate([np.zeros(h.shape[:2] + (1,)), h[..., :-1]], axis=-1)
        for c in range(k):
            pairs = np.stack([h[c].reshape(-1), h_prev[c].reshape(-1)])
            if perm is not None:
                pairs = pairs[:, perm]
            phi0, s20 = before["phi"][c, t], before["sigma2"][c, t]
            phi1 = now["phi"][c, t]
            moves = {"phi": ((phi0, s20), (phi0 + scale["phi"] * xi[c, t, 0], s20)),
                     "sigma2": ((phi1, s20), (phi1, s20 + scale["sigma2"] * xi[c, t, 1]))}
            for j, (op, (cur, prop)) in enumerate(moves.items()):
                info = rec["ops"][op]
                n_eval = int(info["n_evaluated"][c, t])
                order = (np.arange(n_eval) if perm is not None
                         else walked[c, j, :max(n_eval, 0)])
                r = _replay(pairs[:, order], cur, prop, n_eval, int(rounds[c, -1, j]),
                            (log_u[c, t, j], float(info["mu0"][c, t]),
                             float(info["mu_hat"][c, t]), info["accepted"][c, t]),
                            m, eps, n_total)
                stat_gap = max(stat_gap, r["stat"])
                stop_gap = max(stop_gap, r["stop"])
                flips += r["flip"]
    return {"move_gap": move_gap, "stat_gap": float(stat_gap),
            "stop_gap": float(max(stop_gap, 0.0)), "decision_flips": float(flips)}
