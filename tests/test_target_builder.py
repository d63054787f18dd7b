"""Kernel-family registry / target builder and the composite cycle engine."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ChainEnsemble,
    RandomWalk,
    ScheduleConfig,
    SubsampledMHConfig,
    SubsampledMHOp,
    SweepOp,
    build_target,
    cycle,
    get_family,
    registered_families,
    run_cycle_sequential,
)

CFG = SubsampledMHConfig(batch_size=50, epsilon=0.05)


def _logit_data(n=300, d=3, seed=0):
    k1, k2 = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(k1, (n, d))
    y = jnp.where(jax.random.bernoulli(k2, 0.5, (n,)), 1.0, -1.0)
    return x, y


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_builtin_families_registered():
    assert set(registered_families()) >= {"logit", "gaussian_ar1", "ce"}
    assert get_family("logit").name == "logit"
    with pytest.raises(KeyError):
        get_family("nope")


def test_build_target_validation():
    x, y = _logit_data()
    with pytest.raises(ValueError):
        build_target("logit", (x, y), None, prior_logpdf=lambda w: 0.0)
    with pytest.raises(ValueError):
        build_target("logit", (x, y), 300)  # no prior_logpdf / log_global
    with pytest.raises(ValueError):
        build_target(None, num_sections=300, log_global=lambda a, b: 0.0)
    with pytest.raises(KeyError):
        build_target("nope", (x, y), 300, prior_logpdf=lambda w: 0.0)


# ---------------------------------------------------------------------------
# logit family
# ---------------------------------------------------------------------------


def test_logit_family_matches_hand_target():
    x, y = _logit_data()
    prior_var = 0.1
    t = build_target("logit", (x, y), x.shape[0],
                     prior_logpdf=lambda w: (-0.5 / prior_var) * jnp.sum(w**2))
    assert t.family == "logit" and t.num_sections == 300
    w0, w1 = jnp.zeros(3), jnp.asarray([0.4, -0.2, 0.1])
    idx = jnp.arange(60, dtype=jnp.int32)
    hand_local = (-jnp.logaddexp(0, -y[idx] * (x[idx] @ w1))
                  + jnp.logaddexp(0, -y[idx] * (x[idx] @ w0)))
    np.testing.assert_allclose(np.asarray(t.log_local(w0, w1, idx)),
                               np.asarray(hand_local), rtol=1e-5, atol=1e-6)
    hand_global = (-0.5 / prior_var) * (jnp.sum(w1**2) - jnp.sum(w0**2))
    np.testing.assert_allclose(float(t.log_global(w0, w1)), float(hand_global), rtol=1e-5)
    hand_density = ((-0.5 / prior_var) * jnp.sum(w1**2)
                    - jnp.logaddexp(0, -y * (x @ w1)).sum())
    np.testing.assert_allclose(float(t.log_density(w1)), float(hand_density), rtol=1e-5)


def test_logit_family_ensemble_matches_vmapped_local_bit_for_bit():
    """Acceptance criterion: the fused-path (ref dispatch on CPU) ensemble
    round equals the vmapped unfused evaluation bit for bit."""
    x, y = _logit_data()
    t = build_target("logit", (x, y), x.shape[0], prior_logpdf=lambda w: 0.0)
    K, m = 5, 32
    ks = jax.random.split(jax.random.key(1), 3)
    wc = jax.random.normal(ks[0], (K, 3))
    wp = jax.random.normal(ks[1], (K, 3))
    idx = jax.random.randint(ks[2], (K, m), 0, 300)
    vmapped = jax.jit(lambda a, b, i: jax.vmap(t.log_local)(a, b, i))(wc, wp, idx)
    fused = jax.jit(t.log_local_ensemble)(wc, wp, idx)
    np.testing.assert_array_equal(np.asarray(vmapped), np.asarray(fused))


# ---------------------------------------------------------------------------
# gaussian_ar1 family
# ---------------------------------------------------------------------------


def test_gaussian_ar1_family_matches_transition_logpdf_delta():
    from repro.experiments.stochvol import _trans_logpdf

    n = 200
    k1, k2 = jax.random.split(jax.random.key(3))
    xt = jax.random.normal(k1, (n,))
    xp = jax.random.normal(k2, (n,))
    t = build_target(
        "gaussian_ar1", (xt, xp), n,
        prior_logpdf=lambda th: jnp.zeros(()),
        params_fn=lambda th: (th["phi"], th["sigma2"]),
    )
    th0 = {"phi": jnp.asarray(0.9), "sigma2": jnp.asarray(0.05)}
    th1 = {"phi": jnp.asarray(0.8), "sigma2": jnp.asarray(0.07)}
    idx = jnp.arange(80, dtype=jnp.int32)
    want = (_trans_logpdf(xt[idx], xp[idx], th1["phi"], th1["sigma2"])
            - _trans_logpdf(xt[idx], xp[idx], th0["phi"], th0["sigma2"]))
    np.testing.assert_allclose(np.asarray(t.log_local(th0, th1, idx)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)


def test_gaussian_ar1_latent_dependent_data_fn():
    """Callable data: sections derived from theta (the stochvol ensemble
    form) must agree with the closure-based target on the same h."""
    from repro.experiments import stochvol

    data = stochvol.synth(jax.random.key(4), num_series=20, length=5)
    closure = stochvol.make_param_target(data.h_true, "phi")
    joint = stochvol.make_joint_param_target(20, 5)
    th0 = {"phi": jnp.asarray(0.9), "sigma2": jnp.asarray(0.02), "h": data.h_true}
    th1 = {"phi": jnp.asarray(0.85), "sigma2": jnp.asarray(0.03), "h": data.h_true}
    idx = jnp.arange(100, dtype=jnp.int32)
    np.testing.assert_allclose(
        np.asarray(joint.log_local(th0, th1, idx)),
        np.asarray(closure.log_local(th0, th1, idx)), rtol=1e-6, atol=1e-7,
    )
    # ensemble form: a (K, 2) chain axis over theta, per-chain h
    K = 2
    b = lambda v: jnp.broadcast_to(jnp.asarray(v)[None], (K,) + jnp.shape(jnp.asarray(v)))
    thb0 = {k: b(v) for k, v in th0.items()}
    thb1 = {k: b(v) for k, v in th1.items()}
    idxb = jnp.stack([idx, idx + 1])
    fused = joint.log_local_ensemble(thb0, thb1, idxb)
    for c in range(K):
        np.testing.assert_allclose(
            np.asarray(fused[c]),
            np.asarray(joint.log_local(th0, th1, idxb[c])), rtol=1e-5, atol=1e-6,
        )


# ---------------------------------------------------------------------------
# ce family
# ---------------------------------------------------------------------------


def test_ce_family_delta_and_ensemble():
    from repro.kernels.ref import fused_ce_ref

    n, d, v = 60, 8, 30
    ks = jax.random.split(jax.random.key(5), 4)
    h = 0.3 * jax.random.normal(ks[0], (n, d))
    targets = jax.random.randint(ks[1], (n,), 0, v)
    t = build_target("ce", (h, targets), n, prior_logpdf=lambda tab: jnp.zeros(()))
    tab0 = 0.3 * jax.random.normal(ks[2], (v, d))
    tab1 = 0.3 * jax.random.normal(ks[3], (v, d))
    idx = jnp.arange(40, dtype=jnp.int32)
    want = (fused_ce_ref(h[idx], tab1, targets[idx])
            - fused_ce_ref(h[idx], tab0, targets[idx]))
    np.testing.assert_allclose(np.asarray(t.log_local(tab0, tab1, idx)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    K, m = 3, 16
    idxb = jax.random.randint(jax.random.key(6), (K, m), 0, n)
    tabs0 = jnp.stack([tab0] * K)
    tabs1 = jnp.stack([tab1] * K)
    fused = t.log_local_ensemble(tabs0, tabs1, idxb)
    vmapped = jax.vmap(t.log_local, in_axes=(0, 0, 0))(tabs0, tabs1, idxb)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(vmapped),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Composite cycle engine
# ---------------------------------------------------------------------------


def test_cycle_validation():
    x, y = _logit_data()
    t = build_target("logit", (x, y), 300, prior_logpdf=lambda w: 0.0)
    with pytest.raises(ValueError):
        cycle([])
    with pytest.raises(TypeError):
        cycle([lambda k, th: th])
    with pytest.raises(ValueError):
        cycle([SubsampledMHOp(t, RandomWalk(0.1), name="a"),
               SweepOp(lambda k, th: th, name="a")])


def test_ensemble_composite_validation(gaussian_target_factory):
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    cyc = cycle([SubsampledMHOp(target, RandomWalk(0.05), CFG)])
    with pytest.raises(ValueError):
        ChainEnsemble(target, RandomWalk(0.05), 2, transition=cyc)
    with pytest.raises(ValueError):
        ChainEnsemble(num_chains=2, transition=cyc, stepping="masked")
    with pytest.raises(ValueError):
        ChainEnsemble(num_chains=2, transition=cyc, schedule=ScheduleConfig())
    with pytest.raises(ValueError):
        ChainEnsemble(num_chains=2, transition=cyc, shard=True)
    with pytest.raises(ValueError):
        ChainEnsemble(num_chains=2)  # neither target nor transition
    with pytest.raises(ValueError):
        # forcing the fused route on a composite whose MH target has no
        # ensemble evaluation must fail loudly, not silently run unfused
        ChainEnsemble(num_chains=2, transition=cyc, fused_kernels="always")
    with pytest.raises(ValueError):
        # kernel/config are per-component knobs in a composite; the
        # ensemble-level ones would be silently ignored
        ChainEnsemble(num_chains=2, transition=cyc, kernel="exact")
    with pytest.raises(ValueError):
        ChainEnsemble(num_chains=2, transition=cyc, config=CFG)
    x, y = _logit_data()
    fam_t = build_target("logit", (x, y), 300, prior_logpdf=lambda w: 0.0)
    with pytest.raises(ValueError):
        # shard=True demands the sharded vmapped scan; "always" demands the
        # unsharded fused scan — contradictory, rejected at construction
        ChainEnsemble(fam_t, RandomWalk(0.1), 2, fused_kernels="always", shard=True)


def test_cycle_of_one_kernel_equals_bare_kernel(gaussian_target_factory):
    """Determinism: cycle([op]) == the bare kernel ensemble, bit for bit."""
    target, _, _ = gaussian_target_factory(n=600, seed=1)
    K, T = 3, 60
    keys = jax.random.split(jax.random.key(7), K)
    bare = ChainEnsemble(target, RandomWalk(0.05), K, config=CFG)
    comp = ChainEnsemble(num_chains=K, transition=cycle(
        [SubsampledMHOp(target, RandomWalk(0.05), CFG, name="theta")]))
    _, s_b, i_b = bare.run(keys, bare.init(jnp.zeros(())), T)
    _, s_c, i_c = comp.run(keys, comp.init(jnp.zeros(())), T)
    np.testing.assert_array_equal(np.asarray(s_b), np.asarray(s_c))
    for field in ("accepted", "n_evaluated", "rounds", "mu_hat", "mu0", "log_u"):
        np.testing.assert_array_equal(
            np.asarray(getattr(i_b, field)),
            np.asarray(getattr(i_c["theta"], field)), err_msg=field)


def test_composite_k1_matches_sequential_cycle(gaussian_target_factory):
    """A K=1 composite ensemble (MH op + opaque sweep with info) reproduces
    run_cycle_sequential bit for bit."""
    target, _, _ = gaussian_target_factory(n=600, seed=1)

    def sweep(key, th):
        return th + 0.01 * jax.random.normal(key, ()), {"noise": th}

    cyc = cycle([SubsampledMHOp(target, RandomWalk(0.05), CFG, name="mh"),
                 SweepOp(sweep, name="jitter", has_info=True)])
    ens = ChainEnsemble(num_chains=1, transition=cyc)
    keys = jax.random.split(jax.random.key(11), 1)
    _, s_e, i_e = ens.run(keys, ens.init(jnp.zeros(())), 40)
    _, s_q, i_q = run_cycle_sequential(keys[0], jnp.zeros(()), cyc, 40)
    np.testing.assert_array_equal(np.asarray(s_e[0]), np.asarray(s_q))
    np.testing.assert_array_equal(np.asarray(i_e["mh"].accepted[0]),
                                  np.asarray(i_q["mh"].accepted))
    np.testing.assert_array_equal(np.asarray(i_e["jitter"]["noise"][0]),
                                  np.asarray(i_q["jitter"]["noise"]))


# ---------------------------------------------------------------------------
# Fused lock-step scan
# ---------------------------------------------------------------------------


def test_lockstep_fused_path_matches_vmap():
    """Acceptance criterion: the lock-step scan routes rounds through
    log_local_ensemble when dispatch selects the fused path, in parity with
    the unfused scan."""
    x, y = _logit_data(n=800, d=2, seed=9)
    t = build_target("logit", (x, y), 800,
                     prior_logpdf=lambda w: -5.0 * jnp.sum(w**2))
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05, sampler="stream")
    K, T = 3, 40
    keys = jax.random.split(jax.random.key(5), K)
    plain = ChainEnsemble(t, RandomWalk(0.1), K, config=cfg, fused_kernels="never")
    fused = ChainEnsemble(t, RandomWalk(0.1), K, config=cfg, fused_kernels="always")
    _, s_p, i_p = plain.run(keys, plain.init(jnp.zeros(2)), T)
    _, s_f, i_f = fused.run(keys, fused.init(jnp.zeros(2)), T)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_f), rtol=2e-4, atol=2e-5)
    assert (np.asarray(i_p.accepted) == np.asarray(i_f.accepted)).mean() > 0.95


def test_lockstep_fused_with_schedule_stays_in_bounds():
    """The fused lock-step scan composes with the adaptive controller."""
    x, y = _logit_data(n=600, d=2, seed=13)
    t = build_target("logit", (x, y), 600,
                     prior_logpdf=lambda w: -5.0 * jnp.sum(w**2))
    cfg = SubsampledMHConfig(batch_size=50, epsilon=0.05, sampler="stream")
    sched = ScheduleConfig(epsilon_max=0.2)
    ens = ChainEnsemble(t, RandomWalk(0.1), 3, config=cfg,
                        fused_kernels="always", schedule=sched)
    state, samples, infos = ens.run(jax.random.key(0), ens.init(jnp.zeros(2)), 50)
    eps = np.asarray(infos.epsilon)
    assert samples.shape == (3, 50, 2)
    assert eps.min() >= cfg.epsilon - 1e-7 and eps.max() <= 0.2 + 1e-7
    assert set(np.unique(np.asarray(infos.batch_eff)).tolist()) <= set(
        sched.buckets_for(cfg, 600))
    assert np.asarray(state.controller.t).tolist() == [50, 50, 50]

# ---------------------------------------------------------------------------
# Stream windows: a round's contiguous sections read as one slice per chain
# ---------------------------------------------------------------------------


def _family_case(name, k, n=40):
    """(data, params, params_p, section_ndims) of a registered family, with
    a (k,) chain axis on the params."""
    ks = jax.random.split(jax.random.key(21), 6)
    if name == "logit":
        data = _logit_data(n=n, d=3, seed=2)
        p = [0.3 * jax.random.normal(kk, (k, 3)) for kk in ks[:2]]
        return data, p[0], p[1], (1, 0)
    if name == "gaussian_ar1":
        data = tuple(jax.random.normal(kk, (n,)) for kk in ks[:2])
        params = lambda kk: (0.2 + 0.5 * jax.random.uniform(kk, (k,)),
                             0.5 + jax.random.uniform(jax.random.fold_in(kk, 1), (k,)))
        return data, params(ks[2]), params(ks[3]), (0, 0)
    if name == "ce":
        data = (0.3 * jax.random.normal(ks[0], (n, 8)),
                jax.random.randint(ks[1], (n,), 0, 30))
        tab = lambda kk: 0.3 * jax.random.normal(kk, (k, 30, 8))
        return data, tab(ks[2]), tab(ks[3]), (1, 0)
    assert name == "gaussian_mean"
    data = jax.random.normal(ks[0], (n, 2))
    return data, jax.random.normal(ks[2], (k, 2)), jax.random.normal(ks[3], (k, 2)), (1,)


@pytest.mark.parametrize("pool", ["shared", "per_chain"])
@pytest.mark.parametrize("name", ["logit", "gaussian_ar1", "ce", "gaussian_mean"])
def test_window_reads_the_same_sections_as_its_index_block(name, pool):
    """A Window equals the index block it stands for, bit for bit: the
    gather of every pool leaf, the fused (K, m) round and the vmapped
    single-chain delta of every registered family."""
    from repro.core.target import Window
    from repro.core.target_builder import _gather

    assert set(registered_families()) == {"logit", "gaussian_ar1", "ce", "gaussian_mean"}
    k, m, n = 3, 9, 40
    data, p, p_prop, ndims = _family_case(name, k, n)
    if pool == "per_chain":  # each chain its own (rotated) copy of the pool
        data = jax.tree.map(
            lambda a: jnp.stack([jnp.roll(a, 5 * c, axis=0) for c in range(k)]), data)
    starts = jnp.asarray([0, 17, n - m], jnp.int32)
    window = Window(starts, m)
    idx = starts[:, None] + jnp.arange(m, dtype=jnp.int32)
    for leaf, ndim in zip(jax.tree.leaves(data), ndims):
        np.testing.assert_array_equal(np.asarray(_gather(leaf, window, ndim)),
                                      np.asarray(_gather(leaf, idx, ndim)))
    fam = get_family(name)
    np.testing.assert_array_equal(
        np.asarray(fam.ensemble_delta(data, p, p_prop, window)),
        np.asarray(fam.ensemble_delta(data, p, p_prop, idx)))
    axes = (0 if pool == "per_chain" else None, 0, 0, 0)
    vmapped = jax.vmap(fam.delta, in_axes=axes)
    np.testing.assert_array_equal(
        np.asarray(vmapped(data, p, p_prop, window)),
        np.asarray(vmapped(data, p, p_prop, idx)))


def test_only_builder_closures_read_windows():
    """The builder marks its own ``log_local`` as reading windows; a
    hand-wired one (the PPL's graph evaluator, say) keeps index blocks."""
    x, y = _logit_data(n=50)
    prior = lambda w: -0.5 * jnp.sum(w**2)
    assert build_target("logit", (x, y), 50, prior_logpdf=prior).local_window
    hand = build_target("logit", (x, y), 50, prior_logpdf=prior,
                        log_local=lambda a, b, idx: jnp.zeros(idx.shape))
    assert not hand.local_window and hand.log_local_ensemble is not None


_CALLEES = (r"(?:calls|to_apply|body|condition|branch_computations)="
            r"(?:\{([^}]*)\}|%?([\w.\-]+))")


def _pool_reads(hlo: str, root: str) -> list[tuple]:
    """The reads in computation ``root`` of compiled HLO text and in the
    computations it calls: ``("gather", index rows, slice_sizes)`` and
    ``("dynamic-slice", sizes)``."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    out, seen, todo = [], set(), [root]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            for group, single in re.findall(_CALLEES, line):
                todo += [c.strip().lstrip("%") for c in (group or single).split(",")]
            sizes = lambda text: tuple(int(v) for v in text.split(","))
            g = re.search(r"=\s*\w+\[(\d+)[\d,]*\][^=]*\sgather\(.*slice_sizes=\{([\d,]*)\}",
                          line)
            if g:
                out.append(("gather", int(g.group(1)), sizes(g.group(2))))
            d = re.search(r"\sdynamic-slice\(.*dynamic_slice_sizes=\{([\d,]*)\}", line)
            if d:
                out.append(("dynamic-slice", sizes(d.group(1))))
    return out


@pytest.mark.parametrize("fused", ["never", "always"])
def test_stream_round_reads_one_window_per_chain(fused):
    """At the refresh cell's shape (K = 8, m = 500, N = 12,214, D = 50) the
    window branch of a lock-step ``stream`` round reads the pool as K slices
    of m sections, for the rows (m, D) and the labels (m,), and gathers
    nothing; the element gathers (K * m index rows of one section) stay in
    the other branch, the round where a chain reaches the pool's end."""
    n, d, k, m = 12214, 50, 8, 500
    x, y = _logit_data(n=n, d=d, seed=3)
    t = build_target("logit", (x, y), n, prior_logpdf=lambda w: -5.0 * jnp.sum(w**2))
    cfg = SubsampledMHConfig(batch_size=m, epsilon=0.05, sampler="stream")
    ens = ChainEnsemble(t, RandomWalk(0.05), k, config=cfg, fused_kernels=fused,
                        shard=("chains", "data"))  # one device: the batched scan
    state = ens.init(jnp.zeros(d))
    hlo = ens.lower(state, 1, step_keys=ens.step_keys(jax.random.key(0), 0, 1)
                    ).compile().as_text()
    (branches,) = re.findall(
        r"\sconditional\(.*branch_computations=\{%?([\w.\-]+), %?([\w.\-]+)\}", hlo)
    on_index, on_window = (sorted(_pool_reads(hlo, b)) for b in branches)
    assert on_window == sorted([("dynamic-slice", (m,))] * k
                               + [("dynamic-slice", (m, d))] * k), on_window
    assert on_index == [("gather", k * m, (1,)), ("gather", k * m, (1, d))], on_index
