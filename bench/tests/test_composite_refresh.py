"""``bench/harness/refresh.py`` runs a composite-cycle ensemble (a particle-Gibbs
sweep and two per-variable subsampled-MH moves, ``sv_cycle_model``):
draws as a pytree, infos per operator, sizes from the model, and the
states that ``after_block`` keeps, which the check replays. The planted
faults a refresh cell can have on one chip (``frozen``, ``half``,
``altered_draw``) come out as not correct."""
import argparse

import numpy as np
import pytest

import sv_cycle_model
from bench import run as bench_run
from bench.harness import faults, spec
from bench.harness.context import derive_seeds

K, STEPS, S, T = 4, 4, 8, 16
CONFIG = {
    "name": "sv_cycle_s8_t16",
    "model": "sv_cycle",
    "matmul_precision": "highest",
    "data": {"num_series": S, "length": T, "phi": 0.95, "sigma": 0.1},
    "builder": {"batch_size": 32, "epsilon": 0.05, "sigma_phi": 0.02,
                "sigma_sig": 0.003, "num_particles": 8, "sampler": "fy"},
    "serving": {"num_chains": K, "refresh_steps": STEPS, "window": STEPS,
                "micro_batch": 8, "max_batch": 4, "default_deadline_s": 1.0,
                "max_staleness_s": 30.0, "min_draws": STEPS,
                "background_interval_s": 0.0},
    "limits": {"refresh": {"move_gap": 1e-4, "stat_gap": 0.05, "stop_gap": 0.25,
                           "decision_flips": 0}},
    "rehearsal": {},
}
MH_FIELDS = {"accepted", "n_evaluated", "rounds", "mu_hat", "mu0", "pvalue",
             "log_u", "epsilon", "batch_eff"}


def _metrics(*names):
    return tuple(spec.Metric(n, {"unit": spec.metric_reader(n).UNIT},
                             spec.metric_reader(n)) for n in names)


SEED = 2**31 + 29


def _run(sampler="fy", fault=None, seed=SEED, trace=0):
    """One rehearsal of the composite cell; returns the result, the rec the
    check got and what the traffic kind's ``run`` returned."""
    cfg = dict(CONFIG, builder=dict(CONFIG["builder"], sampler=sampler))
    cell = spec.Cell(
        name="sv_cycle_s8_t16.refresh", chips=1, config=cfg,
        traffic={"kind": "refresh", "name": "refresh"}, model=sv_cycle_model,
        end_to_end=_metrics("setup_s", "transitions_per_s"),
        per_layer=_metrics("rounds_per_transition", "frac_data_touched"))
    seen = {}

    def hook(drive):
        def capturing(ctx):
            check = sv_cycle_model.check_refresh

            def spy(cfg, host_data, rec, *rest):
                seen["check_rec"] = rec
                return check(cfg, host_data, rec, *rest)

            sv_cycle_model.check_refresh = spy
            try:
                with faults.planted(fault, sv_cycle_model, ctx.config, "refresh"):
                    rec = drive(ctx)
            finally:
                sv_cycle_model.check_refresh = check
            seen["returned"] = dict(rec)
            return rec
        return capturing

    args = argparse.Namespace(workload=cell.name, seed=seed, seconds=1.0, trace=trace)
    result = bench_run.run_cell(args, rehearse=True, driver_hook=hook, cell=cell)
    return result, seen["check_rec"], seen["returned"]


@pytest.fixture(scope="module")
def sound_fy():
    return _run()


def test_draws_are_a_pytree_per_leaf(sound_fy):
    _, rec, _ = sound_fy
    assert set(rec["draws"]) == {"phi", "sigma2"} == set(rec["theta_before"])
    width = rec["draws"]["phi"].shape[1]
    assert width % STEPS == 0 and width >= 3 * STEPS
    for leaf in ("phi", "sigma2"):
        assert rec["draws"][leaf].shape == (K, width)
        assert rec["theta_before"][leaf].shape == (K,)


def test_infos_per_operator_with_sizes(sound_fy):
    _, rec, returned = sound_fy
    width = rec["draws"]["phi"].shape[1]
    assert set(rec["ops"]) == {"phi", "sigma2"}  # the sweep reports no info
    for op, fields in rec["ops"].items():
        assert MH_FIELDS | {"traced_n_evaluated", "num_sections", "width"} == set(fields)
        for f in MH_FIELDS:
            assert np.shape(fields[f]) == (K, width), (op, f)
        assert fields["traced_n_evaluated"].shape == (K, 0)  # untraced run
        assert fields["num_sections"] == S * T and fields["width"] == 2
    # No flat counts that would mix operators: the single-operator
    # readers find nothing to read.
    assert returned["ops"] is rec["ops"]
    assert not {"rounds", "n_evaluated", "traced_n_evaluated", "num_sections"} & set(returned)
    assert returned["transitions"] == K * width


def test_after_block_keeps_two_seed_chosen_states(sound_fy):
    """Both ends of two seed-chosen blocks among 1..3: three or four states."""
    _, rec, _ = sound_fy
    blocks = sorted(b for b, _ in rec["kept"])
    ends = [b for b in blocks if b - 1 in blocks and b >= 1]
    assert len(set(blocks)) == len(blocks) and 3 <= len(blocks) <= 4
    assert len(ends) >= 2 and max(blocks) <= sv_cycle_model.AMONG
    for _, state in rec["kept"]:
        assert isinstance(state.theta["h"], np.ndarray)  # pulled after the window
        assert state.theta["h"].shape == (K, S, T)


def test_fy_walk_from_the_key_schedule_is_the_programs(sound_fy):
    """The reference's Fisher-Yates replay of a kept block, from the index
    arrays at its start, ends where the program's sampler ended."""
    _, rec, _ = sound_fy
    kept = dict(rec["kept"])
    block = next(b for b in sorted(kept) if b - 1 in kept)
    mh = [sv_cycle_model.COMPONENTS.index(op) for op in sv_cycle_model.MOVES]
    idx = lambda state: np.stack([np.asarray(state.sampler_state[i].idx) for i in mh], 1)
    rounds = np.stack([rec["ops"][op]["rounds"][:, block * STEPS:(block + 1) * STEPS]
                       for op in sv_cycle_model.MOVES], axis=-1)
    walked = sv_cycle_model.fy_walk(derive_seeds(SEED)["program"], idx(kept[block - 1]),
                                    rec["start_step"] + block * STEPS, rounds,
                                    CONFIG["builder"]["batch_size"])
    assert len(walked) == STEPS
    assert (rounds > 0).all() and not np.array_equal(walked[-1], idx(kept[block - 1]))
    np.testing.assert_array_equal(walked[-1], idx(kept[block]))


def test_readers_of_one_operator_read_nothing_in_a_composite(sound_fy):
    result, _, _ = _run(trace=1)
    assert set(result["rehearsal"]) == set()  # rounds, fraction: no single operator
    assert set(sound_fy[0]["rehearsal"]) == {"cpu_rehearsal.setup_s",
                                             "cpu_rehearsal.transitions_per_s"}


@pytest.mark.parametrize("sampler", ["fy", "stream"])
def test_sound_composite_run_is_correct(sampler, sound_fy):
    result, rec, _ = sound_fy if sampler == "fy" else _run("stream")
    assert result["correct"] is True, result["checks"]
    assert result["checks"]["stat_gap"]["value"] < 1e-3
    assert rec["kept"]


@pytest.mark.parametrize("fault,caught_by", [
    ("frozen", "move_gap"),         # a refresh that returns its state unchanged
    ("half", "stat_gap"),           # every AR(1) delta round: half its sections left out
    ("altered_draw", "move_gap"),   # phi and sigma^2 draws altered where produced
])
def test_broken_composite_is_not_correct(fault, caught_by):
    result, _, _ = _run(fault=fault)
    assert result["correct"] is False
    assert result["checks"][caught_by]["value"] > CONFIG["limits"]["refresh"][caught_by]
