"""The open-loop arithmetic: every request is timed from its due time, a
stall is charged to the requests behind it, and a request that never
finishes is a failure at the top of the tail."""
import math
import time

import numpy as np
import pytest

from bench.harness import loadgen, stats


def test_latency_is_timed_from_the_due_time():
    # Submitted late (the generator lagged), finished at 1.5: the latency
    # counts from the due time 1.0, not from the submit.
    lat = stats.latency_from_due([1.0], [1.5], [True])
    assert lat[0] == pytest.approx(0.5)


def test_a_stall_is_charged_to_the_requests_behind_it():
    # A server that is stuck from 0 to 2 s, then answers each queued
    # request 10 ms after the previous one.
    due = np.array([0.0, 0.1, 0.2, 0.3])
    done = [2.0 + 0.01 * i for i in range(4)]
    lat = stats.latency_from_due(due, done, [True] * 4)
    assert lat == pytest.approx([2.0, 1.91, 1.82, 1.73])
    assert stats.percentile(lat, 95) == pytest.approx(2.0)


def test_an_unfinished_or_failed_request_is_infinitely_late():
    due = np.arange(20) * 0.01
    done = list(due + 0.005)
    ok = [True] * 20
    done[3] = None  # never came back
    ok[7] = False  # failed
    lat = stats.latency_from_due(due, done, ok)
    assert math.isinf(lat[3]) and math.isinf(lat[7])
    # 2 of 20 missing: the 95th percentile (rank 19) reaches them.
    assert math.isinf(stats.percentile(lat, 95))
    assert stats.percentile(lat, 50) == pytest.approx(0.005)


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0


def test_every_seed_gets_the_same_gaps_in_its_own_order():
    a = loadgen.poisson_schedule(np.random.default_rng(1), 50.0, 10.0)
    b = loadgen.poisson_schedule(np.random.default_rng(2), 50.0, 10.0)
    assert a.size == b.size == 500
    assert a[0] == b[0] == 0.0 and a[-1] < 10.0
    # The same multiset of gaps (each schedule leaves out its last one).
    common = np.intersect1d(np.round(np.diff(a), 9), np.round(np.diff(b), 9))
    assert common.size >= a.size - 3
    assert not np.allclose(a, b)
    assert np.mean(np.diff(a)) == pytest.approx(1 / 50.0, rel=0.01)


def test_the_generator_submits_on_schedule_and_records_its_lag():
    seen = []
    gen = loadgen.OpenLoop([0.0, 0.05, 0.10], ["a", "b", "c"],
                           lambda p: seen.append((p, time.monotonic())) or p)
    t0 = time.monotonic() + 0.02
    gen.start(t0)
    gen.join(timeout_s=5.0)
    assert [p for p, _ in seen] == ["a", "b", "c"]
    assert gen.handles == ["a", "b", "c"]
    lag = gen.lag_s()
    assert np.all(lag >= 0) and np.all(lag < 0.05)
    assert [t for _, t in seen] == pytest.approx(list(t0 + np.array([0.0, 0.05, 0.10])), abs=0.05)


def test_a_failing_submit_is_reported_by_join():
    gen = loadgen.OpenLoop([0.0], ["x"], lambda p: 1 / 0)
    gen.start(time.monotonic())
    with pytest.raises(RuntimeError):
        gen.join(timeout_s=5.0)
