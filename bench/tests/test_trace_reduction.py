"""The reduction from a profiler trace to busy time, idle gaps and
kernel time, on a synthetic trace and on one recorded on the CPU."""
from types import SimpleNamespace as NS

import pytest

from bench.harness import trace


def ev(name, start_s, dur_s):
    return NS(name=name, start_ns=start_s * 1e9, duration_ns=dur_s * 1e9)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def synthetic():
    # Window 10 s .. 20 s. Device ops overlap (11-13 and 12-14 -> 11-14),
    # one op straddles the window's start, one lies outside it.
    ops = [ev("%fusion.1 = f32[8] fusion(x)", 9.0, 2.0),
           ev("%batched_logit_delta.3 = f32[8,1,512] custom-call(x)", 11.0, 2.0),
           ev("%fusion.2 = f32[8] fusion(y)", 12.0, 2.0),
           ev("%batched_logit_delta.3 = f32[8,1,512] custom-call(x)", 16.0, 1.0),
           ev("%copy = f32[8] copy(x)", 25.0, 1.0)]
    host = [ev("window", 10.0, 10.0), ev("refresh", 14.0, 2.0),
            ev("bench", 17.0, 3.0), ev("generator", 10.0, 10.0)]
    return [plane("/device:TPU:0", XLA_Ops=ops), plane("/host:CPU", python=host)]


def test_busy_is_the_union_clipped_to_the_window():
    r = trace.reduce_trace(synthetic(), labels=("bench", "refresh", "generator"))
    # busy: 10-11 (clipped), 11-14 (merged), 16-17  ->  1 + 3 + 1 = 5 s
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(5.0)
    assert 1.0 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)


def test_idle_gaps_are_labelled_by_the_covering_host_span():
    r = trace.reduce_trace(synthetic(), labels=("bench", "refresh", "generator"))
    gaps = {(name, round(s, 6)) for name, s in r["idle_gaps"]}
    # 14-16 lies in "refresh", 17-20 in "bench"; "generator" covers all
    # but comes last in priority.
    assert gaps == {("refresh", 2.0), ("bench", 3.0)}
    assert r["idle_gaps"][0] == ["bench", pytest.approx(3.0)]


def test_kernel_time_is_found_by_name():
    r = trace.reduce_trace(synthetic(), kernels={"k": ("batched_logit_delta",)})
    assert r["kernel_s"]["k"] == pytest.approx(3.0)
    top = dict(r["device_ops"])
    assert top["batched_logit_delta.3"] == pytest.approx(3.0)
    assert "copy" not in top  # outside the window


def test_control_flow_ops_are_busy_but_not_ranked():
    planes = synthetic()
    planes[0].lines[0].events.append(ev("%while.7 = (s32[]) while(t)", 10.0, 10.0))
    r = trace.reduce_trace(planes)
    assert r["busy_s"] == pytest.approx(10.0)
    assert "while.7" not in dict(r["device_ops"])


def test_busy_is_averaged_over_devices():
    planes = synthetic()
    planes.append(plane("/device:TPU:1", XLA_Ops=[ev("fusion.9", 10.0, 10.0)]))
    r = trace.reduce_trace(planes)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((5.0 + 10.0) / 2)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_trace([plane("/host:CPU", python=[ev("window", 0, 1)])])


def test_host_spans_read_from_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("refresh"):
                f(x).block_until_ready()
    spans = trace.host_spans(trace.load_planes(str(tmp_path)), ["window", "refresh"])
    (w0, w1), = spans["window"]
    (r0, r1), = spans["refresh"]
    assert w0 <= r0 <= r1 <= w1
