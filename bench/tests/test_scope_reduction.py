"""The reduction of a profiler trace by the program's named scopes and
``repro.*`` annotations (``bench/harness/scopes.py``), the readers that
read it, and the op names read off a compiled program, on synthetic
traces and on programs compiled on the CPU."""
from types import SimpleNamespace as NS

import pytest

from bench.harness import scopes, spec, trace
from test_trace_reduction import ev, plane, synthetic


def sev(name, start_s, dur_s, **stats):
    e = ev(name, start_s, dur_s)
    e.stats = list(stats.items())
    return e


ROUND = "jit(run_all)/while/body/closed_call/while/body"


def program_trace():
    """Window 0-10 s. Two refresh blocks, each with its host stages; device
    ops named by their op_name stats; an evaluator run that waits 0.5 s
    for its first query_eval op."""
    ops = [
        sev("%fusion.1 = f32[8] fusion(x)", 1.0, 1.0, tf_op=f"{ROUND}/gather/gather"),
        sev("%fusion.2 = f32[8] fusion(x)", 2.0, 0.5,
            tf_op=f"{ROUND}/seq_test/vmap()/div"),
        sev("%gather.3 = f32[8] gather(x)", 2.5, 0.5, tf_op=f"{ROUND}/draw/vmap()/gather"),
        sev("%fusion.1 = f32[8] fusion(x)", 5.0, 1.0,
            tf_op=f"{ROUND}/delta/vmap(gather)/gather"),
        sev("%fusion.9 = f32[4] fusion(d)", 8.5, 0.25, tf_op="jit(_mean)/query_eval/dot_general"),
        sev("%copy.1 = f32[4] copy(d)", 9.5, 0.25),  # no op_name anywhere
    ]
    host = [ev("window", 0.0, 10.0),
            ev("refresh", 0.5, 4.0), ev("repro.refresh", 0.6, 3.8),
            ev("repro.refresh.keys", 0.6, 0.2), ev("repro.refresh.dispatch", 0.8, 0.2),
            ev("repro.refresh.wait", 1.0, 2.0), ev("repro.refresh.pull", 3.0, 1.0),
            ev("repro.refresh.commit", 4.0, 0.4),
            ev("refresh", 4.5, 2.0), ev("repro.refresh", 4.6, 1.8),
            ev("repro.refresh.keys", 4.6, 0.4), ev("repro.refresh.wait", 5.0, 1.0),
            ev("repro.device_eval.run", 8.0, 1.0)]
    return [plane("/device:TPU:0", XLA_Ops=ops), plane("/host:CPU", python=host)]


SPANS = ("refresh", "repro.refresh", "repro.refresh.keys", "repro.refresh.dispatch",
         "repro.refresh.wait", "repro.refresh.pull", "repro.refresh.commit",
         "repro.device_eval.run")


def test_scope_is_a_component_other_than_the_primitive():
    assert scopes.scopes_of(f"{ROUND}/gather/gather") >= {"gather", "while", "body"}
    assert "gather" not in scopes.scopes_of(f"{ROUND}/draw/vmap()/gather")
    assert "gather" in scopes.scopes_of(f"{ROUND}/delta/vmap(gather)/gather")
    assert "propose" in scopes.scopes_of("jit(f)/vmap(propose)/jit(_normal)/erf_inv")


def test_scope_time_comes_out_as_constructed():
    r = scopes.reduce_program(program_trace(), ("gather", "seq_test", "delta", "query_eval"))
    # gather: fusion.1 at 1-2 s and the vmap(gather) fusion at 5-6 s; the
    # draw's gather primitive is not in the scope
    assert r["scope_s"]["gather"] == pytest.approx(2.0)
    assert r["scope_s"]["seq_test"] == pytest.approx(0.5)
    assert r["scope_s"]["delta"] == pytest.approx(1.0)
    assert r["scope_s"]["query_eval"] == pytest.approx(0.25)
    assert r["op_names_from"] == {"stats": 5, "hlo": 0, "none": 1}


def test_control_flow_in_a_scope_is_not_counted_twice():
    planes = program_trace()
    # a while loop inside seq_test spans its body's ops, which the trace
    # lists on their own
    planes[0].lines[0].events.append(
        sev("%while.4 = (f32[8]) while(t)", 2.0, 0.5, tf_op=f"{ROUND}/seq_test/vmap()/while"))
    r = scopes.reduce_program(planes, ("seq_test",))
    assert r["scope_s"]["seq_test"] == pytest.approx(0.5)


def test_idle_inside_annotations_comes_out_as_constructed():
    r = scopes.reduce_program(program_trace(), spans=SPANS)
    idle = r["idle_in_span_s"]
    # busy 1-3 and 5-6 (and 8.5-8.75, 9.5-9.75) in the window
    assert idle["repro.refresh.keys"] == pytest.approx(0.2 + 0.4)
    assert idle["repro.refresh.dispatch"] == pytest.approx(0.2)
    assert idle["repro.refresh.wait"] == pytest.approx(0.0)
    assert idle["repro.refresh.pull"] == pytest.approx(1.0)
    assert idle["repro.refresh.commit"] == pytest.approx(0.4)
    assert idle["repro.refresh"] == pytest.approx((3.8 - 2.0) + (1.8 - 1.0))
    assert idle["refresh"] == pytest.approx((4.0 - 2.0) + (2.0 - 1.0))
    assert r["span_count"]["repro.refresh"] == 2
    assert r["span_count"]["repro.device_eval.run"] == 1


def test_delay_to_the_first_op_of_a_scope():
    r = scopes.reduce_program(program_trace(), ("query_eval", "seq_test"),
                              ("repro.device_eval.run", "repro.refresh"))
    delay = r["first_op_delay_s"]
    assert delay["repro.device_eval.run"]["query_eval"] == [pytest.approx(0.5)]
    assert delay["repro.refresh"]["seq_test"] == [pytest.approx(1.4), None]


def test_op_names_fall_back_to_the_compiled_program():
    planes = program_trace()
    for e in planes[0].lines[0].events:
        e.stats = [("hlo_module", "jit_run_all(7)")] if "fusion.1 " in e.name else []
    names = {("jit_run_all", "fusion.1"): f"{ROUND}/gather/gather"}
    r = scopes.reduce_program(planes, ("gather",), op_names=names)
    assert r["scope_s"]["gather"] == pytest.approx(2.0)
    assert r["op_names_from"] == {"stats": 0, "hlo": 2, "none": 4}
    # the module may come from the plane's "XLA Modules" line instead
    for e in planes[0].lines[0].events:
        e.stats = []
    planes[0].lines.append(NS(name="XLA Modules", events=[ev("jit_run_all(7)", 0.0, 7.0)]))
    r = scopes.reduce_program(planes, ("gather",), op_names=names)
    assert r["scope_s"]["gather"] == pytest.approx(2.0)


def test_op_names_from_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def f(x, i):
        with jax.named_scope("gather"):
            g = x[i]
        with jax.named_scope("seq_test"):
            return (g / 3.0).sum()

    text = jax.jit(f).lower(jnp.ones((16, 4)), jnp.arange(8)).compile().as_text()
    names = scopes.op_names_from_hlo([text])
    found = {s for path in names.values() for s in scopes.scopes_of(path)}
    assert {"gather", "seq_test"} <= found
    assert {m for m, _ in names} == {"jit_f"}


def test_the_readers_read_the_reduction():
    import numpy as np

    def reader(name):
        return spec._load_module(spec.ROOT / "bench" / "metrics" / f"{name}.py", "metric")

    red = scopes.reduce_program(program_trace(), ("gather", "seq_test", "query_eval"), SPANS)
    rec = {"trace": red, "traced_n_evaluated": np.zeros((2, 8))}  # 16 transitions
    assert reader("gather_us_per_transition.refresh").read(rec) == pytest.approx(2.0e6 / 16)
    assert reader("seq_test_us_per_transition.refresh").read(rec) == pytest.approx(0.5e6 / 16)
    # keys 0.6 + dispatch 0.2 + pull 1.0 + commit 0.4 over 2 blocks
    assert reader("refresh_host_gap_ms.refresh").read(rec) == pytest.approx(1e3 * 2.2 / 2)
    assert reader("eval_device_wait_p50_ms.serve").read(rec) == pytest.approx(500.0)
    # a trace without the program's marks (the parent's) reads nothing
    bare = {"trace": trace.reduce_trace(synthetic()), "traced_n_evaluated": np.zeros((2, 8))}
    for name in ("gather_us_per_transition.refresh", "seq_test_us_per_transition.refresh",
                 "refresh_host_gap_ms.refresh", "eval_device_wait_p50_ms.serve"):
        assert reader(name).read(bare) is None
        assert reader(name).read({}) is None


def test_declared_marks_are_collected_once():
    a = NS(SCOPES=("gather",), SPANS=("repro.refresh",))
    b = NS(SCOPES=("gather", "seq_test"))
    assert scopes.declared([a, b, NS()]) == (("gather", "seq_test"), ("repro.refresh",))
