"""Trace reduction: on a hand-built profile, and on a small trace recorded
on a TPU v5e (``chipbench/testdata``)."""
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import trace_reduce

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "testdata")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(ops, modules, host):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                        NS(name="XLA Modules", events=modules)]),
        NS(name="/device:TPU:0 SparseCore", lines=[]),
    ])


def test_busy_idle_and_gaps_on_a_built_profile():
    host = [ev("bench.window", 1000, 10000),
            ev("bench.tick", 1000, 4000), ev("bench.wait", 5000, 6000),
            ev("decode_dispatch", 1500, 500)]
    ops = [ev("fusion.1", 500, 1500),        # half outside the window
           ev("fusion.2", 3000, 1000), ev("fusion.2", 3500, 1000),  # overlap
           ev("phi_fused_kernel", 8000, 1000)]
    modules = [ev("jit_decode_step_paged", 2500, 2500),
               ev("jit_prefill", 7900, 1200), ev("jit_late", 10500, 2000)]
    r = trace_reduce.reduce(profile(ops, modules, host))
    assert r.window_s == pytest.approx(10000e-9)
    # busy: [1000, 2000) + [3000, 4500) + [8000, 9000) = 3500 ns
    assert r.busy_s == pytest.approx(3500e-9)
    assert r.op_s["fusion.2"] == pytest.approx(2000e-9)
    assert trace_reduce.op_name("%fusion.3 = f32[] fusion(%x)") == "fusion.3"
    assert r.op_s["phi_fused_kernel"] == pytest.approx(1000e-9)
    assert r.module_s == {"jit_decode_step_paged": [pytest.approx(2500e-9)],
                          "jit_prefill": [pytest.approx(1200e-9)]}  # late: after the window
    # idle: [2000, 3000) mid 2500 -> bench.tick; [4500, 8000) mid 6250 ->
    # bench.wait; [9000, 11000) mid 10000 -> bench.wait
    assert r.idle_gaps["bench.tick"] == pytest.approx(1000e-9)
    assert r.idle_gaps["bench.wait"] == pytest.approx(5500e-9)
    b = r.breakdown()
    assert b["device_ops"][0][0] == "fusion.2"
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(5500e-9)]


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce(profile([], [], [ev("bench.tick", 0, 10)]))


def test_a_trace_recorded_on_the_chip():
    from jax.profiler import ProfileData

    path = os.path.join(TESTDATA, "v5e_tiny.xplane.pb")
    r = trace_reduce.reduce(ProfileData.from_file(path))
    assert r.devices == 1
    assert 0 < r.busy_s < r.window_s
    # three runs of one program; the first ran before the window opened
    assert [len(v) for k, v in r.module_s.items() if "jit__lambda" in k] == [2]
    assert r.op_s["fusion"] > 0
    assert sum(r.idle_gaps.values()) == pytest.approx(r.window_s - r.busy_s, rel=1e-6)
    # recorded with the Python tracer on: the host was sleeping between runs
    assert max(r.idle_gaps, key=r.idle_gaps.get) == "$time sleep"
