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


def quadratic_idle_gaps(profile, window=trace_reduce.WINDOW):
    """The idle labels as the reducer found them before its sweep: for each
    gap, a scan of every host span for the shortest covering its midpoint
    (of equal ones, the first by name)."""
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window:
                        host_line, (lo, hi) = line, (e.start_ns, e.start_ns + e.duration_ns)
    dev = next(p for p in profile.planes if p.name == "/device:TPU:0")
    ops = [(e.start_ns, e.start_ns + e.duration_ns) for line in dev.lines
           if line.name == trace_reduce.OPS_LINE for e in line.events]
    busy = trace_reduce._union(trace_reduce._clip(ops, lo, hi))
    host = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in host_line.events]
    host = [(n, s, e) for n, s, e in host if n != window and e > lo and s < hi]
    idle = {}
    prev = lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            mid = (prev + s) / 2
            cover = [(e2 - s2, name) for name, s2, e2 in host if s2 <= mid <= e2]
            label = min(cover)[1] if cover else "no host span"
            idle[label] = idle.get(label, 0.0) + (s - prev) * 1e-9
        prev = max(prev, e)
    return idle


def nested_profile():
    """Nested host spans; spans of equal length under different names
    (``b.tie`` and ``a.tie`` cover the same gaps, ``x.tie`` opens before
    ``w.tie``); a short span that ends before a gap's midpoint; and a gap
    that no span covers."""
    host = [ev("bench.window", 0, 100_000), ev("bench.tick", 0, 40_000),
            ev("engine.step", 1_000, 20_000), ev("b.tie", 5_000, 6_000),
            ev("a.tie", 5_000, 6_000), ev("c.short", 2_000, 500),
            ev("engine.finish", 25_000, 10_000), ev("late", 44_000, 24_000),
            ev("bench.tick", 71_500, 18_500), ev("x.tie", 72_000, 5_000),
            ev("w.tie", 74_000, 5_000)]
    ops = [ev("f", 0, 2_400), ev("f", 3_000, 4_000), ev("g", 9_000, 2_000),
           ev("f", 20_000, 6_000), ev("g", 36_000, 1_000), ev("g", 42_000, 1_000),
           ev("f", 60_000, 1_000), ev("f", 68_500, 500), ev("g", 73_000, 1_000),
           ev("g", 79_000, 2_000), ev("f", 95_000, 10_000)]
    return profile(ops, [], host)


def test_the_sweep_names_gaps_as_the_scan_did():
    from jax.profiler import ProfileData

    built = nested_profile()
    r = trace_reduce.reduce(built)
    assert r.idle_gaps == quadratic_idle_gaps(built)
    assert r.idle_gaps == {
        "engine.step": pytest.approx(9_600e-9),     # [2400, 3000), [11000, 20000)
        "a.tie": pytest.approx(2_000e-9),           # [7000, 9000): a.tie = b.tie
        "engine.finish": pytest.approx(10_000e-9),  # [26000, 36000)
        "bench.tick": pytest.approx(19_000e-9),     # [37000, 42000), [81000, 95000)
        "late": pytest.approx(24_500e-9),           # [43000, 60000), [61000, 68500)
        "no host span": pytest.approx(4_000e-9),    # [69000, 73000)
        "w.tie": pytest.approx(5_000e-9)}           # [74000, 79000): w.tie = x.tie
    recorded = ProfileData.from_file(os.path.join(TESTDATA, "v5e_tiny.xplane.pb"))
    assert trace_reduce.reduce(recorded).idle_gaps == quadratic_idle_gaps(recorded)
