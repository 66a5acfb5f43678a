"""Per-layer metric readers on a hand-built run context."""

import pytest

from chipbench import e2e, harness, peaks, trace_reduce
from chipbench.refs import olmo

V5E = peaks.peaks("TPU v5 lite")


def ctx(config="olmo_1b_phi", trace=True):
    c = harness.load_config(config)
    w = harness.Window(t0=10.0, t_close=20.0, t1=20.5)
    r1 = e2e.ReqTimeline(0, 9.0, 1000, 3)
    r1.stamps = [11.0, 11.0, 12.0]
    r2 = e2e.ReqTimeline(1, 12.0, 500, 2)
    r2.stamps = [13.0, 13.0]
    events = [{"kind": "admit", "rid": 0, "wall_ms": 10_500.0, "bucket": 1024},
              {"kind": "admit", "rid": 1, "wall_ms": 12_600.0, "bucket": 512},
              {"kind": "decode", "active": 2, "wall_ms": 11_000.0},
              {"kind": "decode", "active": 1, "wall_ms": 12_000.0},
              {"kind": "decode", "active": 1, "wall_ms": 13_000.0},
              {"kind": "decode", "active": 8, "wall_ms": 25_000.0}]
    dispatch = [{"site": "lm.w1", "shape": [2048, 2048, 8192], "impl": "fused"},
                {"site": "lm.w1", "shape": [16, 2048, 8192], "impl": "coo"}]
    red = trace_reduce.Reduced(
        window_s=10.5, busy_s=8.4, devices=1,
        op_s={"phi_fused.3": 0.05, "fusion.1": 1.0},
        module_s={"jit__unknown(1)": [0.1, 0.1, 0.1],
                  "jit__unknown(2)": [0.5], "jit__gumbel(3)": [1e-4] * 3},
        idle_gaps={}) if trace else None
    ticks = [(9.0, 9.5, 1), (10.9, 11.0, 2), (11.0, 12.0, 1), (12.0, 12.07, 1),
             (20.2, 23.0, 1)]
    return harness.Context(cell=None, ref=olmo, arch=olmo.arch(c), peak=V5E,
                           window=w, timeline=[r1, r2], ticks=ticks,
                           setup={"warmup_s": 12.5},
                           engine_events=events, dispatch=dispatch, trace=red)


def read(name, c):
    return harness.load_metric(name).read(c)


def test_host_and_engine_readers():
    c = ctx()
    assert read("warmup_s", c) == 12.5
    # ticks begun in [10, 20): the one of 1.0 s is the longest
    assert read("longest_tick_ms", c) == pytest.approx(1000.0)
    # due 12.0 -> admitted 12.6 (rid 0 was due before the window)
    assert read("queue_wait_ms", c) == pytest.approx(600.0)
    assert read("batch_occupancy", c) == pytest.approx(4 / 3)


def test_trace_readers():
    c = ctx()
    assert read("idle_share", c) == pytest.approx(20.0)
    # three decode events in the window: the program run three times
    assert read("decode_step_ms", c) == pytest.approx(100.0)
    assert read("idle_share", ctx(trace=False)) is None
    assert read("decode_step_ms", ctx(trace=False)) is None


def test_mfu_counts_the_window_work():
    c = ctx()
    ops = olmo.model_ops(c.arch, prompt_lens=[1000, 500],
                         decode_contexts=[1001, 1002, 501])
    assert read("mfu", c) == pytest.approx(ops / (10.5 * 197e12) * 100)
    assert 0 < read("mfu", c) < 100


def test_mfu_of_the_spiking_model_counts_phi_work():
    """The spiking model's least work per token is the Phi count, below the
    plain model's dense count, so its share of the peak is lower too."""
    assert 0 < read("mfu", ctx("olmo_1b_phi")) < read("mfu", ctx("olmo_1b"))
