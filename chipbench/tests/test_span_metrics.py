"""``prefill_ms`` and ``tick_host_ms`` on hand-built engine span records
(host clock, milliseconds; the window is [10 s, 20 s))."""

import pytest

from chipbench import harness

WINDOW = harness.Window(t0=10.0, t_close=20.0, t1=20.5)


def span(kind, tick, begin_s, dur_ms, **attrs):
    """A span record as the tracer emits it: stamped when it closes."""
    return {"kind": kind, "tick": tick, "wall_ms": begin_s * 1e3 + dur_ms,
            "dur_ms": dur_ms, **attrs}


def prefill(tick, begin_s, dur_ms):
    return span("prefill", tick, begin_s, dur_ms, rid=tick, slot=0,
                bucket=256, prompt_len=200)


def tick_spans(tick, begin_s, step_ms, host_ms, prefills=()):
    """The records of one tick, in the order they close: its prefills
    (inside ``schedule``), its ``step``, then the ``tick`` itself."""
    out = [prefill(tick, begin_s + 0.001, d) for d in prefills]
    out.append(span("step", tick, begin_s + 0.002 + sum(prefills) * 1e-3,
                    step_ms))
    out.append({"kind": "decode", "tick": tick, "active": 4, "tokens": 4,
                "wall_ms": begin_s * 1e3 + 1.0})
    out.append(span("tick", tick, begin_s, host_ms + step_ms + sum(prefills),
                    active=4))
    return out


def ctx(events):
    return harness.Context(cell=None, ref=None, arch=None, peak=None, window=WINDOW,
                           timeline=[], ticks=[], setup={},
                           engine_events=events, dispatch=[], trace=None)


def read(name, events):
    return harness.load_metric(name).read(ctx(events))


EVENTS = (tick_spans(0, 9.9, 70.0, 50.0, prefills=(500.0,))   # before t0
          + tick_spans(1, 10.0, 70.0, 2.0)                     # no prefill
          + tick_spans(2, 11.0, 70.0, 4.0, prefills=(30.0, 40.0))
          + tick_spans(3, 19.9, 70.0, 3.0, prefills=(20.0,))    # begun in
          + tick_spans(4, 20.0, 70.0, 90.0, prefills=(900.0,)))  # at close


def test_tick_host_ms_subtracts_step_and_nested_prefills_in_the_window():
    # ticks 1-3 begun in [10, 20): host 2, 4 and 3 ms
    assert read("tick_host_ms", EVENTS) == pytest.approx(3.0)
    # a tick without a prefill reads its tick less its step
    assert read("tick_host_ms", tick_spans(1, 10.0, 70.0, 2.5)) == \
        pytest.approx(2.5)


def test_prefill_ms_is_the_median_of_the_window_prefills():
    # prefills begun in the window: 30, 40, 20 (500 before it, 900 after)
    assert read("prefill_ms", EVENTS) == pytest.approx(30.0)


def test_a_tick_shares_no_spans_with_the_one_before_it():
    """Two ticks with one tick number (an idle tick does not advance the
    counter): each subtracts only the spans it holds."""
    idle = [span("tick", 1, 10.0, 0.5, active=0)]
    assert read("tick_host_ms", idle + tick_spans(1, 10.1, 70.0, 2.5)) == \
        pytest.approx((0.5 + 2.5) / 2)


def test_nothing_in_the_window_reads_none():
    outside = tick_spans(0, 9.0, 70.0, 2.0, prefills=(30.0,)) + \
        tick_spans(1, 25.0, 70.0, 2.0, prefills=(30.0,))
    assert read("tick_host_ms", outside) is None
    assert read("prefill_ms", outside) is None
    assert read("tick_host_ms", []) is None
    assert read("prefill_ms", []) is None


def test_a_program_without_the_spans_reads_none():
    """A program whose engine has no ``tick`` spans, and whose ``prefill``
    span times only the enqueue (it carries no ``prompt_len``)."""
    old = [{"kind": "prefill", "tick": 1, "rid": 0, "slot": 0, "bucket": 256,
            "wall_ms": 11_000.0, "dur_ms": 1.5},
           {"kind": "decode", "tick": 1, "active": 4, "tokens": 4,
            "wall_ms": 11_070.0}]
    assert read("prefill_ms", old) is None
    assert read("tick_host_ms", old) is None
