"""End-to-end arithmetic on synthetic timelines."""
import math

import pytest

from chipbench import e2e


def steady(n_req=20, gap=0.05, tokens=10, start=0.0, every=0.2):
    reqs = []
    for i in range(n_req):
        due = start + i * every
        r = e2e.ReqTimeline(i, due, 100, tokens)
        r.stamps = [due + 0.03 + j * gap for j in range(tokens)]
        reqs.append(r)
    return reqs


def test_steady_timeline():
    reqs = steady()
    m = e2e.metrics(reqs, 0.0, 4.0, 4.0)
    assert m["ttft_p50_ms"] == pytest.approx(30.0)
    assert m["ttft_p95_ms"] == pytest.approx(30.0)
    assert m["itl_p95_ms"] == pytest.approx(50.0)
    n = sum(1 for r in reqs for s in r.stamps if 0 < s <= 4.0)
    assert m["tokens_per_s"] == pytest.approx(n / 4.0)


def test_a_stall_moves_the_tails_and_the_window_counts_it():
    reqs = steady()
    base = e2e.metrics(reqs, 0.0, 4.0, 4.0)
    # the engine stalls for 0.2 s at t = 1.0, 1.5, 2.0 and 2.5: every token
    # produced after a stall reaches the client that much later
    stalls = [1.0, 1.5, 2.0, 2.5]
    for r in reqs:
        r.stamps = [s + 0.2 * sum(s >= t for t in stalls) for s in r.stamps]
    stalled = e2e.metrics(reqs, 0.0, 4.0, 4.0)
    assert stalled["itl_p95_ms"] > base["itl_p95_ms"]
    assert stalled["ttft_p95_ms"] > base["ttft_p95_ms"]
    assert stalled["tokens_per_s"] < base["tokens_per_s"]


def test_requests_without_a_first_token_count_as_infinitely_late():
    reqs = steady(n_req=10)
    for r in reqs[-2:]:
        r.stamps = []
    assert e2e.ttfts(reqs, 0.0, 4.0).count(math.inf) == 2
    assert e2e.metrics(reqs, 0.0, 4.0, 4.0)["ttft_p95_ms"] == math.inf


def test_tokens_outside_the_window_do_not_count():
    r = e2e.ReqTimeline(0, 0.0, 10, 4)
    r.stamps = [0.5, 1.5, 2.5, 3.5]
    assert e2e.window_tokens([r], 1.0, 3.0) == 2
    assert e2e.itls([r], 1.0, 3.0) == [1.0, 1.0]


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert e2e.percentile(v, 95) == 95
    assert e2e.percentile(v, 50) == 50
    assert e2e.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        e2e.percentile([], 50)
