"""The traffic generator: deterministic for a seed, the same work for every
seed, and lengths and arrivals that follow the stated distributions."""
import math
from statistics import median

import numpy as np
import pytest

from chipbench import loadgen

MIXES = ["chat_closed", "chat_open", "long_prompt_closed"]


def mix(name):
    return loadgen.load(name)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    p = mix(name)
    a, b = loadgen.Traffic(p, 2 ** 31 + 7, 50304), loadgen.Traffic(p, 2 ** 31 + 7, 50304)
    for i in range(20):
        x, y = a.item(i), b.item(i)
        assert x.max_new == y.max_new and np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", ["chat_closed", "long_prompt_closed"])
def test_every_seed_gets_the_same_pool(name):
    p = mix(name)
    a, b = loadgen.Traffic(p, 1, 50304), loadgen.Traffic(p, 99, 50304)
    assert len(a.p_len) == p["pool"]
    assert np.array_equal(a.p_len, b.p_len) and np.array_equal(a.o_len, b.o_len)
    assert not np.array_equal(a.p_len, np.sort(a.p_len))
    assert not np.array_equal(a.item(0).prompt, b.item(0).prompt)


@pytest.mark.parametrize("name", ["chat_open"])
def test_every_seed_gets_the_same_window(name):
    """The same sizes and arrivals for every seed; only the token ids
    differ."""
    p = mix(name)
    a = loadgen.Traffic(p, 1, 50304).schedule(30.0)
    b = loadgen.Traffic(p, 2 ** 33 + 1, 50304).schedule(30.0)
    assert len(a) == len(b) == round(p["arrival"]["rate"] * 30.0)
    assert [(len(x.prompt), x.max_new, x.due) for x in a] == \
        [(len(x.prompt), x.max_new, x.due) for x in b]
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert all(0.0 < x.due < 30.0 for x in a)


def test_chat_lengths_follow_the_lognormal():
    p = dict(loadgen.load("chat_closed"), pool=1024)
    t = loadgen.Traffic(p, 0, 50304)
    assert median(t.p_len) == pytest.approx(256, rel=0.02)
    assert median(t.o_len) == pytest.approx(64, rel=0.03)
    assert t.p_len.min() == 16 and t.p_len.max() == 1536
    assert t.o_len.min() == 4 and t.o_len.max() == 448
    # sigma 0.9: the 84th percentile sits at median * e^0.9
    assert np.percentile(t.p_len, 84.13) == pytest.approx(256 * math.exp(0.9), rel=0.03)


def test_long_prompt_lengths():
    t = loadgen.Traffic(dict(loadgen.load("long_prompt_closed"), pool=1024), 0, 50304)
    assert t.p_len.min() >= 768 and t.p_len.max() <= 1900
    assert np.median(np.log(t.p_len)) == pytest.approx(
        (math.log(768) + math.log(1900)) / 2, abs=0.01)
    assert set(t.o_len.tolist()) == {1, 2, 3, 4}
    assert abs(np.mean(t.o_len) - 2.5) < 0.01
    assert t.prompt_bounds() == (768, 1900)


@pytest.mark.parametrize("name", ["chat_open"])
def test_open_loop_rate(name):
    p = mix(name)
    t = loadgen.Traffic(p, 5, 50304)
    horizon = 200.0
    sched = t.schedule(horizon)
    rate = p["arrival"]["rate"]
    assert len(sched) == pytest.approx(rate * horizon, abs=1)
    assert all(a.due <= b.due for a, b in zip(sched, sched[1:]))
    assert sched[-1].due < horizon
    # Poisson: gaps exponential (coefficient of variation 1), and neither
    # the gap before a request nor its length predicts the other
    gaps = np.diff([0.0] + [it.due for it in sched])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.15)
    for lens in ([len(it.prompt) for it in sched], [it.max_new for it in sched]):
        assert abs(np.corrcoef(gaps, lens)[0, 1]) < 0.2


def test_the_open_loop_leads_in_for_two_mean_request_lifetimes():
    """At one token per 0.1 s, 20 s hold more than two mean outputs."""
    t = loadgen.Traffic(loadgen.load("chat_open"), 5, 50304)
    assert t.lead_in_s == 20.0
    assert t.lead_in_s >= 2 * np.mean(t.o_len) * 0.1
    assert loadgen.Traffic(loadgen.load("chat_closed"), 5, 50304).lead_in_s == 0.0


def test_token_ids_avoid_special_tokens():
    t = loadgen.Traffic(loadgen.load("chat_closed"), 4, 128)
    ids = np.concatenate([t.item(i).prompt for i in range(50)])
    assert ids.min() >= loadgen.FIRST_TOKEN_ID and ids.max() < 128
