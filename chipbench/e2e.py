"""End-to-end arithmetic over one run's request timeline.

Input: for every request, when it was due and the host-clock time at which
each of its tokens reached the client (the end of the engine tick that
produced it), and the window [open, close] on the same clock.

* ``tokens_per_s``: tokens that reached the client inside the window, over
  the window's seconds. The window closes at the end of the last tick that
  began before the nominal close, so all the work and all the time of the
  ticks in it count.
* ``ttft_*``: time from due to first token, over every request due inside
  the window. A request that never got its first token counts as infinitely
  late.
* ``itl_p95_ms``: every gap between consecutive tokens of one request whose
  later token lies inside the window.

Percentiles are nearest-rank over all samples: no chunk medians.
"""
from __future__ import annotations

import dataclasses
import math


@dataclasses.dataclass
class ReqTimeline:
    """Host-clock record of one request (seconds, one clock for all)."""

    rid: int
    due: float
    prompt_len: int
    max_new: int
    stamps: list[float] = dataclasses.field(default_factory=list)
    admitted: float | None = None


def percentile(values: list[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def window_tokens(reqs: list[ReqTimeline], t0: float, t1: float) -> int:
    """Tokens that reached the client in (t0, t1]."""
    return sum(1 for r in reqs for s in r.stamps if t0 < s <= t1)


def ttfts(reqs: list[ReqTimeline], t0: float, t_close: float) -> list[float]:
    """Seconds from due to first token of every request due in
    [t0, t_close); ``inf`` for one that never got a first token."""
    return [(r.stamps[0] - r.due) if r.stamps else math.inf
            for r in reqs if t0 <= r.due < t_close]


def itls(reqs: list[ReqTimeline], t0: float, t1: float) -> list[float]:
    """Gaps between consecutive tokens of a request, the later one in
    (t0, t1]."""
    out = []
    for r in reqs:
        for a, b in zip(r.stamps, r.stamps[1:]):
            if t0 < b <= t1:
                out.append(b - a)
    return out


def metrics(reqs: list[ReqTimeline], t0: float, t1: float,
            t_close: float) -> dict[str, float]:
    """The end-to-end metrics of one window, in their units (see module
    docstring). ``t1`` is the end of the last tick of the window, and
    ``t_close`` the nominal close that decides which requests were due in
    it."""
    out = {"tokens_per_s": window_tokens(reqs, t0, t1) / (t1 - t0)}
    gaps = itls(reqs, t0, t1)
    if gaps:
        out["itl_p95_ms"] = percentile(gaps, 95) * 1e3
    first = ttfts(reqs, t0, t_close)
    if first:
        out["ttft_p50_ms"] = percentile(first, 50) * 1e3
        out["ttft_p95_ms"] = percentile(first, 95) * 1e3
    return out
