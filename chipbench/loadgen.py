"""The one traffic generator. A traffic mix is a data file,
``chipbench/traffic/<name>.json``, that this module reads:

    {"loop": "closed", "clients": 8,                       # or
     "loop": "open", "arrival": {"process": "poisson", "rate": 4.0},
     "lead_in_s": 20,
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                "min": 16, "max": 1536},                    # or loguniform,
     "output": {"dist": "uniform", "min": 1, "max": 4},     # uniform
     "pool": 64}

Every seed gets the same work, in the same order. Lengths are the
quantiles (i + 0.5) / n of their distribution, rounded, clipped, and put in
one fixed shuffled order; inter-arrival gaps are quantiles of the
exponential in the same way. The seed draws the token ids (and, in the
harness, the weights), never the sizes or the arrivals: a window of a
fixed length then holds the same work for every seed, where a seeded order
had moved tokens/s by 12% from seed to seed (PERF.md).

An open loop's schedule runs for ``lead_in_s`` before the measured window
opens, so that the window sees the engine as the offered load keeps it.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TRAFFIC_DIR = os.path.join(HERE, "traffic")
FIRST_TOKEN_ID = 3          # ids below are the tokenizer's special tokens
ORDER_SEED = 20251018       # fixes the order of the work, for every run seed


def load(name: str) -> dict:
    """The parameters of traffic mix ``name``."""
    with open(os.path.join(TRAFFIC_DIR, name + ".json")) as f:
        return json.load(f)


def quantile(dist: dict, u: float) -> float:
    """The ``u`` quantile of a length distribution (before clipping)."""
    kind = dist["dist"]
    if kind == "lognormal":
        return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    if kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return math.exp(lo + u * (hi - lo))
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] + 1 - dist["min"]) - 0.5
    raise ValueError(f"unknown length distribution {kind!r}")


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of ``dist``, ascending, clipped to its
    [min, max]."""
    u = (np.arange(n) + 0.5) / n
    raw = np.array([quantile(dist, x) for x in u])
    return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)


@dataclasses.dataclass
class Item:
    """One request of the mix: its prompt and how many tokens it asks for.
    ``due`` is the offset from the window's opening at which an open loop
    sends it (None in a closed loop)."""

    prompt: np.ndarray
    max_new: int
    due: float | None = None


class Traffic:
    """Seeded request stream of one mix.

    Closed loop: clients take requests in order from a pool of ``pool``
    stratified lengths in the fixed order, which repeats. Open loop:
    ``schedule(horizon)`` gives exactly round(rate * horizon) requests, with
    that many stratified lengths and gaps, so every seed offers the same
    work; the seed draws the token ids."""

    def __init__(self, params: dict, seed: int, vocab: int,
                 max_prompt: int | None = None, max_new: int | None = None):
        self.params = params
        self.closed = params["loop"] == "closed"
        self.clients = int(params.get("clients", 0))
        self.lead_in_s = float(params.get("lead_in_s", 0.0))
        self.vocab = vocab
        self.caps = (max_prompt, max_new)
        self._rng = np.random.default_rng(seed)
        self._pool(int(params.get("pool", 64)))

    @staticmethod
    def _order(n: int, stream: int) -> np.ndarray:
        """The one shuffled order of ``n`` stratified values of ``stream``
        (0 prompt lengths, 1 output lengths, 2 arrival gaps), each drawn
        apart so that no two are correlated."""
        return np.random.default_rng([ORDER_SEED, n, stream]).permutation(n)

    def _pool(self, n: int) -> None:
        p_len = lengths(self.params["prompt"], n)
        o_len = lengths(self.params["output"], n)
        if self.caps[0] is not None:       # CPU rehearsal at small context
            p_len = np.minimum(p_len, self.caps[0])
        if self.caps[1] is not None:
            o_len = np.minimum(o_len, self.caps[1])
        self.p_len = p_len[self._order(n, 0)]
        self.o_len = o_len[self._order(n, 1)]

    def item(self, i: int) -> Item:
        """Request ``i`` of the stream (the pool of lengths repeats)."""
        n = len(self.p_len)
        p = int(self.p_len[i % n])
        prompt = self._rng.integers(FIRST_TOKEN_ID, self.vocab, p).astype(np.int32)
        return Item(prompt, int(self.o_len[i % n]))

    def schedule(self, horizon_s: float) -> list[Item]:
        """Open loop: the requests due in ``horizon_s`` seconds, each with
        its due offset, in order."""
        arr = self.params["arrival"]
        if arr["process"] != "poisson":
            raise ValueError(f"unknown arrival process {arr['process']!r}")
        n = max(1, round(float(arr["rate"]) * horizon_s))
        u = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-u)[self._order(n, 2)]
        # the arrivals fill the horizon: gaps scaled to end one mean gap
        # before its close
        due = np.cumsum(gaps) * horizon_s / (gaps.sum() + gaps.mean())
        self._pool(n)
        out = []
        for i, t in enumerate(due):
            it = self.item(i)
            it.due = float(t)
            out.append(it)
        return out

    def prompt_bounds(self) -> tuple[int, int]:
        """Shortest and longest prompt the stream can send."""
        lo, hi = lengths(self.params["prompt"], 2 ** 12)[[0, -1]]
        if self.caps[0] is not None:
            lo, hi = min(lo, self.caps[0]), min(hi, self.caps[0])
        return int(lo), int(hi)
