"""Serving-engine benchmark: paged-KV parity, scheduler behaviour, latency.

Three sections over ``repro.serve.engine`` (run standalone with
``PYTHONPATH=src``); the first two are deterministic and CI-gated via
``check_regression.py``, the third is wall-clock and informational:

  * ``parity``  — the same mixed-length greedy workload through a dense
    (contiguous-cache) engine and a paged engine on the phi-dyadic olmo
    smoke model. Token streams AND per-request logit traces must be
    **bitwise** identical (dyadic 2^-10 weights make the Phi partial sums
    exact, so any divergence is a real indexing bug, not float noise), and
    the paged pool's high-water mark must undercut the contiguous
    allocation. The engine-reported byte counts are cross-checked against
    the closed forms in ``repro.core.perfmodel`` (``kv_cache_bytes`` /
    ``paged_pool_bytes``) — ``model_mismatch_frac`` is gated at 0.
  * ``sched``   — an undersized page pool (the pool floor,
    ``num_pages == max_context/page_size``) that forces mid-decode
    preemption: victims re-queue with their generated prefix and every
    request still finishes with its full budget. Decision counts land in
    the top-level ``scheduler_decisions`` dict, gated **exactly** — a
    silently flipped scheduling decision is the same regression class as
    a flipped dispatch decision.
  * ``latency`` — engine tick wall-time percentiles (the gap every busy
    slot sees between two tokens) and request throughput from the parity
    workload's paged run, read from the engine's ``serve_tick_ms``
    histogram (``repro.obs.metrics``, ``wall_time=True``) — the same registration and percentile code path
    the production launcher reports from, so bench and production can
    never drift apart. Deliberately NOT gated (``p50_ms`` / ``p99_ms`` /
    ``requests_per_s`` match no gated column class): wall time is runner
    noise; the gated story is bytes, ratios and decisions.

``--json PATH`` writes ``BENCH_serve.json`` (schema-versioned); CI
compares it against ``benchmarks/baseline/BENCH_serve.json``.
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, phi_variant
from repro.core import perfmodel
from repro.distributed.sharding import init_params
from repro.models import model
from repro.serve.engine import Engine, Request

SCHEMA = 1


def _round(x: float, digits: int = 6) -> float:
    return float(round(float(x), digits))


def _phi_dyadic_setup():
    """Olmo smoke LM with dyadic (2^-10) weights, Phi-calibrated — the
    bit-exactness recipe from tests/test_dispatch.py."""
    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: jnp.round(x * 1024) / 1024, params)
    batch = model.dummy_batch(cfg, 2, 16, with_labels=False)
    cfg, params, _ = model.calibrate_lm_phi_budgeted(cfg, params, batch)
    return cfg, params


def _requests(rng: np.random.Generator, cfg, n: int, lo: int, hi: int,
              max_new: int) -> list[Request]:
    """Fresh deterministic mixed-length greedy requests (fresh per engine —
    Request carries mutable resume state)."""
    return [Request(rid=i,
                    tokens=[int(t) for t in
                            rng.integers(3, cfg.vocab, int(rng.integers(lo, hi)))],
                    max_new_tokens=max_new, temperature=0.0)
            for i in range(n)]


def _leaf_geometry(cfg, slots: int, context: int) -> dict:
    """(n_scan, kv_heads, head_dim) of the decode cache leaves, for the
    perfmodel cross-check."""
    leaf = jax.tree.leaves(model.decode_state_specs(cfg, slots, context))[0]
    return {"n_scan": leaf.shape[0], "kv_heads": leaf.shape[3],
            "head_dim": leaf.shape[4]}


def main(json_path: str | None = None) -> list[str]:
    rows = ["serve,section,metric,value"]
    serve_cols: dict[str, dict] = {}
    decisions: dict[str, int] = {}

    def emit(section: str, cols: dict) -> None:
        serve_cols[section] = cols
        for metric, v in cols.items():
            rows.append(f"serve,{section},{metric},{v}")

    def absorb(eng: Engine) -> None:
        for k, v in eng.scheduler.report().items():
            decisions[k] = decisions.get(k, 0) + v

    # ---- parity: dense vs paged, bitwise, on the phi-dyadic model --------
    cfg, params = _phi_dyadic_setup()
    slots, ctx, page = 2, 64, 8
    make = lambda: _requests(np.random.default_rng(7), cfg, n=4,  # noqa: E731
                             lo=5, hi=14, max_new=4)

    dense = Engine(cfg, params, batch_slots=slots, max_context=ctx,
                   record_logits=True)
    for r in make():
        dense.submit(r)
    dense_res = {r.rid: r.tokens for r in dense.run()}
    absorb(dense)

    paged = Engine(cfg, params, batch_slots=slots, max_context=ctx,
                   paged=True, page_size=page, record_logits=True,
                   wall_time=True)
    for r in make():
        paged.submit(r)
    paged_res = {r.rid: r.tokens for r in paged.run()}
    absorb(paged)

    assert dense_res == paged_res, \
        f"paged tokens diverge from dense: {dense_res} vs {paged_res}"
    for rid, trace in dense.logit_trace.items():
        for i, (a, b) in enumerate(zip(trace, paged.logit_trace[rid])):
            assert np.array_equal(a, b), \
                f"logits diverge at rid={rid} step={i} (not bitwise)"

    cache = paged.cache_report()
    geo = _leaf_geometry(cfg, slots, ctx)
    model_contig = perfmodel.kv_cache_bytes(slots=slots, context=ctx, **geo)
    model_pool = perfmodel.paged_pool_bytes(
        num_pages=paged.pm.num_pages, page_size=page, **geo)
    mismatch = (abs(cache["contig_cache_bytes"] - model_contig)
                + abs(cache["pool_bytes"] - model_pool))
    assert cache["page_hwm_bytes"] < cache["contig_cache_bytes"], cache
    emit("parity", {
        "contig_cache_bytes": int(cache["contig_cache_bytes"]),
        "pool_bytes": int(cache["pool_bytes"]),
        "page_hwm_bytes": int(cache["page_hwm_bytes"]),
        "cache_saving_ratio": _round(
            cache["contig_cache_bytes"] / cache["page_hwm_bytes"], 4),
        "model_mismatch_frac": _round(
            mismatch / cache["contig_cache_bytes"], 6),
        "requests": len(paged_res),
    })

    # ---- latency: wall-clock from the paged parity run (NOT gated), read
    # from the engine's own metrics histogram — one code path with the
    # production report in launch/serve.py --obs ------------------------
    hist = paged.metrics.get("tick_ms")
    total_s = max(hist.sum() / 1e3, 1e-9)
    emit("latency", {
        "p50_ms": _round(hist.percentile(50), 3),
        "p99_ms": _round(hist.percentile(99), 3),
        "requests_per_s": _round(len(paged_res) / total_s, 3),
    })

    # ---- sched: undersized pool forces preemption + re-queue ------------
    dcfg = get_config("olmo_1b", smoke=True)
    dparams = init_params(model.lm_specs(dcfg), jax.random.PRNGKey(0))
    sctx, spage = 32, 8
    eng = Engine(dcfg, dparams, batch_slots=2, max_context=sctx,
                 paged=True, page_size=spage, num_pages=sctx // spage)
    rng = np.random.default_rng(3)
    want = {}
    for i in range(4):
        toks = [int(t) for t in rng.integers(3, dcfg.vocab, 9)]
        # len-9 prompts bucket to 16 (2 pages); budget 10 pushes decode
        # past position 16 so every request needs a 3rd page mid-flight —
        # with the pool at its floor (4 pages) that is guaranteed dry.
        want[i] = 10
        eng.submit(Request(rid=i, tokens=toks, max_new_tokens=10,
                           temperature=0.0))
    sched_res = {r.rid: r.tokens for r in eng.run()}
    absorb(eng)
    assert {rid: len(t) for rid, t in sched_res.items()} == want, sched_res
    sched = eng.scheduler.report()
    assert sched.get("preempt_pool_dry", 0) > 0, \
        f"pool floor did not force preemption: {sched}"
    rep = eng.cache_report()
    emit("sched", {
        "pool_peak_frac": _round(rep["hwm_pages"] / rep["num_pages"], 4),
        "tokens_per_tick": _round(eng.decoded_tokens / eng.ticks, 4),
        "ticks": eng.ticks,
        "completed": len(sched_res),
    })

    for k, v in sorted(decisions.items()):
        rows.append(f"serve,decisions,{k},{v}")

    if json_path:
        payload = {
            "schema": SCHEMA,
            "kind": "serve",
            "serve": serve_cols,
            "scheduler_decisions": dict(sorted(decisions.items())),
            "config": {"slots": slots, "max_context": ctx,
                       "page_size": page, "sched_pool_pages": sctx // spage},
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2)

    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", nargs="?", const="BENCH_serve.json",
                    default=None, metavar="PATH",
                    help="write structured results (default path "
                         "BENCH_serve.json when the flag is given bare)")
    args = ap.parse_args()
    print("\n".join(main(json_path=args.json)))
