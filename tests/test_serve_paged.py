"""Paged serving engine + telemetry scheduler edge cases.

The acceptance spine: a paged engine must be **token-identical** to the
contiguous engine on a mixed-length greedy workload — bitwise at the
logits level under dyadic 2^-10 weights (Phi partial sums are exact on
that grid, so any divergence is an indexing bug) — while touching fewer
cache bytes. Around it: preemption round-trips, pool exhaustion,
family capability gates, the over-long-prompt contract, and scheduler
determinism/unit behaviour.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, phi_variant
from repro.distributed.sharding import init_params
from repro.models import model
from repro.serve.engine import Engine, Request, bucket_len
from repro.serve.scheduler import SchedulerConfig, TelemetryScheduler


def _dense_setup(arch="olmo_1b"):
    cfg = get_config(arch, smoke=True)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _requests(cfg, lens, max_new, seed=11):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=[int(t) for t in
                                   rng.integers(3, cfg.vocab, plen)],
                    max_new_tokens=max_new, temperature=0.0)
            for i, plen in enumerate(lens)]


# ---------------------------------------------------------------- parity --

def test_paged_bitwise_identical_to_dense_phi_dyadic():
    """Mixed-length greedy workload, phi-dyadic weights: the paged engine's
    tokens AND per-request logit traces match the contiguous engine
    bitwise, and the page pool's high-water mark undercuts the contiguous
    allocation."""
    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    params = jax.tree.map(lambda x: jnp.round(x * 1024) / 1024, params)
    batch = model.dummy_batch(cfg, 2, 16, with_labels=False)
    cfg, params, _ = model.calibrate_lm_phi_budgeted(cfg, params, batch)

    lens, max_new = (5, 11, 7), 3
    dense = Engine(cfg, params, batch_slots=2, max_context=64,
                   record_logits=True)
    for r in _requests(cfg, lens, max_new):
        dense.submit(r)
    dense_res = {r.rid: r.tokens for r in dense.run()}

    paged = Engine(cfg, params, batch_slots=2, max_context=64,
                   paged=True, page_size=8, record_logits=True)
    for r in _requests(cfg, lens, max_new):
        paged.submit(r)
    paged_res = {r.rid: r.tokens for r in paged.run()}

    assert dense_res == paged_res
    assert set(dense.logit_trace) == set(paged.logit_trace)
    for rid in dense.logit_trace:
        for a, b in zip(dense.logit_trace[rid], paged.logit_trace[rid]):
            assert np.array_equal(a, b), f"rid {rid}: logits not bitwise"

    cache = paged.cache_report()
    assert cache["hwm_pages"] >= 1
    assert cache["page_hwm_bytes"] < cache["contig_cache_bytes"]


# ------------------------------------------------------------- preemption --

def test_preemption_roundtrip_token_identical():
    """A pool at its floor forces mid-decode preemption; the preempted
    requests resume with their generated prefix and finish with streams
    identical to an unconstrained run."""
    cfg, params = _dense_setup()
    lens, max_new = (9, 9, 9, 9), 10

    free = Engine(cfg, params, batch_slots=2, max_context=32,
                  paged=True, page_size=8)
    for r in _requests(cfg, lens, max_new):
        free.submit(r)
    free_res = {r.rid: r.tokens for r in free.run()}
    assert free.scheduler.report().get("preempt_pool_dry", 0) == 0

    tight = Engine(cfg, params, batch_slots=2, max_context=32,
                   paged=True, page_size=8, num_pages=4)
    for r in _requests(cfg, lens, max_new):
        tight.submit(r)
    tight_res = {r.rid: r.tokens for r in tight.run()}
    sched = tight.scheduler.report()
    assert sched.get("preempt_pool_dry", 0) > 0, sched
    assert sched.get("requeue_preempted", 0) > 0, sched
    assert tight_res == free_res


def test_pool_exhaustion_blocks_admission_then_drains():
    """When the pool cannot back a new prompt's bucket the pick re-queues
    (admit_blocked_pool) and admits after a retire frees pages — every
    request completes with its full budget."""
    cfg, params = _dense_setup()
    eng = Engine(cfg, params, batch_slots=2, max_context=32,
                 paged=True, page_size=8, num_pages=4)
    reqs = _requests(cfg, (9, 9, 9, 9), 10)
    for r in reqs:
        eng.submit(r)
    res = {r.rid: r.tokens for r in eng.run()}
    assert eng.scheduler.report().get("admit_blocked_pool", 0) > 0
    assert {rid: len(t) for rid, t in res.items()} == \
        {r.rid: r.max_new_tokens for r in reqs}


# ------------------------------------------------------------------ gates --

def test_paged_gate_keeps_dense_slots_for_ssm():
    """Recurrent families have no sequence axis to page: paged=True is
    gated off (raw-length prefill, dense state) and the gate is counted."""
    cfg, params = _dense_setup("mamba2_2p7b")
    eng = Engine(cfg, params, batch_slots=2, max_context=32,
                 paged=True, page_size=8)
    assert not eng.paged and not eng.bucketed
    assert eng.scheduler.report().get("paged_gate_dense") == 1
    for r in _requests(cfg, (5, 8), 3):
        eng.submit(r)
    res = eng.run()
    assert {r.rid: len(r.tokens) for r in res} == {0: 3, 1: 3}


def test_paged_state_specs_rejects_unpageable_family():
    cfg = get_config("mamba2_2p7b", smoke=True)
    with pytest.raises(ValueError):
        model.paged_state_specs(cfg, num_pages=4, page_size=8)


# -------------------------------------------------------- prompt contract --

def test_bucket_len_raises_beyond_cap():
    assert bucket_len(5, 64) == 8
    assert bucket_len(64, 64) == 64
    with pytest.raises(ValueError):
        bucket_len(65, 64)


def test_submit_rejects_overlong_prompt():
    """A prompt that cannot leave room for a single generated token is
    rejected at submit(), not at admit time."""
    cfg, params = _dense_setup()
    eng = Engine(cfg, params, batch_slots=2, max_context=32)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, tokens=list(range(3, 35)),
                           max_new_tokens=2, temperature=0.0))
    eng.submit(Request(rid=1, tokens=list(range(3, 34)),
                       max_new_tokens=2, temperature=0.0))


# -------------------------------------------------------------- scheduler --

def test_scheduler_deterministic_across_runs():
    """Two identical paged runs under a fixed seed produce identical
    results and identical decision counts."""
    cfg, params = _dense_setup()

    def go():
        eng = Engine(cfg, params, batch_slots=2, max_context=32,
                     paged=True, page_size=8, num_pages=4, seed=0)
        for r in _requests(cfg, (9, 5, 9, 12), 6):
            eng.submit(r)
        res = {r.rid: r.tokens for r in eng.run()}
        return res, eng.scheduler.report()

    res_a, dec_a = go()
    res_b, dec_b = go()
    assert res_a == res_b
    assert dec_a == dec_b


def _req(rid, plen):
    return Request(rid=rid, tokens=list(range(3, 3 + plen)),
                   max_new_tokens=4, temperature=0.0)


def test_scheduler_warmup_single_on_cold_sites():
    s = TelemetryScheduler()
    q = [_req(0, 5), _req(1, 5)]
    snap = {"sites": 3, "warm": False, "mean_usage_ratio": 0.5}
    picks = s.select(q, free_slots=2, cap=64, snapshot=snap)
    assert [p.rid for p in picks] == [0] and len(q) == 1
    assert s.report() == {"admit_warmup_single": 1}


def test_scheduler_skew_cohort_batches_same_bucket():
    """Skewed warm telemetry admits the largest same-prefill-bucket cohort
    in submission order; ties break to the smallest bucket."""
    s = TelemetryScheduler()
    # buckets: 8, 16, 8, 16, 16 -> cohort {16: [1, 3, 4]} wins
    q = [_req(0, 7), _req(1, 9), _req(2, 6), _req(3, 12), _req(4, 16)]
    snap = {"sites": 3, "warm": True, "mean_usage_ratio": 0.3}
    picks = s.select(q, free_slots=2, cap=64, snapshot=snap)
    assert [p.rid for p in picks] == [1, 3]
    assert [r.rid for r in q] == [0, 2, 4]
    assert s.report() == {"admit_skew_cohort": 2}
    # flat usage -> FIFO
    picks = s.select(q, free_slots=2, cap=64,
                     snapshot={"sites": 3, "warm": True,
                               "mean_usage_ratio": 1.0})
    assert [p.rid for p in picks] == [0, 2]


def test_scheduler_pick_victim_most_remaining_then_youngest():
    s = TelemetryScheduler(SchedulerConfig())
    assert s.pick_victim([(0, 3, 10), (1, 7, 4), (2, 7, 9)]) == 2
    assert s.report() == {"preempt_pool_dry": 1}
    with pytest.raises(ValueError):
        s.pick_victim([])
