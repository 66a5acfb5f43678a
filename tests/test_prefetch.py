"""Pattern-usage prefetch subsystem: histogram path, policy gates, parity,
the launch-cost crossover, and the bench-regression CI gate.

The calibration usage histogram (``core.patterns.pattern_usage``) drives the
``fused_prefetch`` lowering: skewed histograms size a static PWP gather
buffer, per-M-stripe active sets are recomputed at trace time
(``kernels.phi_fused.stripe_active_sets``), and only referenced PWP rows
reach VMEM. Degenerate histograms must resolve AWAY from the prefetch
lowering, and restricting the match can never change the product (rows with
cold patterns fall through to the exact L2 residual path).
"""
import copy
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.patterns import (
    PhiConfig,
    active_pattern_sets,
    calibrate,
    pattern_usage,
    pattern_weight_products,
    quantize_pwp,
)
from repro.kernels import dispatch, ops

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_policy():
    dispatch.get_policy().reset()
    yield
    dispatch.get_policy().reset()


def zipf_setup(m=256, K=64, n=256, q=128, flip=0.02, seed=0, dyadic=True):
    """Zipf-skewed workload: row prototypes drawn with p ∝ 1/rank², so a
    small head of the calibrated pattern bank covers ≥90% of matches."""
    rng = np.random.default_rng(seed)
    probs = 1.0 / (np.arange(q) + 1.0) ** 2
    probs /= probs.sum()
    protos = (rng.random((q, K)) < 0.25).astype(np.float32)
    a = np.abs(protos[rng.choice(q, m, p=probs)]
               - (rng.random((m, K)) < flip)).astype(np.float32)
    w = rng.standard_normal((K, n)).astype(np.float32)
    if dyadic:
        w = np.round(w * 1024) / 1024            # 2^-10 grid: exact sums
    pats = calibrate(a, PhiConfig(k=16, q=q, iters=6))
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    usage = pattern_usage(a, pats)
    return (jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats), pwp, usage)


# ------------------------------------------------------ histogram basics ----
def test_pattern_usage_histogram_counts_rows():
    a, w, pats, pwp, usage = zipf_setup(m=128)
    T, q1 = usage.shape
    assert (T, q1) == (pats.shape[0], pats.shape[1] + 1)
    # every row-partition lands somewhere: counts sum to M per partition
    assert (usage.sum(axis=1) == 128).all()
    # Zipf head: the top patterns dominate the assigned mass
    assigned = usage[:, :-1]
    top32 = np.sort(assigned, axis=1)[:, ::-1][:, :32].sum()
    assert top32 >= 0.9 * assigned.sum()


def test_pattern_usage_empty_calibration_is_all_zero():
    pats = np.zeros((4, 16, 16), np.uint8)
    usage = pattern_usage(np.zeros((0, 64), np.float32), pats)
    assert usage.shape == (4, 17) and usage.sum() == 0


def test_active_sets_degenerate_histograms():
    # empty calibration: nothing known -> no skew
    assert active_pattern_sets(np.zeros((4, 129), np.int64)) == (None, 1.0)
    # uniform usage: covering 90% needs ~0.9·q patterns -> no win
    uni = np.full((4, 129), 10, np.int64)
    assert active_pattern_sets(uni) == (None, 1.0)
    # single pattern on a tiny bank (q ≤ pad_to): a gather can't beat
    # streaming 8 rows
    tiny = np.zeros((4, 9), np.int64)
    tiny[:, 0] = 100
    assert active_pattern_sets(tiny) == (None, 1.0)
    # unassigned-dominated histogram: L1 barely used, nothing to prefetch
    cold = np.zeros((4, 129), np.int64)
    cold[:, -1] = 1000                            # none-slot
    cold[:, 0] = 10
    assert active_pattern_sets(cold) == (None, 1.0)


def test_active_sets_skewed_histogram():
    _, _, pats, _, usage = zipf_setup()
    active, frac = active_pattern_sets(usage)
    assert active is not None
    T, p_active = active.shape
    q = usage.shape[1] - 1
    assert p_active % 8 == 0 and p_active <= q // 2
    assert frac == pytest.approx((p_active + 1) / (q + 1))
    # hottest pattern of each partition is in its active set
    hottest = usage[:, :-1].argmax(axis=1)
    for t in range(T):
        assert hottest[t] in active[t]


# ------------------------------------------------------------ policy gates ---
def test_degenerate_histograms_resolve_away_from_prefetch():
    pol = dispatch.get_policy()
    for tag, usage in (
            ("uniform", np.full((4, 129), 10, np.int64)),
            ("empty", np.zeros((4, 129), np.int64)),
            ("single_tiny", np.diag([100] * 4) @ np.ones((4, 9), np.int64))):
        d = pol.resolve(site=f"t.degen_{tag}", m=96, k_dim=64, n=128, t=4,
                        q=usage.shape[1] - 1, usage=usage)
        assert d.impl != "fused_prefetch", (tag, d)
        assert d.impl == "fused" and d.usage_ratio is None


def test_viable_gate_prefers_prefetch_only_with_skew():
    _, _, pats, _, usage = zipf_setup()
    T, q = pats.shape[0], pats.shape[1]
    assert ops.fused_shape_viable(256, 64, 256, T, q) == "fused"
    assert ops.fused_shape_viable(256, 64, 256, T, q,
                                  usage=usage) == "fused_prefetch"
    uni = np.full((T, q + 1), 7, np.int64)
    assert ops.fused_shape_viable(256, 64, 256, T, q, usage=uni) == "fused"


def test_usage_registry_feeds_site_resolution():
    """Sites whose histogram arrives via ``register_usage`` (the LM
    calibration path — in-graph params are tracers at trace time) resolve
    fused_prefetch without usage ever being passed at the call."""
    a, w, pats, pwp, usage = zipf_setup()
    pol = dispatch.get_policy()
    pol.register_usage("t.reg", usage)
    assert pol.usage_for("t.reg") is not None
    out = pol.matmul(a, w, pats, pwp, site="t.reg")
    ref = ops.phi_matmul(a, w, pats, pwp, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)
    dec = pol.decisions()
    assert any(s == "t.reg" and i == "fused_prefetch"
               and r.startswith("pattern_usage_prefetch")
               for (s, i, r) in dec), dec
    # re-registration with the same shape accumulates (pooled layers)
    pol.register_usage("t.reg", usage)
    assert pol.usage_for("t.reg").sum() == 2 * usage.sum()


def test_prefetch_override_demotes_without_skew():
    pol = dispatch.get_policy()
    d = pol.resolve(site="t.noskew", m=96, k_dim=64, n=128, t=4, q=16,
                    override="fused_prefetch")
    assert d.impl == "fused" and d.reason == "no_skew_demotes_fused_prefetch"
    with dispatch.spmd_region():
        d = pol.resolve(site="t.spmdpf", m=96, k_dim=64, n=128, t=4, q=16,
                        override="fused_prefetch")
    assert d.impl == "coo" and d.reason == "spmd_region_demotes_fused_prefetch"
    # skew measured but the compact working set busts VMEM (large K): the
    # demotion reason must name the budget, not the calibration
    T = 1 << 12
    skewed = np.zeros((T, 129), np.int64)
    skewed[:, :8] = 100
    d = pol.resolve(site="t.vmempf", m=256, k_dim=1 << 16, n=512, t=T,
                    q=128, override="fused_prefetch", usage=skewed)
    assert d.impl == "fused_stream"
    assert d.reason == "vmem_gate_streams_fused_prefetch"


def test_old_checkpoint_without_usage_leaf_restores(tmp_path):
    """Pre-PR-4 phi checkpoints lack the ``usage`` leaf; restoring into the
    new spec tree zero-fills it (missing_ok) instead of raising, and the
    all-zero histogram reads as "no histogram" downstream."""
    from repro.checkpoint.checkpoint import restore_tree, save_tree

    old_tree = {"w": np.ones((4, 4), np.float32),
                "phi_w": {"pwp": np.ones((2, 9, 4), np.float32)}}
    save_tree(str(tmp_path / "step"), old_tree)
    like = {"w": np.zeros((4, 4), np.float32),
            "phi_w": {"pwp": np.zeros((2, 9, 4), np.float32),
                      "usage": np.zeros((2, 9), np.int32)}}
    with pytest.raises(KeyError, match="missing leaf"):
        restore_tree(str(tmp_path / "step"), like)
    tree, _ = restore_tree(str(tmp_path / "step"), like,
                           missing_ok=("usage",))
    assert np.asarray(tree["phi_w"]["usage"]).sum() == 0
    np.testing.assert_array_equal(np.asarray(tree["phi_w"]["pwp"]),
                                  old_tree["phi_w"]["pwp"])
    # zero histograms are skipped by the registry walk and show no skew
    assert dispatch.register_usage_from_params(tree) == 0
    assert active_pattern_sets(np.asarray(tree["phi_w"]["usage"])) \
        == (None, 1.0)


def test_phi_fused_prefetch_requires_usage_or_p_active():
    a, w, pats, pwp, usage = zipf_setup(m=64)
    with pytest.raises(ValueError, match="usage histogram|gather size"):
        ops.phi_fused_prefetch(a, pats, pwp, w)
    with pytest.raises(ValueError, match="no exploitable skew"):
        uni = np.full_like(usage, 3)
        ops.phi_fused_prefetch(a, pats, pwp, w, usage=uni)


# ------------------------------------------------------------- exactness ----
@pytest.mark.parametrize("shape", [(128, 64, 128), (200, 32, 128),
                                   (64, 128, 256), (300, 64, 384)])
def test_prefetch_matches_fused_bitwise_on_dyadic_sweep(shape):
    """Restricting the match to the active sets changes the decomposition,
    never the product: under dyadic 2^-10 weights every Phi partial sum is
    exactly representable, so fused and fused_prefetch — despite assigning
    different patterns to cold rows — produce BIT-identical outputs."""
    m, K, n = shape
    a, w, pats, pwp, usage = zipf_setup(m=m, K=K, n=n, q=128,
                                        seed=m + K + n, dyadic=True)
    out_p, nnz_p = ops.phi_fused_prefetch(a, pats, pwp, w, usage=usage)
    out_f, nnz_f = ops.phi_fused(a, pats, pwp, w)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(a) @ np.asarray(w),
                               rtol=1e-4, atol=1e-3)
    assert np.array_equal(np.asarray(out_p), np.asarray(out_f))
    # rows whose pattern fell outside the active set land on the residual:
    # the restricted assignment can only have MORE L2 entries
    assert int(np.asarray(nnz_p).sum()) >= int(np.asarray(nnz_f).sum())


def test_prefetch_int8_pwp_dequant():
    """In-kernel dequant of the gathered int8 rows matches running the same
    restricted assignment on pre-dequantized f32 rows. (The full-bank "ref"
    is NOT the oracle here: with quantized PWPs the per-row quantization
    error depends on which pattern was assigned, and the restricted
    assignment legitimately differs on cold rows.)"""
    a, w, pats, pwp, usage = zipf_setup(m=128, dyadic=False)
    q8, scale = quantize_pwp(pwp)
    out, _ = ops.phi_fused_prefetch(a, pats, q8, w, usage=usage,
                                    pwp_scale=scale)
    deq = q8.astype(jnp.float32) * scale[..., None]
    want, _ = ops.phi_fused_prefetch(a, pats, deq, w, usage=usage)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
    # and the quantized result stays within int8 error of the exact product
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(a) @ np.asarray(w),
                               rtol=5e-2, atol=0.35)


def test_stripe_active_sets_shape_and_content():
    from repro.kernels.phi_fused import stripe_active_sets
    a, w, pats, pwp, usage = zipf_setup(m=256)
    active = stripe_active_sets(a, pats, 16, 128)
    assert active.shape == (2, pats.shape[0], 16)
    assert active.dtype == jnp.int32
    # index range is the pattern bank
    act = np.asarray(active)
    assert act.min() >= 0 and act.max() < pats.shape[1]


def test_stripe_active_sets_returns_match_histogram():
    from repro.kernels.phi_fused import stripe_active_sets
    a, w, pats, pwp, usage = zipf_setup(m=256)
    T, q = pats.shape[0], pats.shape[1]
    active, hist = stripe_active_sets(a, pats, 16, 128, return_hist=True)
    assert active.shape == (2, T, 16) and hist.shape == (T, q + 1)
    h = np.asarray(hist)
    # every row-partition lands somewhere (col q = unmatched)
    assert (h.sum(axis=1) == 256).all()
    # the in-graph histogram agrees with the host-side calibration one
    # (same activations, same bank, same strict match rule)
    np.testing.assert_array_equal(h, np.asarray(usage))
    # non-multiple M: zero-padding rows must NOT count as unmatched —
    # the kernel wrapper passes the unpadded row count through
    import jax
    out, nnz, h2 = ops.phi_fused_prefetch(a[:200], pats, pwp, w,
                                          p_active=16, return_hist=True)
    jax.block_until_ready(out)
    h2 = np.asarray(h2)
    assert (h2.sum(axis=1) == 200).all(), h2.sum(axis=1)


def test_top_p_sets_orders_by_mass():
    from repro.core.patterns import top_p_sets
    hist = np.zeros((2, 9), np.int64)
    hist[0, [3, 1, 5]] = [100, 50, 10]
    hist[1, [7, 0]] = [9, 8]
    sets = top_p_sets(hist, 2)
    assert sets.shape == (2, 2) and sets.dtype == np.int32
    assert list(sets[0]) == [3, 1] and list(sets[1]) == [7, 0]
    # p is clamped to the bank size
    assert top_p_sets(hist, 99).shape == (2, 8)


def test_runtime_sets_arg_validation():
    a, w, pats, pwp, usage = zipf_setup(m=128)
    T = pats.shape[0]
    bad = jnp.zeros((T, 3), jnp.int32)
    with pytest.raises(ValueError, match="runtime_sets shape"):
        ops.phi_fused_prefetch(a, pats, pwp, w, p_active=16,
                               runtime_sets=bad)
    good = jnp.zeros((T, 16), jnp.int32)
    with pytest.raises(ValueError, match="return_hist requires"):
        ops.phi_fused_prefetch(a, pats, pwp, w, runtime_sets=good,
                               return_hist=True)


# ------------------------------- runtime-telemetry-driven active sets -------
def test_runtime_match_telemetry_replaces_prepass_bitwise():
    """ROADMAP item: the first trace runs the stripe_active_sets pre-pass
    and streams its match histogram into the policy's per-site aggregates
    (_record_nnz); later traces derive the gather sets from that runtime
    telemetry instead (reason suffix "_runtime_sets") — with BIT-identical
    results under dyadic weights, and the pre-pass as fallback."""
    import jax

    a, w, pats, pwp, usage = zipf_setup(m=256, dyadic=True)
    T, q = pats.shape[0], pats.shape[1]
    pol = dispatch.get_policy()
    pol.register_usage("t.rt", usage)

    d1 = pol.resolve(site="t.rt", m=256, k_dim=64, n=256, t=T, q=q)
    assert d1.impl == "fused_prefetch" and d1.runtime_sets is None
    assert pol.runtime_usage_for("t.rt") is None     # nothing executed yet

    out1 = pol.matmul(a, w, pats, pwp, site="t.rt")  # pre-pass path
    jax.effects_barrier()
    rt = pol.runtime_usage_for("t.rt")
    assert rt is not None and rt.shape == (T, q + 1)
    # aggregated runtime histogram == the calibration histogram here (same
    # activations through the same matcher math)
    np.testing.assert_array_equal(rt, np.asarray(usage))

    d2 = pol.resolve(site="t.rt", m=256, k_dim=64, n=256, t=T, q=q)
    assert d2.impl == "fused_prefetch"
    assert d2.reason.endswith("_runtime_sets")
    assert d2.runtime_sets is not None
    assert d2.runtime_sets.shape == (T, d2.p_active)

    out2 = pol.matmul(a, w, pats, pwp, site="t.rt")  # runtime-sets path
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    out_coo = ops.phi_matmul(a, w, pats, pwp, impl="coo")
    assert np.array_equal(np.asarray(out2), np.asarray(out_coo))

    # telemetry keeps aggregating across executions
    jax.effects_barrier()
    rt2 = pol.runtime_usage_for("t.rt")
    assert rt2.sum() == rt.sum()  # runtime-sets path adds no pre-pass hist


def test_runtime_sets_fall_back_to_prepass_for_fresh_site():
    """A site with a calibration histogram but no executions keeps using
    the trace-time pre-pass (runtime_sets is None on every resolve until
    telemetry lands)."""
    _, _, pats, _, usage = zipf_setup(m=128)
    T, q = pats.shape[0], pats.shape[1]
    pol = dispatch.get_policy()
    pol.register_usage("t.fresh", usage)
    for _ in range(3):
        d = pol.resolve(site="t.fresh", m=128, k_dim=64, n=256, t=T, q=q)
        assert d.impl == "fused_prefetch" and d.runtime_sets is None


def test_perfmodel_prepass_toggle_drops_exact_bytes():
    """phi_kernel_traffic(prefetch_prepass=False) models the runtime-sets
    kernel: exactly one (M, K) f32 activation read and one full-bank read
    cheaper than the pre-pass variant, identical everywhere else."""
    from repro.core.perfmodel import GemmShape, phi_kernel_traffic
    shape, k, q = GemmShape(512, 128, 256), 16, 128
    on = phi_kernel_traffic(shape, k=k, q=q, pwp_usage=0.25)
    off = phi_kernel_traffic(shape, k=k, q=q, pwp_usage=0.25,
                             prefetch_prepass=False)
    T = shape.k // k
    assert on["fused_prefetch"].a_bytes - off["fused_prefetch"].a_bytes \
        == shape.m * shape.k * 4
    assert (on["fused_prefetch"].patterns_bytes
            - off["fused_prefetch"].patterns_bytes) == T * q * k * 4
    for entry in ("fused", "fused_stream", "three_kernel"):
        assert on[entry].total == off[entry].total


# --------------------------------------- acceptance: Zipf-skewed workload ---
def test_acceptance_zipf_policy_prefetch_bitwise_and_traffic():
    """ISSUE acceptance: on a Zipfian workload (top 32 of 128 patterns cover
    ≥90% of matches) the policy resolves ``fused_prefetch``, the output is
    BIT-identical to forced-``coo`` under dyadic 2^-10 weights, and the
    modelled PWP HBM bytes are ≤ 0.5× of ``fused_stream`` for the shape."""
    a, w, pats, pwp, usage = zipf_setup(m=256, K=64, n=256, q=128,
                                        dyadic=True)
    T, q = pats.shape[0], pats.shape[1]
    active, frac = active_pattern_sets(usage)
    assert active is not None and frac <= 0.5

    pol = dispatch.get_policy()
    out_pol = pol.matmul(a, w, pats, pwp, site="t.zipf", usage=usage)
    out_coo = ops.phi_matmul(a, w, pats, pwp, impl="coo")
    assert np.array_equal(np.asarray(out_pol), np.asarray(out_coo)), \
        f"differ by {np.abs(np.asarray(out_pol) - np.asarray(out_coo)).max()}"
    dec = pol.decisions()
    assert any(s == "t.zipf" and i == "fused_prefetch"
               and r.startswith("pattern_usage_prefetch")
               for (s, i, r) in dec), dec
    # decision telemetry carries the measured usage fraction + gather size
    d = pol.resolve(site="t.zipf2", m=256, k_dim=64, n=256, t=T, q=q,
                    usage=usage)
    assert d.usage_ratio == pytest.approx(frac)
    assert d.p_active == active.shape[-1] and len(d.blocks) == 2

    from repro.core.perfmodel import GemmShape, phi_kernel_traffic
    tr = phi_kernel_traffic(GemmShape(256, 64, 256), k=16, q=q,
                            pwp_usage=frac)
    assert tr["fused_prefetch"].pwp_bytes <= 0.5 * tr["fused_stream"].pwp_bytes
    assert tr["fused_prefetch"].idx_bytes == 0
    assert tr["fused_prefetch"].residual_bytes == 0


def test_traffic_model_prefetch_at_full_usage_is_dominated():
    """With no measured skew (usage 1.0) the prefetch entry pays the
    pre-pass for nothing — strictly more bytes than "fused". This is why
    the policy only resolves it on a skewed histogram."""
    from repro.core.perfmodel import GemmShape, phi_kernel_traffic
    tr = phi_kernel_traffic(GemmShape(2048, 256, 512), k=16, q=128)
    assert tr["fused_prefetch"].total > tr["fused"].total


# -------------------------------------------- launch-cost crossover (coo) ---
def test_launch_cost_crossover_boundary():
    """The modelled-bytes-vs-launch-cost threshold is monotone in M with a
    single flip: tiny M (decode steps) prefers the XLA path, at scale the
    fused kernels win."""
    ks = dict(k_dim=256, n=512, t=16, q=128)
    ms = [1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096]
    prefers = [ops.launch_cost_prefers_coo(m, **ks) for m in ms]
    assert prefers[0] is True and prefers[-1] is False
    flips = sum(1 for x, y in zip(prefers, prefers[1:]) if x != y)
    assert flips == 1, list(zip(ms, prefers))
    # the crossover sits where the M-proportional gather traffic overtakes
    # the fixed full-bank streams + one launch — O(q) rows, not O(1)/O(M·K)
    boundary = ms[prefers.index(False)]
    assert 16 <= boundary <= 512


def test_policy_crossover_picks_coo_on_tpu_backend_only(monkeypatch):
    pol = dispatch.get_policy()
    # interpret backend (this container): tiny M stays on the fused kernel
    d = pol.resolve(site="t.tinycpu", m=4, k_dim=256, n=512, t=16, q=128)
    assert d.impl == "fused"
    # native backend: the crossover demotes tiny M to the XLA path ...
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    d = pol.resolve(site="t.tinytpu", m=4, k_dim=256, n=512, t=16, q=128)
    assert d.impl == "coo" and d.reason == "launch_cost_crossover"
    # ... but an explicit override still wins (the A/B harness contract)
    d = pol.resolve(site="t.tinyov", m=4, k_dim=256, n=512, t=16, q=128,
                    override="coo")
    assert d.reason == "call_override"


def test_skew_picks_prefetch_off_chip_only(monkeypatch):
    """On "tpu" the compact banks are gathered in HBM before the kernel, so
    a skewed histogram no longer picks fused_prefetch there: the site runs
    plain ``fused`` and fused_prefetch stays reachable as an override."""
    _, _, _, _, usage = zipf_setup()
    T, q = usage.shape[0], usage.shape[1] - 1
    pol = dispatch.get_policy()
    shape = dict(m=4096, k_dim=64, n=256, t=T, q=q, usage=usage)
    d = pol.resolve(site="t.skewcpu", **shape)
    assert d.impl == "fused_prefetch"
    assert d.reason == "pattern_usage_prefetch_interpret"
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    d = pol.resolve(site="t.skewtpu", **shape)
    assert d.impl == "fused" and d.reason == "single_device_default_native"
    d = pol.resolve(site="t.skewtpuov", override="fused_prefetch", **shape)
    assert d.impl == "fused_prefetch" and d.reason == "call_override"


# ----------------------------------------- usage checkpoint extra round-trip
def test_usage_survives_checkpoint_extra_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager

    _, _, _, _, usage = zipf_setup(m=64)
    usage_dict = {"fc1": usage, "head": usage * 2}
    extra = dispatch.usage_checkpoint_extra(usage_dict)
    assert "phi_usage" in extra

    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(7, {"x": jnp.arange(3.0)}, {"loader": {"step": 7}, **extra})
    restored = dispatch.usage_from_checkpoint_extra(mgr.latest_extra())
    assert set(restored) == {"fc1", "head"}
    np.testing.assert_array_equal(restored["fc1"], usage)
    np.testing.assert_array_equal(restored["head"], usage * 2)
    # restored histograms drive the gate exactly like live ones
    act_live, frac_live = active_pattern_sets(usage)
    act_rest, frac_rest = active_pattern_sets(restored["fc1"])
    np.testing.assert_array_equal(act_live, act_rest)
    assert frac_live == frac_rest
    # empty/no-usage paths stay silent
    assert dispatch.usage_checkpoint_extra({}) == {}
    assert dispatch.usage_from_checkpoint_extra(None) == {}


def test_lm_calibration_stores_and_registers_usage():
    """The LM calibration path writes the histogram into the params tree
    (checkpoint persistence) AND the policy registry (trace-time gate), and
    ``register_usage_from_params`` rebuilds the registry after a restore."""
    import jax
    from repro.configs import get_config, phi_variant
    from repro.distributed.sharding import init_params
    from repro.models import model

    cfg = phi_variant(get_config("olmo_1b", smoke=True), timesteps=2, q=16)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    batch = model.dummy_batch(cfg, 2, 8, with_labels=False)
    params, _ = model.calibrate_lm_phi(cfg, params, batch)

    pol = dispatch.get_policy()
    sites = [s for s in pol._usage if s.startswith("lm.")]
    assert sites, "calibration registered no usage histograms"
    # histograms ride in the params tree with matching spec shapes
    found = []

    def walk(node):
        if isinstance(node, dict):
            for k, v in node.items():
                if k.startswith("phi_") and isinstance(v, dict):
                    assert "usage" in v, k
                    found.append(np.asarray(v["usage"]))
                elif isinstance(v, dict):
                    walk(v)

    walk(params)
    assert found and all(u.sum() > 0 for u in found)
    # a fresh policy (post-restore) rebuilds the registry from the params
    dispatch.get_policy().reset()
    n = dispatch.register_usage_from_params(params)
    assert n == len(sites)
    assert set(s for s in dispatch.get_policy()._usage) == set(sites)


# ------------------------------------------------- bench-regression gate ----
def _run_gate(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "check_regression.py"), *args],
        capture_output=True, text=True)


def test_check_regression_passes_on_committed_baseline(tmp_path):
    baseline = os.path.join(REPO, "benchmarks", "baseline",
                            "BENCH_kernels.json")
    assert os.path.exists(baseline), "committed baseline missing"
    # the baseline vs itself is the determinism floor: must pass
    r = _run_gate("--current", baseline)
    assert r.returncode == 0, r.stdout + r.stderr


def test_check_regression_fails_on_doctored_bytes_and_decisions(tmp_path):
    baseline = os.path.join(REPO, "benchmarks", "baseline",
                            "BENCH_kernels.json")
    with open(baseline) as f:
        base = json.load(f)

    # inflated modelled HBM bytes -> nonzero exit naming the column
    doc = copy.deepcopy(base)
    tag = next(iter(doc["hbm_model_bytes"]))
    col = next(c for c, v in doc["hbm_model_bytes"][tag].items()
               if isinstance(v, (int, float)) and not c.endswith("ratio"))
    doc["hbm_model_bytes"][tag][col] *= 1.5
    p = tmp_path / "inflated.json"
    p.write_text(json.dumps(doc))
    r = _run_gate("--current", str(p))
    assert r.returncode == 1 and "modelled bytes grew" in r.stdout

    # a silently flipped dispatch decision -> nonzero exit
    doc2 = copy.deepcopy(base)
    assert doc2["dispatch_decisions"], "baseline carries no decisions"
    doc2["dispatch_decisions"][0]["impl"] = "coo" \
        if doc2["dispatch_decisions"][0]["impl"] != "coo" else "fused"
    p2 = tmp_path / "flipped.json"
    p2.write_text(json.dumps(doc2))
    r = _run_gate("--current", str(p2))
    assert r.returncode == 1 and "resolved impl changed" in r.stdout

    # schema bump -> nonzero exit (intentional changes update the baseline)
    doc3 = copy.deepcopy(base)
    doc3["schema"] = base["schema"] + 1
    p3 = tmp_path / "schema.json"
    p3.write_text(json.dumps(doc3))
    r = _run_gate("--current", str(p3))
    assert r.returncode == 1 and "schema" in r.stdout

    # pwp_ratio is a smaller-is-better streamed fraction, NOT an advantage
    # ratio: growth must fail (and shrinking must not)
    doc4 = copy.deepcopy(base)
    skew = next(t for t in doc4["hbm_model_bytes"] if t.startswith("skew"))
    doc4["hbm_model_bytes"][skew]["pwp_ratio"] *= 2.0
    p4 = tmp_path / "usage.json"
    p4.write_text(json.dumps(doc4))
    r = _run_gate("--current", str(p4))
    assert r.returncode == 1 and "pwp_ratio" in r.stdout
    doc5 = copy.deepcopy(base)
    doc5["hbm_model_bytes"][skew]["pwp_ratio"] *= 0.5
    p5 = tmp_path / "usage_better.json"
    p5.write_text(json.dumps(doc5))
    assert _run_gate("--current", str(p5)).returncode == 0
