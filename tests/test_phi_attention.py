"""Phi-sparse flash attention: exact score decomposition, bitwise parity of
the XLA lowering with dense flash, dispatch gating, the spikformer
end-to-end A/B acceptance, and the HBM traffic-model criterion.

The exactness chain under test (paper losslessness applied to attention):
binary spike Q/K make every score partial product exact, so the Phi
L1 (pattern gather) + L2 (±1 residual) split recomposes the dense scores
*bitwise* under any contraction order. The pure-XLA lowering then reuses
``models.flash._flash_fwd_impl`` verbatim, so its output is bit-identical
to ``flash_attention``; the Pallas kernel owns its accumulator and matches
to ~1 ulp of XLA fusion rounding (scores still bitwise-exact).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.patterns import PhiConfig, calibrate
from repro.core.perfmodel import phi_attention_traffic
from repro.kernels import dispatch, ops
from repro.kernels.phi_attention import (attn_score_block,
                                         phi_flash_attention_pallas,
                                         phi_flash_attention_xla)
from repro.models import flash


@pytest.fixture(autouse=True)
def _fresh_policy():
    dispatch.get_policy().reset()
    yield
    dispatch.get_policy().reset()


def _spikes(shape, seed=0, density=0.1):
    return jnp.asarray(
        (np.random.default_rng(seed).random(shape) < density), jnp.float32)


@pytest.fixture(scope="module")
def attn_setup():
    rng = np.random.default_rng(0)
    B, S, H, D = 2, 72, 2, 32
    q = _spikes((B, S, H, D), 1)
    k = _spikes((B, S, H, D), 2)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    acts = (rng.random((256, D)) < 0.1).astype(np.float32)
    pats = calibrate(acts, PhiConfig(k=16, q=64))
    return q, k, v, pats


# ------------------------------------------------------------- score block ---
def test_score_block_bitwise_exact(attn_setup):
    q, k, v, pats = attn_setup
    kt = jnp.moveaxis(k, 2, 1)[0, 0]                     # (S, D)
    qi = jnp.moveaxis(q, 2, 1)[0, 0]
    s, nnz = attn_score_block(kt, qi, jnp.asarray(pats, jnp.float32))
    ref = jnp.dot(qi, kt.T)
    assert bool(jnp.all(s == ref))
    assert int(nnz) >= 0


def test_score_block_ragged_tail():
    # T·kp < D: the uncovered columns contract densely, still exact.
    q = _spikes((8, 24), 3)
    k = _spikes((16, 24), 4)
    pats = calibrate((np.random.default_rng(5).random((64, 16)) < 0.2
                      ).astype(np.float32), PhiConfig(k=16, q=32))
    s, _ = attn_score_block(k, q, jnp.asarray(pats, jnp.float32))
    assert bool(jnp.all(s == jnp.dot(q, k.T)))


# ------------------------------------------- lowerings vs dense flash ---
MASKS = [(False, None, None), (True, None, None), (True, 16, None),
         (True, None, 16)]


@pytest.mark.parametrize("causal,window,chunk", MASKS)
def test_xla_lowering_bitwise_vs_flash(attn_setup, causal, window, chunk):
    q, k, v, pats = attn_setup
    ref = flash.flash_attention(q, k, v, causal, window, chunk, 128, 128)
    got = phi_flash_attention_xla(q, k, v, pats, causal=causal,
                                  window=window, chunk=chunk,
                                  block_q=128, block_kv=128)
    assert bool(jnp.all(got == ref))


@pytest.mark.parametrize("causal,window,chunk", MASKS)
def test_pallas_lowering_matches_flash(attn_setup, causal, window, chunk):
    q, k, v, pats = attn_setup
    ref = flash.flash_attention(q, k, v, causal, window, chunk, 128, 128)
    got, nnz = phi_flash_attention_pallas(
        q, k, v, pats, causal=causal, window=window, chunk=chunk,
        block_q=128, block_kv=128, interpret=True)
    # scores are bitwise-exact; the kernel's own softmax accumulator sits
    # within XLA fusion rounding of the scan-based one
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    assert nnz.shape == (q.shape[0] * q.shape[2], 1) and int(nnz.sum()) >= 0


def test_non_divisible_length_both_lowerings():
    B, S, H, D = 1, 60, 2, 32                            # S % 32 != 0
    q, k = _spikes((B, S, H, D), 7), _spikes((B, S, H, D), 8)
    v = jnp.asarray(np.random.default_rng(9).standard_normal((B, S, H, D)),
                    jnp.float32)
    pats = calibrate((np.random.default_rng(10).random((128, D)) < 0.1
                      ).astype(np.float32), PhiConfig(k=16, q=32))
    ref = flash.flash_attention(q, k, v, True, None, None, 32, 32)
    got = phi_flash_attention_xla(q, k, v, pats, causal=True,
                                  block_q=32, block_kv=32)
    assert bool(jnp.all(got == ref))
    got_p, _ = phi_flash_attention_pallas(q, k, v, pats, causal=True,
                                          block_q=32, block_kv=32,
                                          interpret=True)
    np.testing.assert_allclose(np.asarray(got_p), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


# ----------------------------------------------------------------- ops entry ---
def test_ops_entry_validates_bank_shape(attn_setup):
    q, k, v, _ = attn_setup
    bad = jnp.zeros((4, 16, 16), jnp.float32)            # T·kp = 64 > D = 32
    with pytest.raises(ValueError, match="pattern bank"):
        ops.phi_flash_attention(q, k, v, bad)


def test_attn_autotune_blocks_deterministic():
    b1 = ops.autotune_attn_blocks(256, 64, 2, 128, 16)
    b2 = ops.autotune_attn_blocks(256, 64, 2, 128, 16)
    assert b1 == b2 and all(isinstance(x, int) for x in b1)


# ------------------------------------------------------------- dispatch gates ---
def test_dispatch_spike_gate(attn_setup):
    q, k, v, pats = attn_setup
    pol = dispatch.get_policy()
    t, qp, kp = pats.shape
    d_spike = pol.resolve_attention(site="t.spike", s=72, d=32, t=t, q=qp,
                                    kp=kp, spike_qk=True, has_patterns=True)
    assert d_spike.impl == "phi_flash" and d_spike.blocks is not None
    d_dense = pol.resolve_attention(site="t.dense", s=72, d=32, t=t, q=qp,
                                    kp=kp, spike_qk=False, has_patterns=True)
    assert (d_dense.impl, d_dense.reason) == ("flash", "dense_qk_keeps_flash")
    d_nopat = pol.resolve_attention(site="t.nopat", s=72, d=32,
                                    spike_qk=True, has_patterns=False)
    assert (d_nopat.impl, d_nopat.reason) == ("flash",
                                              "no_patterns_keeps_flash")


def test_dispatch_autodiff_demotes(attn_setup):
    q, k, v, pats = attn_setup
    pol = dispatch.get_policy()

    def f(qq):
        return pol.attention(qq, k, v, pats, site="t.grad",
                             spike_qk=True).sum()

    g = jax.grad(f)(q)
    assert g.shape == q.shape
    assert ("t.grad", "flash", "autodiff_keeps_flash") in pol.decisions()


def test_dispatch_policy_bitwise_and_shared_blocks(attn_setup):
    # The acceptance anchor: policy-resolved phi_flash and a forced "flash"
    # override run the *same* decision blocks, so they are bit-identical.
    q, k, v, pats = attn_setup
    pol = dispatch.get_policy()
    out_phi = pol.attention(q, k, v, pats, site="t.ab", spike_qk=True)
    out_dense = pol.attention(q, k, v, pats, site="t.ab", spike_qk=True,
                              override="flash")
    assert bool(jnp.all(out_phi == out_dense))
    assert ("t.ab", "flash", "call_override") in pol.decisions()


def test_dispatch_unknown_override_raises(attn_setup):
    q, k, v, pats = attn_setup
    with pytest.raises(ValueError, match="attention impl"):
        dispatch.get_policy().attention(q, k, v, pats, site="t.bad",
                                        spike_qk=True, override="fused")


# ------------------------------------------------- spikformer end-to-end ---
def test_spikformer_phi_flash_bit_identical_dyadic():
    from repro.snn import models

    cfg = models.SNNConfig(kind="spikformer", num_classes=4, timesteps=2,
                           input_size=8, input_channels=3, dim=32, heads=2,
                           blocks=1, attn="flash", phi=PhiConfig(k=16, q=64))
    params = models.init(cfg, jax.random.PRNGKey(0))
    # dyadic 2^-10 weights: every product/sum below the f32 mantissa stays
    # exact, the regime of the paper's losslessness claim
    params = jax.tree_util.tree_map(lambda w: jnp.round(w * 1024) / 1024,
                                    params)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    phi, acts = models.calibrate_model(params, cfg, x)
    assert "b0_attn" in phi.patterns and "b0_attn" not in phi.pwp
    out_phi = models.phi_apply(params, cfg, phi, x)
    out_dense = models.phi_apply(params, cfg, phi, x, attn_impl="flash")
    assert bool(jnp.all(out_phi == out_dense))
    dec = dispatch.get_policy().decisions()
    assert any(s == "snn.b0_attn" and i == "phi_flash" for (s, i, _) in dec)
    # and the phi run matches the plain forward bit-for-bit
    ref = models.apply(params, cfg, x)
    assert bool(jnp.all(out_phi == ref))


def test_spikformer_ssa_default_untouched():
    from repro.snn import models

    cfg = models.SNNConfig(kind="spikformer", num_classes=4, timesteps=2,
                           input_size=8, input_channels=3, dim=32, heads=2,
                           blocks=1, phi=PhiConfig(k=16, q=64))
    assert cfg.attn == "ssa"
    params = models.init(cfg, jax.random.PRNGKey(0))
    # dyadic 2^-10 weights, as in the phi_flash test above: bitwise equality
    # of the Phi decomposition and the dense GEMM holds where every partial
    # sum is exact (other weights differ in the last ulp by association)
    params = jax.tree_util.tree_map(lambda w: jnp.round(w * 1024) / 1024,
                                    params)
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    phi, _ = models.calibrate_model(params, cfg, x)
    assert not any(n.endswith("_attn") for n in phi.patterns)
    assert bool(jnp.all(models.phi_apply(params, cfg, phi, x)
                        == models.apply(params, cfg, x)))


def test_spikformer_ssa_default_untouched_f32_weights():
    """The same run on ordinary f32 weights: the decomposition sums its
    partial products in another order than the dense GEMM, so the outputs
    agree to a few ulp rather than bitwise (for every impl, ``ref`` too)."""
    from repro.snn import models

    cfg = models.SNNConfig(kind="spikformer", num_classes=4, timesteps=2,
                           input_size=8, input_channels=3, dim=32, heads=2,
                           blocks=1, phi=PhiConfig(k=16, q=64))
    params = models.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    phi, _ = models.calibrate_model(params, cfg, x)
    assert not any(n.endswith("_attn") for n in phi.patterns)
    np.testing.assert_allclose(np.asarray(models.phi_apply(params, cfg, phi, x)),
                               np.asarray(models.apply(params, cfg, x)),
                               rtol=1e-6, atol=1e-6)


def test_capture_phi_traces_skips_attention_sites():
    from repro.snn import models

    cfg = models.SNNConfig(kind="spikformer", num_classes=4, timesteps=2,
                           input_size=8, input_channels=3, dim=32, heads=2,
                           blocks=1, attn="flash", phi=PhiConfig(k=16, q=64))
    params = models.init(cfg, jax.random.PRNGKey(0))
    x = jax.random.uniform(jax.random.PRNGKey(1), (2, 8, 8, 3))
    phi, _ = models.calibrate_model(params, cfg, x)
    traces = models.capture_phi_traces(params, cfg, phi, x)
    assert traces and not any(t.name.endswith("_attn") for t in traces)


# ------------------------------------------------------------ traffic model ---
# Table-4 spike suites: input density -> L2⁺+L2⁻ residual density
TABLE4_L2 = {0.05: 0.026, 0.10: 0.034, 0.20: 0.068}


@pytest.mark.parametrize("l2", sorted(TABLE4_L2.values()))
def test_traffic_model_meets_criterion(l2):
    r = phi_attention_traffic(256, 64, heads=2, k=16, q=128, l2_density=l2)
    assert r["phi_flash"] <= 0.6 * r["dense_flash"]
    assert r["phi_attn_ratio"] == pytest.approx(
        r["dense_flash"] / r["phi_flash"])


def test_traffic_model_monotone_in_density():
    rs = [phi_attention_traffic(512, 64, l2_density=d)["phi_flash"]
          for d in (0.01, 0.05, 0.2, 0.8)]
    assert rs == sorted(rs)
