"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.assign import assign_patterns, pack_l2_coo_jit
from repro.core.patterns import PhiConfig, calibrate, pattern_weight_products
from repro.kernels import ops, ref


def structured_binary(rng, m, k_total, protos=6, density=0.25, flip=0.05):
    base = (rng.random((protos, k_total)) < density).astype(np.float32)
    a = base[rng.integers(0, protos, m)]
    return np.abs(a - (rng.random((m, k_total)) < flip)).astype(np.float32)


@pytest.mark.parametrize("m", [64, 256, 300, 1024])
@pytest.mark.parametrize("kq", [(16, 32), (16, 128), (8, 16), (32, 64)])
def test_matcher_matches_oracle(m, kq):
    k, q = kq
    rng = np.random.default_rng(m * k + q)
    K = 4 * k
    a = structured_binary(rng, m, K)
    pats = calibrate(a, PhiConfig(k=k, q=q, iters=8))
    idx1, res1 = ops.matcher(jnp.asarray(a), jnp.asarray(pats))
    idx2, res2 = assign_patterns(jnp.asarray(a), jnp.asarray(pats))
    # Ties in argmin may differ only when two patterns are identical rows —
    # calibrate() dedupes, so indices must agree exactly.
    np.testing.assert_array_equal(np.asarray(idx1), np.asarray(idx2))
    np.testing.assert_array_equal(np.asarray(res1), np.asarray(res2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mode", ["mxu", "take"])
@pytest.mark.parametrize("mn", [(256, 128), (512, 256), (300, 384)])
def test_l1_gather_modes(dtype, mode, mn):
    m, n = mn
    rng = np.random.default_rng(n)
    T, q = 5, 33
    idx = jnp.asarray(rng.integers(0, q + 1, (m, T)), jnp.int32)
    pwp = jnp.asarray(rng.standard_normal((T, q + 1, n)), dtype)
    pwp = pwp.at[:, q].set(0.0)
    out = ops.l1_gather(idx, pwp, mode=mode, block_n=128)
    want = ref.l1_gather_ref(idx, pwp.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-2, atol=1e-2)


@pytest.mark.parametrize("mode", ["take", "mxu"])
@pytest.mark.parametrize("mk", [(40, 64), (256, 160), (513, 48)])
def test_l2_spmm_modes(mode, mk):
    m, K = mk
    rng = np.random.default_rng(m + K)
    r = (rng.integers(0, 3, (m, K)) - 1).astype(np.int8)
    r[rng.random((m, K)) < 0.9] = 0
    rows, cols, signs, over = pack_l2_coo_jit(jnp.asarray(r), int(m * K * 0.2))
    assert int(over) == 0
    w = jnp.asarray(rng.standard_normal((K, 128)), jnp.float32)
    out = ops.l2_spmm(rows, cols, signs, w, m, mode=mode, block_n=128)
    want = ref.l2_dense_ref(jnp.asarray(r), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_bucket_coo_overflow_reported():
    rows = jnp.asarray(np.sort(np.zeros(16, np.int32)))  # 16 entries in block 0
    cols = jnp.zeros(16, jnp.int32)
    signs = jnp.ones(16, jnp.int8)
    _, _, _, dropped = ops.bucket_coo(rows, cols, signs, 8, 8, cap=4)
    assert int(dropped) == 12


def test_bucket_coo_sentinels_not_counted_dropped():
    """Sentinel padding (row == true M, sign == 0) must not consume bucket
    capacity or be counted dropped when the caller's m = G·bm exceeds the
    true M (M not a multiple of the effective block): the sentinels then
    land *inside* the last block's searchsorted span."""
    r = np.zeros((10, 8), np.int8)              # 3 real entries, M=10
    r[0, 0] = 1
    r[5, 3] = -1
    r[9, 1] = 1
    rows, cols, signs, over = pack_l2_coo_jit(jnp.asarray(r), 32)
    assert int(over) == 0                       # 29 sentinel slots
    # G=2 blocks of bm=8 -> G*bm=16 > M=10: sentinels sit in block 1's span.
    br, bc, bs, dropped = ops.bucket_coo(rows, cols, signs, 16, 8, cap=4)
    assert int(dropped) == 0                    # was 26 before the fix
    # ... and the bucketed product is still exact
    w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)),
                    jnp.float32)
    out = ops.l2_spmm(rows, cols, signs, w, 10, block_m=8, cap=4)
    want = ref.l2_dense_ref(jnp.asarray(r), w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_phi_l2_audit_zero_counters_non_block_multiple_m():
    """Acceptance (sentinel false-drop repro): on a non-block-multiple-M
    input whose budgeted paths drop nothing, every audit counter is zero.
    Before the fix the COO sentinels landed inside the last block's span
    and phi_l2_audit reported a capacity overflow that never happened."""
    rng = np.random.default_rng(0)
    a = structured_binary(rng, 300, 64)         # M=300: 300 % 8 != 0
    pats = calibrate(a, PhiConfig(k=16, q=16, iters=6))
    aud = ops.phi_l2_audit(jnp.asarray(a), jnp.asarray(pats),
                           nnz_budget=0.08, block_m=8)
    # the budgeted paths have ample headroom for this input ...
    assert 0 < aud["l2_nnz"] < aud["cap"]
    # ... so nothing may be reported dropped anywhere
    assert aud["pack_overflow"] == 0
    assert aud["bucket_dropped"] == 0
    assert aud["chunk_overflow"] == 0


def test_phi_l2_audit_matches_real_path_cap_for_small_m():
    """The audit and the real ``impl="pallas"`` path must derive the
    per-block cap from the same (requested) block_m: for M < 256 the
    effective block is smaller, and deriving from it under-reports the
    capacity the real path actually enforces (false bucket_dropped)."""
    rng = np.random.default_rng(3)
    a = (rng.random((20, 32)) < 0.3).astype(np.float32)
    pats = calibrate(a, PhiConfig(k=16, q=8, iters=4))
    aud = ops.phi_l2_audit(jnp.asarray(a), jnp.asarray(pats), nnz_budget=0.01)
    cap = aud["cap"]                            # the max(128, ...) floor
    # effective-bm derivation would cap below the observed nnz ...
    bm_eff = ops.effective_block_m(20, 256)
    assert ops.l2_per_block_cap(0.01, bm_eff, 32, cap) < aud["l2_nnz"] <= cap
    # ... but the real path's requested-bm cap covers it: no false drops.
    assert aud["bucket_dropped"] == 0
    # and the real budgeted path is indeed exact at this budget
    w = rng.standard_normal((32, 64)).astype(np.float32)
    from repro.core.patterns import pattern_weight_products
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    out = ops.phi_matmul(jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats),
                         pwp, impl="pallas", nnz_budget=0.01)
    np.testing.assert_allclose(np.asarray(out), a @ w, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("nnz_budget", [0.04, 0.3])
def test_coo_decode_m_exact_above_budget(nnz_budget):
    """A decode-sized call (M=8 < one 2048-row chunk) runs as one chunk of
    its own rows, but keeps the L2 capacity of a full chunk clipped to its
    M·K cells: residual density far above ``nnz_budget`` stays exact."""
    rng = np.random.default_rng(5)
    K, n = 2048, 128
    pats = calibrate(structured_binary(rng, 256, K), PhiConfig(k=16, q=16, iters=4))
    a = (rng.random((8, K)) < 0.95).astype(np.float32)
    w = rng.standard_normal((K, n)).astype(np.float32)
    aud = ops.phi_l2_audit(jnp.asarray(a), jnp.asarray(pats), nnz_budget=nnz_budget)
    assert aud["l2_nnz"] > 0.5 * a.size          # residual well above budget
    assert aud["chunk_overflow"] == 0
    assert ops.coo_chunk_layout(8, K, nnz_budget) == (8, 8 * K)
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    args = (jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats), pwp)
    out = ops.phi_matmul(*args, impl="coo", nnz_budget=nnz_budget)
    want = ops.phi_matmul(*args, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out), a @ w, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("reset", ["hard", "soft"])
@pytest.mark.parametrize("shape", [(32, 128), (3, 50, 70), (1000,)])
def test_lif_kernel(reset, shape):
    rng = np.random.default_rng(7)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    s1, v1 = ops.lif_step(v, x, decay=0.6, threshold=0.8, reset=reset)
    s2, v2 = ref.lif_ref(v, x, 0.6, 0.8, reset)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2))
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "coo", "pallas", "fused",
                                  "fused_stream"])
@pytest.mark.parametrize("shape", [(128, 64, 96), (200, 32, 128), (64, 128, 256)])
def test_phi_matmul_exact(impl, shape):
    """Phi without PAFT is lossless (paper Sec. 5.4.2): decomposition == dense."""
    m, K, n = shape
    rng = np.random.default_rng(m + K + n)
    a = structured_binary(rng, m, K)
    w = rng.standard_normal((K, n)).astype(np.float32)
    pats = calibrate(a, PhiConfig(k=16, q=32, iters=8))
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    out = ops.phi_matmul(jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats), pwp, impl=impl)
    np.testing.assert_allclose(np.asarray(out), a @ w, rtol=1e-4, atol=1e-3)


def test_phi_matmul_batched_leading_dims():
    rng = np.random.default_rng(11)
    a = structured_binary(rng, 60, 32).reshape(2, 30, 32)
    w = rng.standard_normal((32, 64)).astype(np.float32)
    pats = calibrate(a, PhiConfig(k=16, q=16, iters=6))
    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
    out = ops.phi_matmul(jnp.asarray(a), jnp.asarray(w), jnp.asarray(pats), pwp, impl="coo")
    assert out.shape == (2, 30, 64)
    np.testing.assert_allclose(np.asarray(out), a @ w, rtol=1e-4, atol=1e-3)
