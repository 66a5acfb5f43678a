"""The fused Phi lowerings compile for a TPU v5e at olmo_1b's GEMM widths,
and so do the Phi flash-attention kernel at olmo_1b's head layout and the
paged decode attention kernel at olmo_1b's serving shapes.

The TPU compiler is installed wherever JAX's TPU support is, and it compiles
for a chip that is described rather than attached, so these tests run on a
CPU-only host: nothing executes, the compiler refuses what the chip would
(tiling, VMEM). Shapes: (K, N) of the olmo_1b projections with the serving
Phi config (q=16, k=16, T=K/16), at a prefill M (128-token bucket × 2
timesteps) and a decode M (4 slots × 2 timesteps), with the blocks the
execution policy would pick.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops, paged_attention, phi_attention, phi_fused

Q, K_PART = 16, 16
P_ACTIVE = 8                     # prefetch gather size from a skewed bank
SHAPES = [(2048, 2048), (2048, 8192), (8192, 2048)]      # (K, N)
M_PREFILL, M_DECODE = 2 * 128, 2 * 4


@pytest.fixture(scope="module")
def topo():
    prev = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means: no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        if prev is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _lower(impl: str, M: int, K: int, N: int, chip):
    T = K // K_PART

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    pats, pwp = sds((T, Q, K_PART)), sds((T, Q + 1, N))
    scale, w = sds((T, Q + 1)), sds((K, N))
    if impl == "fused":
        bm, bn = ops.autotune_fused_blocks(M, K, N, Q, T)
        bm = ops.effective_block_m(M, bm)
        fn = jax.jit(lambda *x: phi_fused.phi_fused_pallas(
            *x, block_m=bm, block_n=bn))
        return fn.lower(sds((M, K)), pats, pwp, scale, w)
    if impl == "fused_stream":
        bm, bn, gt = ops.autotune_stream_blocks(M, K, N, Q, T)
        bm = ops.effective_block_m(M, bm)
        fn = jax.jit(lambda *x: phi_fused.phi_fused_stream_pallas(
            *x, block_m=bm, block_n=bn, group_t=gt))
        return fn.lower(sds((M, K)), pats, pwp, scale, w)
    bm, bn = ops.autotune_prefetch_blocks(M, K, N, Q, T, P_ACTIVE)
    bm = ops.effective_block_m(M, bm)
    fn = jax.jit(lambda *x: phi_fused.phi_fused_prefetch_pallas(
        *x, block_m=bm, block_n=bn))
    return fn.lower(sds((M, K)), pats, pwp, scale, w,
                    sds((M // bm, T, P_ACTIVE), jnp.int32))


@pytest.mark.parametrize("M", [M_PREFILL, M_DECODE], ids=["prefill", "decode"])
@pytest.mark.parametrize("K,N", SHAPES, ids=[f"K{k}_N{n}" for k, n in SHAPES])
@pytest.mark.parametrize("impl", ["fused", "fused_stream", "fused_prefetch"])
def test_phi_lowering_compiles_for_v5e(impl, K, N, M, one_chip,
                                       no_compile_cache):
    compiled = _lower(impl, M, K, N, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_phi_flash_attention_compiles_for_v5e(one_chip, no_compile_cache):
    """olmo_1b's 16 heads of 128 at a 256-token prefill, with a bank of
    T=8 partitions of 16 over the head dim (q=16)."""
    B, S, H, D, T, qp, kp = 1, 256, 16, 128, 8, 16, 16
    bq, bkv = ops.autotune_attn_blocks(S, D, T, qp, kp)
    qkv = jax.ShapeDtypeStruct((B, S, H, D), jnp.float32, sharding=one_chip)
    pats = jax.ShapeDtypeStruct((T, qp, kp), jnp.float32, sharding=one_chip)
    fn = jax.jit(lambda q, k, v, p: phi_attention.phi_flash_attention_pallas(
        q, k, v, p, causal=True, block_q=bq, block_kv=bkv))
    compiled = fn.lower(qkv, qkv, qkv, pats).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_decode_attention_compiles_for_v5e(one_chip, no_compile_cache):
    """olmo_1b served on one chip: 16 slots, 128 logical pages of 16, 16 KV
    heads of 128, 1,024 pool pages plus the scratch page, bf16. The pools
    reach the kernel in place: no copy of them is made around the call."""
    B, H, D, PS, LP, N = 16, 16, 128, 16, 128, 1025

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((N, PS, H, D), jnp.bfloat16)
    fn = jax.jit(paged_attention.paged_decode_attention)
    compiled = fn.lower(sds((B, H, D), jnp.bfloat16), pool, pool,
                        sds((B, LP), jnp.int32), sds((B,), jnp.int32)
                        ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < N * PS * H * D
