"""The paged decode attention kernel (``kernels/paged_attention.py``) against
the ``gather`` lowering of ``attn_block_decode_paged``.

The kernel runs here in Pallas TPU interpret mode with its DMAs executed at
their waits and fresh VMEM filled with NaN, so a page the kernel reads
without copying it, or a stale buffer row that reaches the output, shows as
a NaN. Tolerance: the kernel rounds the unnormalised probabilities to bf16
before p·v (as a default-precision f32 einsum does on a TPU) and the
``gather`` lowering on the CPU does not; both round the output to bf16. On
unit-normal bf16 inputs that is at most ~2 bf16 ulps of outputs below 4:
``atol = rtol = 1e-2`` in f32.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.configs import get_config
from repro.distributed.sharding import init_params
from repro.kernels import dispatch
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import layers as ll
from repro.models import model, transformer

PS, LP, HD = 16, 8, 128              # 8 logical pages of 16: context 128
N_PAGES = 40                          # physical pages; page N_PAGES = scratch
INTERPRET = pltpu.InterpretParams(dma_execution_mode="on_wait",
                                  uninitialized_memory="nan")
TOL = dict(atol=1e-2, rtol=1e-2)


def _gather(q, k_pool, v_pool, table, lengths):
    """The ``gather`` lowering's read: every logical page viewed, −1 → page
    0, positions ≥ length masked by ``ll.attention_decode``."""
    view_table = jnp.maximum(table, 0)

    def view(pool):
        g = pool[view_table]
        return g.reshape(g.shape[0], -1, g.shape[3], g.shape[4])

    return ll.attention_decode(q[:, None], view(k_pool), view(v_pool),
                               lengths - 1, mode="full")[:, 0]


def _pools(rng, hkv):
    shape = (N_PAGES + 1, PS, hkv, HD)
    k = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
    return k, v


def _tables(rng, lengths):
    """Non-contiguous tables: each slot's pages drawn from a shuffled pool."""
    table = np.full((len(lengths), LP), -1, np.int32)
    free = list(rng.permutation(N_PAGES))
    for b, n in enumerate(lengths):
        for lpage in range(-(-n // PS)):
            table[b, lpage] = free.pop()
    return table


def _run(q, k, v, table, lengths, ppb):
    return paged_decode_attention(q, k, v, jnp.asarray(table),
                                  jnp.asarray(lengths, jnp.int32),
                                  pages_per_block=ppb, interpret=INTERPRET)


@pytest.mark.parametrize("ppb", [1, 2, 8], ids=lambda p: f"ppb{p}")
def test_kernel_matches_gather_at_page_edges(ppb):
    """Lengths 1, ps−1, ps, ps+1, a partial last block and the whole
    context, on shuffled pages, with 1, 2 and all pages per block."""
    rng = np.random.default_rng(ppb)
    lengths = [1, PS - 1, PS, PS + 1, 3 * PS + 5, LP * PS]
    k, v = _pools(rng, 2)
    q = jnp.asarray(rng.standard_normal((len(lengths), 2, HD)), jnp.bfloat16)
    table = _tables(rng, lengths)
    out = _run(q, k, v, table, lengths, ppb)
    ref = _gather(q, k, v, jnp.asarray(table), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL)


@pytest.mark.parametrize("hq,hkv", [(4, 2), (8, 2)], ids=["gqa2", "gqa4"])
def test_kernel_matches_gather_with_grouped_kv_heads(hq, hkv):
    """Query heads share KV heads in groups of hq/hkv."""
    rng = np.random.default_rng(hq)
    lengths = [PS + 3, 2 * PS, 77]
    k, v = _pools(rng, hkv)
    q = jnp.asarray(rng.standard_normal((len(lengths), hq, HD)),
                    jnp.bfloat16)
    table = _tables(rng, lengths)
    out = _run(q, k, v, table, lengths, 2)
    ref = _gather(q, k, v, jnp.asarray(table), jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL)


def test_inactive_lanes_read_only_the_scratch_page():
    """Lanes with an all −1 table row and length 1 (inactive slots) read
    position 0 of the shared scratch page, whatever it holds, and leave the
    active lanes between them exact."""
    rng = np.random.default_rng(7)
    lengths = [1, 40, 1, 1, 90]
    k, v = _pools(rng, 2)
    junk = jnp.full((PS, 2, HD), 3.0e4, jnp.bfloat16)
    k, v = k.at[N_PAGES].set(junk), v.at[N_PAGES].set(junk * -1)
    q = jnp.asarray(rng.standard_normal((len(lengths), 2, HD)), jnp.bfloat16)
    table = _tables(rng, lengths)
    inactive = [0, 2, 3]
    table[inactive] = -1
    out = np.asarray(_run(q, k, v, table, lengths, 2), np.float32)
    ref = _gather(q, k, v, jnp.asarray(table), jnp.asarray(lengths))
    active = [1, 4]
    np.testing.assert_allclose(out[active],
                               np.asarray(ref, np.float32)[active], **TOL)
    # One position attended: the output is that position's V row.
    for b in inactive:
        np.testing.assert_array_equal(out[b],
                                      np.asarray(v[N_PAGES, 0], np.float32))


def test_decode_block_reads_the_same_through_both_lowerings(monkeypatch):
    """``attn_block_decode_paged`` at olmo_1b's head layout (16 heads of
    128, bf16) through the ``gather`` lowering and through the kernel (the
    policy told it runs on a TPU; the kernel then runs interpreted): the
    same new K/V rows land in the pools, bitwise, and the active lanes'
    outputs agree within the tolerance above. Lane 3 is inactive (table
    row all −1) and its output is not compared: the engine discards it."""
    cfg = get_config("olmo_1b").with_(n_layers=1, d_ff=256, vocab=128)
    params = init_params(model.lm_specs(cfg), jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["decoder"]["stack"])["p0"]
    rng = np.random.default_rng(0)
    k, v = _pools(rng, cfg.n_kv_heads)
    x = jnp.asarray(rng.standard_normal((4, 1, cfg.d_model)), jnp.bfloat16)
    pos = jnp.asarray([37, 0, PS - 1, 100], jnp.int32)
    table = _tables(rng, [38, 1, PS, 0])

    def block():
        return jax.jit(lambda *a: transformer.attn_block_decode_paged(
            cfg, p, *a))(x, pos, (k, v), jnp.asarray(table))

    x_gather, pools_gather = block()
    monkeypatch.setattr(dispatch, "_backend", lambda: "tpu")
    x_kernel, pools_kernel = block()
    assert dispatch.get_policy().last_decision(
        "lm.attn_decode_paged").impl == "paged_kernel"
    for a, b in zip(pools_gather, pools_kernel):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(x_kernel, np.float32)[:3],
                               np.asarray(x_gather, np.float32)[:3], **TOL)
