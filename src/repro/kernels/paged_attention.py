"""Pallas TPU kernel: one-token decode attention over a paged KV pool.

The paged engine keeps every layer's keys and values in a shared pool of
fixed-size pages, ``(P+1, page_size, kv_heads, head_dim)``, and gives each
slot a row of the page table (``serve/page_manager.py``). The XLA lowering
(``gather``) rebuilds every slot's whole ``max_context`` view from the table
and masks what lies past ``pos``, so its work scales with slots ×
max_context whatever the slots hold. This kernel reads only the pages a
slot holds, straight from the pool in HBM:

  * grid: one program per slot, run in order ("arbitrary"). A program walks
    its slot's pages in blocks of ``pages_per_block`` with an in-kernel
    loop whose trip count is the slot's length, so blocks past the length
    cost neither a DMA nor a grid step;
  * each block's pages are DMA'd page by page (one page of all heads is
    one contiguous copy) into a double buffer; the next block — or the
    next slot's first block — is in flight while the current one is
    computed;
  * the page table (flattened) and the lengths ride in scalar prefetch
    (SMEM); a table entry < 0 reads the pool's last page, the scratch page;
  * online softmax per head, with scores, the running max and sum and the
    accumulator in f32.

Heads. A block of ``T = pages_per_block · page_size`` positions in the
buffer, ``(T, kv_heads, head_dim)``, is read as its
``(T·kv_heads, head_dim)`` rows, its own VMEM layout when ``kv_heads`` is a
multiple of the bf16 tile's 16 rows. One MXU product of those rows with
every query head (bf16 in, f32 out) gives a ``(T, kv_heads, q_heads)``
score block, of which each query head keeps its own KV head's entries —
the diagonal for plain multi-head attention; the rest is masked, the price
of a layout that needs no relayout. For ``p·v`` the probabilities are
rounded to bf16 (as a default-precision f32 einsum rounds them on a TPU),
spread over the head dim by an exact MXU product with a 0/1 matrix, and
multiplied with V and summed over positions on the VPU in f32. A GQA group
of ``rep`` query heads per KV head takes ``rep`` such passes.

Positions of a block past the slot's length are masked to ``-inf`` in the
scores, and the V rows there are zeroed before the product: pages not
copied this step leave stale VMEM in the buffer, and ``0 · NaN`` would
poison the sum.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hwconst import VMEM_LIMIT_BYTES

# Positions a block covers: 8 pages of 16, 0.5 MB of K and of V at
# olmo_1b's 16 KV heads of 128 in bf16. On a TPU v5e blocks of 4 to 32 such
# pages ran within 12% of each other; larger blocks only cost VMEM.
_BLOCK_TOKENS = 128
_BF16_ROWS = 16


def unsupported(page_size: int, kv_heads: int, head_dim: int,
                dtype) -> str | None:
    """Why the kernel does not take this pool, or None when it does.

    The kernel is built for bf16 pools whose page is whole (16, 128) tiles
    and whose (positions · kv_heads, head_dim) view of a block is the
    buffer's own layout: ``page_size`` and ``kv_heads`` multiples of the
    16-row bf16 tile, ``head_dim`` a multiple of the 128 lanes.
    """
    if np.dtype(dtype) != np.dtype(jnp.bfloat16):
        return "pool_not_bf16"
    if page_size % _BF16_ROWS or kv_heads % _BF16_ROWS:
        return "page_not_bf16_tiles"
    if head_dim % 128:
        return "head_dim_not_lanes"
    return None


def block_pages(page_size: int, logical_pages: int) -> int:
    """Pages one loop iteration copies and computes."""
    return max(1, min(logical_pages, _BLOCK_TOKENS // page_size))


def _kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sem, step_ref, m_ref, l_ref, acc_ref, *,
            ppb: int, ps: int, lp: int, rep: int, scale: float):
    b = pl.program_id(0)
    n_slots = pl.num_programs(0)
    T = ppb * ps

    def n_pages(s, j):
        return jnp.minimum(ppb, pl.cdiv(len_ref[s], ps) - j * ppb)

    def page_copies(s, j, buf, i):
        page = tbl_ref[s * lp + j * ppb + i]
        return (pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, i],
                                      sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, i],
                                      sem.at[1, buf]))

    def start(s, j, buf):
        def one(i, c):
            for cp in page_copies(s, j, buf, i):
                cp.start()
            return c
        jax.lax.fori_loop(0, n_pages(s, j), one, 0)

    def wait(s, j, buf):
        def one(i, c):
            for cp in page_copies(s, j, buf, i):
                cp.wait()
            return c
        jax.lax.fori_loop(0, n_pages(s, j), one, 0)

    @pl.when(b == 0)
    def _():
        step_ref[0] = 0
        start(0, 0, 0)

    _, hkv, hd = acc_ref.shape
    hq = hkv * rep
    qv = q_ref[0].astype(kbuf.dtype)                          # (hq, hd)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    length = len_ref[b]
    n_blocks = pl.cdiv(length, T)
    # [t, g, h]: query head h reads KV head g.
    own = (jax.lax.broadcasted_iota(jnp.int32, (T, hkv, hq), 2) // rep
           == jax.lax.broadcasted_iota(jnp.int32, (T, hkv, hq), 1))
    t_in_block = jax.lax.broadcasted_iota(jnp.int32, (T, hkv, hq), 0)
    v_row = jax.lax.broadcasted_iota(jnp.int32, (T, hkv, hd), 0)
    # [g, h]: query head h is member r of KV head g's group.
    member = [jax.lax.broadcasted_iota(jnp.int32, (hkv, hq), 1)
              == jax.lax.broadcasted_iota(jnp.int32, (hkv, hq), 0) * rep + r
              for r in range(rep)]
    # [h, d]: broadcasts member r's probability over the head dim.
    spread = [(jax.lax.broadcasted_iota(jnp.int32, (hq, hd), 0) % rep == r
               ).astype(kbuf.dtype) for r in range(rep)]

    def rows(x, r):
        """(1, hq) per-head values -> (hkv, 1), row g = member r of g."""
        return jnp.sum(jnp.where(member[r], x, 0.0), axis=1, keepdims=True)

    def block(j, c):
        buf = step_ref[0] % 2

        @pl.when(j + 1 < n_blocks)
        def _():
            start(b, j + 1, 1 - buf)

        @pl.when(jnp.logical_and(j + 1 == n_blocks, b + 1 < n_slots))
        def _():
            start(b + 1, 0, 1 - buf)

        wait(b, j, buf)
        live = j * T + t_in_block < length                    # (T, hkv, hq)
        k = kbuf[buf].reshape(T * hkv, hd)
        s = jax.lax.dot_general(k, qv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(live & own, s.reshape(T, hkv, hq) * scale, -jnp.inf)
        m_prev = m_ref[...]                                   # (1, hq)
        m_new = jnp.maximum(m_prev, jnp.max(jnp.max(s, axis=0), axis=0,
                                            keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[None])                          # 0 off own/live
        l_ref[...] = alpha * l_ref[...] + jnp.sum(jnp.sum(p, axis=0), axis=0,
                                                  keepdims=True)
        m_ref[...] = m_new
        p = p.reshape(T * hkv, hq).astype(kbuf.dtype)
        v = vbuf[buf].reshape(T, hkv, hd)
        v = jnp.where(j * T + v_row < length, v,
                      jnp.zeros_like(v)).astype(jnp.float32)
        for r in range(rep):
            pb = jnp.dot(p, spread[r], preferred_element_type=jnp.float32)
            pv = jnp.sum(pb.reshape(T, hkv, hd) * v, axis=0)  # (hkv, hd)
            acc_ref[r] = rows(alpha, r) * acc_ref[r] + pv
        step_ref[0] += 1
        return c

    jax.lax.fori_loop(0, n_blocks, block, 0)
    for r in range(rep):
        o_ref[0, r * hkv:(r + 1) * hkv] = (
            acc_ref[r] / rows(l_ref[...], r)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret"))
def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           page_table: jax.Array, lengths: jax.Array, *,
                           pages_per_block: int | None = None,
                           interpret: bool | pltpu.InterpretParams = False
                           ) -> jax.Array:
    """Attention of one query token per slot over the slot's paged context.

    q:          (B, q_heads, head_dim)
    k_pool,
    v_pool:     (P+1, page_size, kv_heads, head_dim); the last page is the
                scratch page an unmapped (−1) table entry reads
    page_table: (B, logical_pages) int32 physical page per logical page
    lengths:    (B,) int32 in [1, logical_pages·page_size]: positions
                0..length−1 of each slot attend

    Returns (B, q_heads, head_dim) in q's dtype. ``interpret`` runs the
    kernel body off the TPU (``pltpu.InterpretParams`` models the DMAs).
    """
    B, hq, hd = q.shape
    n_phys, ps, hkv, hd_k = k_pool.shape
    lp = page_table.shape[1]
    assert hd_k == hd and hq % hkv == 0 and v_pool.shape == k_pool.shape, (
        q.shape, k_pool.shape, v_pool.shape)
    ppb = pages_per_block or block_pages(ps, lp)
    table = jnp.where(page_table < 0, n_phys - 1, page_table).reshape(-1)
    rep = hq // hkv
    kernel = functools.partial(_kernel, ppb=ppb, ps=ps, lp=lp, rep=rep,
                               scale=hd ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, hq, hd), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, hq, hd), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, ps, hkv, hd), k_pool.dtype),
            pltpu.VMEM((2, ppb, ps, hkv, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((1, hq), jnp.float32),
            pltpu.VMEM((1, hq), jnp.float32),
            pltpu.VMEM((rep, hkv, hd), jnp.float32),
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # Each program prefetches the next slot's first block: in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), q, k_pool, v_pool)
    # Query head g·rep + r is row r·kv_heads + g of the kernel's output.
    return out.reshape(B, rep, hkv, hd).transpose(0, 2, 1, 3).reshape(q.shape)
