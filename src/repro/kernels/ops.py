"""Jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * shape padding to block multiples (kernels require exact tiling);
  * backend dispatch — Pallas TPU kernels run natively on TPU, in
    ``interpret=True`` mode on CPU (correctness validation), and the pure-XLA
    reference path (`ref.py`) is used inside pjit-lowered distributed graphs
    (XLA cannot auto-partition through a ``pallas_call``). Inside a
    shard_map *body* the operands are already per-shard local arrays, so
    the Pallas kernels run there unchanged — ``kernels.dispatch`` re-gates
    on the local shape (``spmd_local_*``) instead of demoting;
  * COO bucketing for the L2 spmm (the static analogue of the ASIC packer);
  * the composite ``phi_matmul`` = matcher → L1 gather → L2 spmm.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

# Single source of truth in core.hwconst (PHI-LINT-HWCONST): the policy's
# VMEM gate and the perf stories must read one copy of the budget.
from repro.core.hwconst import VMEM_BUDGET_BYTES as _VMEM_BUDGET_BYTES
from repro.core.patterns import PhiConfig, pattern_weight_products  # noqa: F401 (re-export)
from repro.kernels import ref
from repro.kernels.lif import lif_pallas
from repro.kernels.matcher import matcher_pallas
from repro.kernels.phi_fused import (
    group_size,
    phi_fused_pallas,
    phi_fused_prefetch_pallas,
    phi_fused_stream_pallas,
    stripe_active_sets,
)
from repro.kernels.phi_gather import l1_gather_pallas
from repro.kernels.phi_spmm import l2_spmm_pallas
from repro.utils import cdiv


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def effective_block_m(M: int, block_m: int) -> int:
    """Block-m actually used for an M-row problem: requested size clamped to
    the next power of two ≥ M (kernels pad M up to a whole block)."""
    return min(block_m, max(8, 1 << (M - 1).bit_length()))


def _pad_rows(x: jax.Array, mult: int, fill: int = 0) -> jax.Array:
    m = x.shape[0]
    pad = (-m) % mult
    if pad == 0:
        return x
    return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1), constant_values=fill)


def _pick_block_n(N: int, block_n: int) -> int:
    """Largest block size ≤ block_n that divides N (kernels require exact
    N tiling; e.g. N=384 with block_n=256 -> 192). Degenerate divisors are
    rejected loudly: a 1- or 2-wide lane tile is not a usable TPU layout."""
    b = min(block_n, N)
    while N % b:
        b -= 1
    if b < 8 and b != N:
        raise ValueError(
            f"no usable block_n ≤ {block_n} divides N={N} (best divisor {b}); "
            "pad N to a multiple of 128 before calling")
    return b


# ---------------------------------------------------------------- matcher ---
def matcher(a: jax.Array, patterns: jax.Array, *,
            block_m: int = 256) -> tuple[jax.Array, jax.Array]:
    """Pattern match: a (..., K) binary, patterns (T, q, k) -> (idx, residual)."""
    lead = a.shape[:-1]
    K = a.shape[-1]
    a2 = a.reshape(-1, K)
    M = a2.shape[0]
    bm = effective_block_m(M, block_m)
    a2 = _pad_rows(a2, bm)
    idx, res = matcher_pallas(a2, patterns, block_m=bm, interpret=_interpret())
    T = patterns.shape[0]
    return idx[:M].reshape(*lead, T), res[:M].reshape(*lead, K)


# -------------------------------------------------------------- L1 gather ---
def l1_gather(idx: jax.Array, pwp: jax.Array, *, block_m: int = 256, block_n: int = 256,
              mode: str = "mxu") -> jax.Array:
    """idx (..., T) -> (..., N) sum of PWP rows."""
    lead = idx.shape[:-1]
    T = idx.shape[-1]
    N = pwp.shape[-1]
    idx2 = idx.reshape(-1, T)
    M = idx2.shape[0]
    bm = effective_block_m(M, block_m)
    bn = _pick_block_n(N, block_n)
    # Padding rows index the all-zero slot q.
    idx2 = _pad_rows(idx2, bm, fill=pwp.shape[1] - 1)
    out = l1_gather_pallas(idx2, pwp, block_m=bm, block_n=bn, mode=mode,
                           interpret=_interpret())
    return out[:M].reshape(*lead, N)


# ---------------------------------------------------------------- L2 spmm ---
def bucket_coo(rows: jax.Array, cols: jax.Array, signs: jax.Array, m: int,
               block_m: int, cap: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Bucket row-sorted padded COO into per-M-block packs.

    rows must be ascending (sentinel == m last), as produced by
    ``pack_l2_coo_jit``. Returns (G, cap) local rows (sentinel block_m),
    (G, cap) cols, (G, cap) signs, and per-block overflow dropped count.

    Sentinel padding never consumes capacity and is never counted dropped:
    the packer emits sentinels with sign == 0 after all real (sign ±1)
    entries, so clamping the span boundaries to the real-entry count
    excludes them. Without the clamp, a caller whose ``m = G·block_m``
    exceeds the packer's true M (M not a multiple of the effective block)
    would find the sentinel rows *inside* the last block's searchsorted
    span — ``dropped`` then reports a capacity overflow that never
    happened, poisoning the ``phi_l2_audit`` contract.
    """
    G = cdiv(m, block_m)
    n_valid = (signs != 0).sum()
    starts = jnp.minimum(
        jnp.searchsorted(rows, jnp.arange(G + 1) * block_m, side="left"),
        n_valid)
    take = starts[:-1, None] + jnp.arange(cap)[None, :]            # (G, cap)
    valid = take < starts[1:, None]
    take_c = jnp.clip(take, 0, rows.shape[0] - 1)
    r = jnp.where(valid, rows[take_c] - jnp.arange(G)[:, None] * block_m, block_m)
    c = jnp.where(valid, cols[take_c], 0)
    s = jnp.where(valid, signs[take_c], 0)
    dropped = (starts[1:] - starts[:-1] - cap).clip(min=0).sum()
    return r.astype(jnp.int32), c.astype(jnp.int32), s, dropped


def l2_per_block_cap(nnz_budget: float, block_m: int, K: int, cap: int) -> int:
    """Per-M-block L2 bucket capacity: the global budget with 4× local-
    imbalance headroom, clamped to the global cap.

    Single source of truth for BOTH the real ``impl="pallas"`` lowering and
    ``phi_l2_audit`` — and derived from the *requested* block_m, exactly as
    the real path derives it (the bucketing itself may still use the
    clamped ``effective_block_m``). When the audit derived its cap from the
    effective block instead, any M < 256 problem audited against a smaller
    capacity than the real path actually enforces, and the audit could
    report ``bucket_dropped`` the real path doesn't have — violating its
    docstring contract.
    """
    return max(8, min(cap, int(4 * nnz_budget * block_m * K)))


def coo_chunk_layout(M: int, K: int, nnz_budget: float,
                     chunk_rows: int | None = None,
                     entry_block: int = 8192) -> tuple[int, int]:
    """Rows per chunk and L2 entry capacity per chunk of the "coo" path.

    ``chunk_rows`` defaults to ``$PHI_CHUNK_ROWS`` (2048). A call with fewer
    rows than a chunk (decode) is one chunk of M rows, so its temporaries are
    M-sized. Its capacity stays that of a full ``chunk_rows`` chunk, clipped
    to the M·K cells the call has: a short call is then never less exact than
    it would be padded to a full chunk. The cap is a whole number of
    ``entry_block`` slabs.
    """
    import os as _os
    if chunk_rows is None:
        chunk_rows = int(_os.environ.get("PHI_CHUNK_ROWS", "2048"))
    cap = max(128, int(nnz_budget * chunk_rows * K))
    rows = min(chunk_rows, M)
    cap = min(cap, rows * K)
    cap = ((cap + entry_block - 1) // entry_block) * entry_block
    return rows, cap


def phi_l2_audit(a: jax.Array, patterns: jax.Array, *, nnz_budget: float = 0.08,
                 block_m: int = 256, chunk_rows: int | None = None,
                 entry_block: int = 8192) -> dict:
    """Capacity-budget audit of a Phi decomposition (no matmul performed).

    Returns the dropped-entry counters of every budgeted path for activations
    ``a`` (..., K): ``pack_overflow`` (entries beyond the global COO cap of
    the pallas path), ``bucket_dropped`` (entries beyond the per-M-block cap
    of ``bucket_coo``), and ``chunk_overflow`` (entries beyond the per-chunk
    cap of the "coo" path). All zero ⇔ the budgeted impls are exact for this
    input; a numerics mismatch with nonzero counters is a capacity problem,
    not a kernel bug. The "fused"/"fused_stream"/"ref" impls are budget-free.
    """
    from repro.core.assign import assign_patterns, pack_l2_coo_jit

    a2 = a.reshape(-1, a.shape[-1])
    M, K = a2.shape
    _, residual = assign_patterns(a2, patterns)
    cap = max(128, int(nnz_budget * M * K))
    rows, cols, signs, pack_over = pack_l2_coo_jit(residual, cap)
    bm = effective_block_m(M, block_m)
    per_block = l2_per_block_cap(nnz_budget, block_m, K, cap)
    G = cdiv(M, bm)
    _, _, _, bucket_drop = bucket_coo(rows, cols, signs, G * bm, bm, per_block)
    # Same layout as _phi_matmul_coo_chunked, so the audit can never report
    # overflow the real path doesn't have.
    chunk_rows, chunk_cap = coo_chunk_layout(M, K, nnz_budget, chunk_rows,
                                             entry_block)
    nc = cdiv(M, chunk_rows)
    pad = nc * chunk_rows - M
    res3 = jnp.pad(residual, ((0, pad), (0, 0))).reshape(nc, chunk_rows, K)
    chunk_nnz = jnp.abs(res3).sum(axis=(1, 2))
    chunk_over = (chunk_nnz - chunk_cap).clip(min=0).sum()
    return {
        "l2_nnz": int(jnp.abs(residual).sum()),
        "cap": cap,
        "pack_overflow": int(pack_over),
        "bucket_dropped": int(bucket_drop),
        "chunk_cap": chunk_cap,
        "chunk_overflow": int(chunk_over),
    }


def l2_spmm(rows: jax.Array, cols: jax.Array, signs: jax.Array, w: jax.Array,
            m: int, *, block_m: int = 256, block_n: int = 256, cap: int | None = None,
            mode: str = "take") -> jax.Array:
    """Padded COO (sentinel row == m) × w (K, N) -> (m, N) f32."""
    K, N = w.shape
    bm = effective_block_m(m, block_m)
    bn = _pick_block_n(N, block_n)
    G = cdiv(m, bm)
    if cap is None:
        cap = int(rows.shape[0])
    br, bc, bs, _ = bucket_coo(rows, cols, signs, G * bm, bm, cap)
    out = l2_spmm_pallas(br, bc, bs, w, block_m=bm, block_n=bn, mode=mode,
                         interpret=_interpret())
    return out[:m]


# -------------------------------------------------------------------- LIF ---
def lif_step(v: jax.Array, x: jax.Array, *, decay: float = 0.5, threshold: float = 1.0,
             reset: str = "hard",
             use_pallas: bool = True) -> tuple[jax.Array, jax.Array]:
    """LIF update on arbitrary-shape tensors; returns (spike, v')."""
    if not use_pallas:
        return ref.lif_ref(v, x, decay, threshold, reset)
    shape = v.shape
    n = int(np.prod(shape))
    c = shape[-1] if v.ndim > 1 and shape[-1] % 128 == 0 else 128
    r = cdiv(n, c)
    br = min(256, max(8, 1 << (r - 1).bit_length()))
    pad = r * c - n
    v2 = jnp.pad(v.reshape(-1), (0, pad)).reshape(r, c)
    x2 = jnp.pad(x.reshape(-1), (0, pad)).reshape(r, c)
    v2 = _pad_rows(v2, br)
    x2 = _pad_rows(x2, br)
    s, vn = lif_pallas(v2, x2, decay=decay, threshold=threshold, reset=reset,
                       block_r=br, block_c=c, interpret=_interpret())
    s = s.reshape(-1)[:n].reshape(shape)
    vn = vn.reshape(-1)[:n].reshape(shape)
    return s, vn


# ------------------------------------------------------------ fused kernel ---
# Block-size choice for the fused kernels, keyed on the GEMM shape: the
# largest blocks whose per-program VMEM footprint fits the budget. The
# footprints below count what Mosaic allocates: every pipelined block twice
# (double buffering), each padded to whole (8, 128) f32 tiles, plus scratch
# and the body's L1/L2 accumulators.
_FUSED_TUNE_CACHE: dict[tuple, tuple[int, int]] = {}


def _tile(rows: int, cols: int) -> int:
    """Elements of a (rows, cols) block padded to whole (8, 128) tiles."""
    return cdiv(rows, 8) * 8 * cdiv(cols, 128) * 128


def _fused_vmem_bytes(bm: int, bn: int, K: int, T: int, q: int) -> int:
    """Per-program f32 footprint of ``phi_fused_pallas``."""
    gt = group_size(T, K // T)
    blocks = (_tile(bm, K) + T // gt * _tile(q, K // T * gt)
              + T * _tile(q + 1, bn) + _tile(T, q + 1) + _tile(K, bn)
              + _tile(bm, bn) + _tile(8, 128))
    return 4 * (2 * blocks + 2 * _tile(bm, bn))


def _fused_candidates(M: int, N: int) -> list[tuple[int, int]]:
    bms = [bm for bm in (128, 256) if bm <= max(8, 1 << (M - 1).bit_length())]
    bns = [bn for bn in (128, 256, 512) if N % bn == 0] or [N]
    return [(bm, bn) for bm in bms or [128] for bn in bns]


def _stream_vmem_bytes(bm: int, bn: int, K: int, T: int, q: int,
                       gt: int) -> int:
    """Per-program f32 footprint of ``phi_fused_stream_pallas``: one
    ``gt``-partition group of every operand, double-buffered, plus the
    L1/L2 accumulator scratch."""
    k = K // T
    blocks = (_tile(bm, gt * k) + _tile(q, gt * k) + gt * _tile(q + 1, bn)
              + _tile(gt, q + 1) + _tile(gt * k, bn) + _tile(bm, bn)
              + _tile(8, 128))
    return 4 * (2 * blocks + 2 * _tile(bm, bn))


def _stream_candidates(M: int, N: int, K: int,
                       T: int) -> list[tuple[int, int, int]]:
    """(block_m, block_n, group_t) candidates: groups of 1, 2, 4 or 8
    tile-aligned partition groups that divide T."""
    g0 = group_size(T, K // T)
    gts = [g0 * m for m in (8, 4, 2, 1) if T % (g0 * m) == 0]
    return [(bm, bn, gt) for bm, bn in _fused_candidates(M, N) for gt in gts]


def _prefetch_vmem_bytes(bm: int, bn: int, K: int, T: int, q: int,
                         p_active: int) -> int:
    """Per-program f32 footprint of ``phi_fused_prefetch_pallas``: the
    all-resident layout with the stripe's compact P-pattern bank, PWP
    stripe and scales in place of the full ones."""
    gt = group_size(T, K // T)
    blocks = (_tile(bm, K) + T // gt * _tile(p_active, K // T * gt)
              + T * _tile(p_active + 1, bn) + _tile(T, p_active + 1)
              + _tile(K, bn) + _tile(bm, bn) + _tile(8, 128))
    return 4 * (2 * blocks + 2 * _tile(bm, bn))


def fused_shape_viable(M: int, K: int, N: int, T: int, q: int,
                       usage: Any = None, p_active: int | None = None) -> str:
    """Shape gate for the execution policy: which fused lowering (if any)
    fits the VMEM budget for this shape.

    Returns ``"fused"`` when some all-resident block config fits (the
    kernel holds the whole (bm, K) activation block and (K, bn) weight
    stripe on-chip), else ``"fused_stream"`` when some K-group config fits,
    else ``"coo"`` (pure-XLA fallback — in practice only pathological
    pattern counts land here; K no longer matters since streaming holds
    just ``group_t`` partitions resident).

    With a calibration ``usage`` histogram ((T, q+1) counts from
    ``core.patterns.pattern_usage``): when the histogram shows exploitable
    skew (``active_pattern_sets``) and the compact-bank working set fits,
    the answer is ``"fused_prefetch"`` — preferred over plain ``"fused"``
    because it streams only the referenced fraction of the PWP bank.
    Callers that already ran ``active_pattern_sets`` (the execution policy)
    pass the resulting gather size as ``p_active`` instead, skipping the
    duplicate histogram analysis.
    """
    if p_active is None and usage is not None:
        from repro.core.patterns import active_pattern_sets
        active, _ = active_pattern_sets(usage)
        if active is not None:
            p_active = int(active.shape[-1])
    if p_active is not None:
        if min(_prefetch_vmem_bytes(bm, bn, K, T, q, p_active)
               for bm, bn in _fused_candidates(M, N)) <= _VMEM_BUDGET_BYTES:
            return "fused_prefetch"
    if min(_fused_vmem_bytes(bm, bn, K, T, q)
           for bm, bn in _fused_candidates(M, N)) <= _VMEM_BUDGET_BYTES:
        return "fused"
    if min(_stream_vmem_bytes(bm, bn, K, T, q, gt)
           for bm, bn, gt in _stream_candidates(M, N, K, T)
           ) <= _VMEM_BUDGET_BYTES:
        return "fused_stream"
    return "coo"


def launch_cost_prefers_coo(m: int, k_dim: int, n: int, t: int, q: int,
                            *, nnz_budget: float = 0.08) -> bool:
    """Policy cost-model crossover: True when the modelled cost of the
    pure-XLA "coo" lowering undercuts the cheapest fused lowering *plus*
    one Pallas kernel launch.

    The fused kernels stream the full PWP bank and weight stripe per
    M-stripe regardless of M; the XLA path's gathers touch only referenced
    rows, so its traffic scales with M. For tiny M (decode steps) the
    fixed streams plus the launch overhead dominate — the ROADMAP's
    "kernel launch overhead dominates on TPU" crossover. Modelled in HBM
    byte-equivalents (``perfmodel.PALLAS_LAUNCH_BYTES``), so the answer is
    deterministic and unit-testable. ``fused_prefetch`` does not compete:
    on the chip its compact banks are gathered in HBM, so the modelled
    PWP-byte saving is not real there.
    """
    from repro.core.perfmodel import (
        GemmShape,
        PALLAS_LAUNCH_BYTES,
        phi_coo_traffic,
        phi_kernel_traffic,
    )
    tr = phi_kernel_traffic(GemmShape(m, k_dim, n), k=k_dim // t, q=q,
                            nnz_budget=nnz_budget)
    fused_total = min(tr["fused"].total, tr["fused_stream"].total)
    coo_total = phi_coo_traffic(GemmShape(m, k_dim, n), k=k_dim // t, q=q,
                                nnz_budget=nnz_budget)
    return coo_total < fused_total + PALLAS_LAUNCH_BYTES


def _largest_fitting(cands: list, vmem_bytes: Callable[[Any], int],
                     rank: Callable[[Any], Any]) -> Any:
    """The candidate with the highest ``rank`` among those whose footprint
    fits the budget; the smallest-footprint one when none fits."""
    fit = [c for c in cands if vmem_bytes(c) <= _VMEM_BUDGET_BYTES]
    return max(fit, key=rank) if fit else min(cands, key=vmem_bytes)


def autotune_fused_blocks(M: int, K: int, N: int, q: int,
                          T: int) -> tuple[int, int]:
    """Pick (block_m, block_n) for the fused kernel; cached per shape key."""
    key = (M, K, N, q, T)
    if key not in _FUSED_TUNE_CACHE:
        _FUSED_TUNE_CACHE[key] = _largest_fitting(
            _fused_candidates(M, N),
            lambda c: _fused_vmem_bytes(c[0], c[1], K, T, q),
            lambda c: (c[0] * c[1], c[1]))
    return _FUSED_TUNE_CACHE[key]


_STREAM_TUNE_CACHE: dict[tuple, tuple[int, int, int]] = {}


def autotune_stream_blocks(M: int, K: int, N: int, q: int,
                           T: int) -> tuple[int, int, int]:
    """Pick (block_m, block_n, group_t) for the K-streaming fused kernel:
    the largest blocks under the streaming VMEM budget, then the deepest
    group (fewer grid steps per program)."""
    key = (M, K, N, q, T)
    if key not in _STREAM_TUNE_CACHE:
        _STREAM_TUNE_CACHE[key] = _largest_fitting(
            _stream_candidates(M, N, K, T),
            lambda c: _stream_vmem_bytes(c[0], c[1], K, T, q, c[2]),
            lambda c: (c[0] * c[1], c[2], c[1]))
    return _STREAM_TUNE_CACHE[key]


_PREFETCH_TUNE_CACHE: dict[tuple, tuple[int, int]] = {}


def autotune_prefetch_blocks(M: int, K: int, N: int, q: int, T: int,
                             p_active: int) -> tuple[int, int]:
    """Pick (block_m, block_n) for the PWP-prefetching fused kernel, sized
    with its compact-bank footprint (``_prefetch_vmem_bytes``)."""
    key = (M, K, N, q, T, p_active)
    if key not in _PREFETCH_TUNE_CACHE:
        _PREFETCH_TUNE_CACHE[key] = _largest_fitting(
            _fused_candidates(M, N),
            lambda c: _prefetch_vmem_bytes(c[0], c[1], K, T, q, p_active),
            lambda c: (c[0] * c[1], c[1]))
    return _PREFETCH_TUNE_CACHE[key]


def _attn_vmem_bytes(bq: int, bkv: int, S: int, D: int, T: int, qp: int,
                     kp: int) -> int:
    """Per-program f32 working set of the Phi flash-attention kernel
    (``phi_attention._attn_kernel``): one q-block plus the full padded K/V
    panels, the pattern bank, the per-partition pattern×Q products, the
    transposed L1/L2 score accumulators, the softmax block and the output
    accumulator."""
    return 4 * (bq * D            # q block
                + 2 * S * D       # resident K and V panels
                + T * qp * kp     # pattern bank
                + (qp + 1) * bq   # pattern×Q products (one partition live)
                + 2 * bkv * bq    # L1/L2 score accumulators
                + bq * bkv        # softmax p block
                + 2 * bq * D)     # out accumulator + out block


def _attn_candidates(S: int) -> list[tuple[int, int]]:
    cap = max(8, 1 << (max(S, 1) - 1).bit_length())
    bqs = sorted({min(b, cap) for b in (128, 256, 512)})
    bkvs = sorted({min(b, cap) for b in (128, 256, 512, 1024)})
    return [(bq, bkv) for bq in bqs for bkv in bkvs]


def attn_shape_viable(S: int, D: int, T: int, qp: int, kp: int) -> bool:
    """VMEM gate for the execution policy's attention row: True when some
    (block_q, block_kv) config of the Phi flash kernel fits the budget."""
    return min(_attn_vmem_bytes(bq, bkv, S, D, T, qp, kp)
               for bq, bkv in _attn_candidates(S)) <= _VMEM_BUDGET_BYTES


_ATTN_TUNE_CACHE: dict[tuple, tuple[int, int]] = {}


def autotune_attn_blocks(S: int, D: int, T: int, qp: int,
                         kp: int) -> tuple[int, int]:
    """Pick (block_q, block_kv) for the Phi flash-attention kernel.

    Largest blocks under the ``_attn_vmem_bytes`` budget, preferring wide
    kv blocks (fewer online-softmax rescales). Deterministic, because the
    dense-flash A/B arm must run the *same* blocks for the bitwise-identity
    contract.
    """
    key = (S, D, T, qp, kp)
    if key in _ATTN_TUNE_CACHE:
        return _ATTN_TUNE_CACHE[key]
    cands = [c for c in _attn_candidates(S)
             if _attn_vmem_bytes(c[0], c[1], S, D, T, qp, kp)
             <= _VMEM_BUDGET_BYTES]
    cands = cands or [min(_attn_candidates(S),
                          key=lambda c: _attn_vmem_bytes(c[0], c[1], S, D,
                                                         T, qp, kp))]
    best = max(cands, key=lambda c: (c[0] * c[1], c[1]))
    _ATTN_TUNE_CACHE[key] = best
    return best


def phi_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        patterns: jax.Array | None, *, causal: bool = False,
                        window: int | None = None, chunk: int | None = None,
                        block_q: int | None = None,
                        block_kv: int | None = None,
                        impl: str | None = None) -> jax.Array:
    """Phi-sparse flash attention: q/k/v (B, S, H, D) with binary spike Q/K,
    patterns (T, qp, kp) calibrated on the K rows (T·kp ≤ D; the ragged
    tail is contracted densely). Output matches ``models.flash``'s
    ``flash_attention(q, k, v, causal, window, chunk, block_q, block_kv)``
    layout **bitwise** (binary operands make every score block integer-
    exact, and scale is applied after the contraction in both lowerings).

    impl: "pallas" — fused kernel (native on TPU, interpret elsewhere);
          "xla"    — pure-XLA fallback sharing the dense flash accumulator
                     (pjit-safe: SPMD regions resolve here);
          None     — "pallas".
    """
    from repro.kernels import phi_attention as pa

    B, S, H, D = q.shape
    pats = jnp.asarray(patterns)
    T, qp, kp = pats.shape
    if T * kp > D:
        raise ValueError(
            f"phi_flash_attention: pattern bank covers {T}×{kp}={T * kp} "
            f"features but head_dim is only {D} — the bank was calibrated "
            "for a different head layout")
    if block_q is None or block_kv is None:
        bq, bkv = autotune_attn_blocks(S, D, T, qp, kp)
        block_q, block_kv = block_q or bq, block_kv or bkv
    impl = impl or "pallas"
    if impl == "xla":
        return pa.phi_flash_attention_xla(
            q, k, v, pats, causal=causal, window=window, chunk=chunk,
            block_q=block_q, block_kv=block_kv)
    assert impl == "pallas", impl
    out, _ = pa.phi_flash_attention_pallas(
        q, k, v, pats, causal=causal, window=window, chunk=chunk,
        block_q=block_q, block_kv=block_kv, interpret=_interpret())
    return out


def _fused_prologue(a2: jax.Array, pwp: jax.Array,
                    pwp_scale: jax.Array | None, T: int, q: int, N: int,
                    block_m: int, block_n: int) -> tuple[
        jax.Array, jax.Array, jax.Array | None, int, int, int]:
    """Shared prologue of the fused wrappers: clamp/pad the row blocks,
    pick the N tiling, and default the PWP dequant scales. The bm·K bound
    keeps the kernels' int32 ``l2_nnz`` audit counter exact (a block holds
    at most bm·K residual entries — see ``_partition_body``)."""
    M, K = a2.shape
    bm = effective_block_m(M, block_m)
    assert bm * K < 2 ** 31, (bm, K, "l2_nnz int32 audit counter would wrap")
    a2 = _pad_rows(a2, bm)
    bn = _pick_block_n(N, block_n)
    if pwp_scale is None:
        if pwp.dtype == jnp.int8:
            raise ValueError("int8 pwp requires pwp_scale (from quantize_pwp); "
                             "without it the L1 rows would be silently unscaled")
        pwp_scale = jnp.ones((T, q + 1), jnp.float32)
    return a2, bm, bn, pwp_scale


def phi_fused(a: jax.Array, patterns: jax.Array, pwp: jax.Array, w: jax.Array,
              *, pwp_scale: jax.Array | None = None,
              block_m: int | None = None, block_n: int | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """Single-pass fused Phi matmul (matcher + L1 + L2 in one kernel).

    a (..., K) binary × w (K, N) -> ((..., N) f32, l2_nnz (num_m_blocks,)
    int32). ``l2_nnz`` counts residual entries per M-block — what a budgeted
    unfused pipeline would have had to fit in its per-block ``cap``. The
    fused kernel itself is exact for any budget (the residual is contracted
    densely in VMEM), so nothing is ever dropped.

    pwp may be f32/bf16 (pwp_scale None) or int8 with per-row scales from
    ``quantize_pwp`` — the dequant happens in-kernel on the selected rows.
    """
    lead = a.shape[:-1]
    K = a.shape[-1]
    T, q, k = patterns.shape
    N = w.shape[-1]
    a2 = a.reshape(-1, K)
    M = a2.shape[0]
    if block_m is None or block_n is None:
        tbm, tbn = autotune_fused_blocks(M, K, N, q, T)
        block_m, block_n = block_m or tbm, block_n or tbn
    a2, bm, bn, pwp_scale = _fused_prologue(a2, pwp, pwp_scale, T, q, N,
                                            block_m, block_n)
    out, nnz = phi_fused_pallas(a2, patterns, pwp, pwp_scale, w,
                                block_m=bm, block_n=bn, interpret=_interpret())
    return out[:M, :N].reshape(*lead, N), nnz


def phi_fused_stream(a: jax.Array, patterns: jax.Array, pwp: jax.Array,
                     w: jax.Array, *, pwp_scale: jax.Array | None = None,
                     block_m: int | None = None, block_n: int | None = None,
                     group_t: int | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """K-streaming fused Phi matmul — ``phi_fused`` for shapes whose
    activation block / weight stripe / pattern bank bust the VMEM budget.

    Same contract and return value as ``phi_fused`` (exact for any budget;
    per-M-block int32 ``l2_nnz`` audit counter); only ``group_t``
    K-partitions are resident per grid step, the groups pipelined
    HBM→VMEM along a grid axis.
    """
    lead = a.shape[:-1]
    K = a.shape[-1]
    T, q, k = patterns.shape
    N = w.shape[-1]
    a2 = a.reshape(-1, K)
    M = a2.shape[0]
    if block_m is None or block_n is None or group_t is None:
        tbm, tbn, tgt = autotune_stream_blocks(M, K, N, q, T)
        block_m, block_n = block_m or tbm, block_n or tbn
        group_t = group_t or tgt
    if T % group_t:
        raise ValueError(
            f"group_t={group_t} does not divide the partition count T={T}; "
            "K-partition groups must tile the partition axis (pass a "
            "divisor, or leave group_t=None to autotune)")
    a2, bm, bn, pwp_scale = _fused_prologue(a2, pwp, pwp_scale, T, q, N,
                                            block_m, block_n)
    out, nnz = phi_fused_stream_pallas(a2, patterns, pwp, pwp_scale, w,
                                       block_m=bm, block_n=bn,
                                       group_t=group_t,
                                       interpret=_interpret())
    return out[:M, :N].reshape(*lead, N), nnz


def phi_fused_prefetch(a: jax.Array, patterns: jax.Array, pwp: jax.Array,
                       w: jax.Array, *, usage: Any = None,
                       p_active: int | None = None,
                       pwp_scale: jax.Array | None = None,
                       block_m: int | None = None, block_n: int | None = None,
                       runtime_sets: jax.Array | None = None,
                       return_hist: bool = False):
    """PWP-prefetching fused Phi matmul — ``phi_fused`` that streams only
    the pattern-weight products a stripe actually references.

    The static gather-buffer size ``p_active`` comes from the calibration
    ``usage`` histogram (``core.patterns.active_pattern_sets``; pass either
    ``usage`` or an explicit ``p_active``); the per-M-stripe active index
    sets are recomputed at trace time from the live activations
    (``stripe_active_sets``) and select each stripe's compact bank.
    Same contract and return value as ``phi_fused`` except the int32
    ``l2_nnz`` counter reflects the *restricted* assignment (rows whose
    best pattern is outside their stripe's active set are counted as L2
    residual — they execute exactly, on the residual path).

    ``runtime_sets`` ((T, P) int32, concrete) supplies the active sets
    from aggregated *runtime match telemetry* instead — the trace-time
    pre-pass (and its extra read of the activations) is skipped and the
    same sets serve every stripe. Exactness is unchanged for any set
    choice. ``return_hist`` (pre-pass path only) additionally returns the
    (T, q+1) match histogram the pre-pass computed, so the caller can
    aggregate it as that telemetry.
    """
    lead = a.shape[:-1]
    K = a.shape[-1]
    T, q, k = patterns.shape
    N = w.shape[-1]
    a2 = a.reshape(-1, K)
    M = a2.shape[0]
    if runtime_sets is not None and p_active is None:
        p_active = int(runtime_sets.shape[-1])
    if p_active is None:
        from repro.core.patterns import active_pattern_sets
        if usage is None:
            raise ValueError(
                "phi_fused_prefetch needs a pattern-usage histogram (usage=) "
                "or an explicit gather size (p_active=); without one there "
                "is nothing to size the PWP gather buffer from")
        active_sets, _ = active_pattern_sets(usage)
        if active_sets is None:
            raise ValueError(
                "usage histogram shows no exploitable skew (uniform/empty "
                "calibration or tiny bank) — use impl='fused' instead")
        p_active = int(active_sets.shape[-1])
    p_active = min(int(p_active), q)
    if block_m is None or block_n is None:
        tbm, tbn = autotune_prefetch_blocks(M, K, N, q, T, p_active)
        block_m, block_n = block_m or tbm, block_n or tbn
    a2, bm, bn, pwp_scale = _fused_prologue(a2, pwp, pwp_scale, T, q, N,
                                            block_m, block_n)
    hist = None
    if runtime_sets is not None:
        rs = jnp.asarray(runtime_sets, jnp.int32)
        if rs.shape != (T, p_active):
            raise ValueError(
                f"runtime_sets shape {rs.shape} does not match the gather "
                f"buffer (T={T}, p_active={p_active}); derive them with "
                "core.patterns.top_p_sets(hist, p_active)")
        active = jnp.broadcast_to(rs[None], (a2.shape[0] // bm, T, p_active))
        if return_hist:
            raise ValueError("return_hist requires the pre-pass path "
                             "(runtime_sets=None): with runtime sets there "
                             "is no in-graph match histogram to return")
    elif return_hist:
        active, hist = stripe_active_sets(a2, patterns, p_active, bm,
                                          return_hist=True, rows=M)
    else:
        active = stripe_active_sets(a2, patterns, p_active, bm)
    out, nnz = phi_fused_prefetch_pallas(a2, patterns, pwp, pwp_scale, w,
                                         active, block_m=bm, block_n=bn,
                                         interpret=_interpret())
    out = out[:M, :N].reshape(*lead, N)
    if return_hist:
        return out, nnz, hist
    return out, nnz


# -------------------------------------------------------- pjit-scale path ---
def _phi_matmul_coo_chunked(a2: jax.Array, w: jax.Array, patterns: jax.Array,
                            pwp: jax.Array, nnz_budget: float,
                            chunk_rows: int | None = None, entry_block: int = 8192,
                            gather_dtype: Any = None,
                            pwp_scale: jax.Array | None = None) -> jax.Array:
    """Scalable pure-XLA Phi matmul: row-chunked (K-first hardware tiling).

    Per chunk of ≤``chunk_rows`` rows:
      L1 — scan over K-tiles accumulating ``pwp[t][idx[:, t]]`` (a (chunk, N)
           gather per tile; never materialises the (M, T, N) tensor);
      L2 — padded COO (int32-safe: indices local to the chunk), processed in
           ``entry_block``-sized slabs of gather + scatter-add.
    This is the lowering used inside pjit graphs at 32k-prefill scale, where
    the flat formulation overflows int32 and the dense gather wouldn't fit.
    """
    M, K = a2.shape
    N = w.shape[-1]
    chunk_rows, cap = coo_chunk_layout(M, K, nnz_budget, chunk_rows, entry_block)
    gather_dtype = gather_dtype or jnp.float32
    from repro.core.assign import assign_patterns, pack_l2_coo_jit

    nc = cdiv(M, chunk_rows)
    pad = nc * chunk_rows - M
    a3 = jnp.pad(a2, ((0, pad), (0, 0))).reshape(nc, chunk_rows, K)
    wf = w.astype(gather_dtype)     # gathers stream in gather_dtype, accumulate f32
    pwpf = pwp if pwp.dtype == jnp.int8 else pwp.astype(gather_dtype)

    def one_chunk(chunk_a):
        idx, residual = assign_patterns(chunk_a, patterns)

        if pwp_scale is not None:  # int8 PWP: dequantise per gathered row
            def tile_step(acc, tp):
                pwp_t, scale_t, idx_t = tp
                rows = pwp_t[idx_t].astype(jnp.float32) * scale_t[idx_t][:, None]
                return acc + rows, None

            out1, _ = jax.lax.scan(
                tile_step, jnp.zeros((chunk_rows, N), jnp.float32),
                (pwpf, pwp_scale.astype(jnp.float32), jnp.swapaxes(idx, 0, 1)))
        else:
            def tile_step(acc, tp):
                pwp_t, idx_t = tp
                return acc + pwp_t[idx_t].astype(jnp.float32), None

            out1, _ = jax.lax.scan(
                tile_step, jnp.zeros((chunk_rows, N), jnp.float32),
                (pwpf, jnp.swapaxes(idx, 0, 1)))

        rows, cols, signs, _ = pack_l2_coo_jit(residual, cap)
        blocks = (rows.reshape(-1, entry_block), cols.reshape(-1, entry_block),
                  signs.reshape(-1, entry_block))

        def entry_step(acc, blk):
            r, c, s = blk
            vals = wf[c].astype(jnp.float32) * s.astype(jnp.float32)[:, None]
            return acc.at[r].add(vals, mode="drop"), None

        out2, _ = jax.lax.scan(
            entry_step, jnp.zeros((chunk_rows + 1, N), jnp.float32), blocks)
        return out1 + out2[:chunk_rows]

    out = jax.lax.map(one_chunk, a3)
    return out.reshape(nc * chunk_rows, N)[:M]


# -------------------------------------------------------------- composite ---
def phi_matmul(
    a: jax.Array,
    w: jax.Array,
    patterns: jax.Array,
    pwp: jax.Array,
    *,
    impl: str = "pallas",
    nnz_budget: float = 0.08,
    block_m: int | None = None,   # None: autotune (fused) / 256 (pallas)
    block_n: int | None = None,
    group_t: int | None = None,   # fused_stream K-group depth (None: autotune)
    gather_dtype: Any = None,
    pwp_scale: jax.Array | None = None,
    usage: Any = None,                   # fused_prefetch: (T, q+1) usage histogram
    p_active: int | None = None,  # fused_prefetch: explicit gather size
) -> jax.Array:
    """Full Phi sparse matmul: a (..., K) binary × w (K, N) -> (..., N) f32.

    impl:
      "fused"          — single-pass Pallas kernel (match + L1 + L2 fused in
                         VMEM; index/residual never touch HBM; exact for any
                         budget);
      "fused_stream"   — same fused pipeline, K-partition groups streamed
                         HBM→VMEM on a grid axis so large-K shapes stay on
                         the fused dataflow;
      "fused_prefetch" — same fused pipeline, only the PWP rows referenced
                         per M-stripe reach VMEM (per-stripe compact bank;
                         needs ``usage`` or ``p_active``);
      "pallas"         — matcher/gather/spmm kernels (interpret mode off-TPU);
      "coo"            — pure-XLA gather/scatter path (pjit-safe; dry-run);
      "ref"            — dense L2 oracle (exactness baseline).
    ``nnz_budget`` is the static L2 capacity as a fraction of M·K (paper
    measures ≈3% density; default leaves 2.6× headroom). It does not apply
    to "fused"/"fused_stream"/"ref", which are budget-free.
    """
    lead = a.shape[:-1]
    K = a.shape[-1]
    N = w.shape[-1]
    a2 = a.reshape(-1, K)
    M = a2.shape[0]
    if impl == "ref":
        return ref.phi_matmul_ref(a2, w, patterns, pwp).reshape(*lead, N)

    if impl == "fused":
        out, _ = phi_fused(a2, patterns, pwp, w, pwp_scale=pwp_scale,
                           block_m=block_m, block_n=block_n)
        return out.reshape(*lead, N)

    if impl == "fused_stream":
        out, _ = phi_fused_stream(a2, patterns, pwp, w, pwp_scale=pwp_scale,
                                  block_m=block_m, block_n=block_n,
                                  group_t=group_t)
        return out.reshape(*lead, N)

    if impl == "fused_prefetch":
        out, _ = phi_fused_prefetch(a2, patterns, pwp, w, usage=usage,
                                    p_active=p_active, pwp_scale=pwp_scale,
                                    block_m=block_m, block_n=block_n)
        return out.reshape(*lead, N)

    from repro.core.assign import assign_patterns, pack_l2_coo_jit

    if impl == "coo":
        return _phi_matmul_coo_chunked(a2, w, patterns, pwp, nnz_budget,
                                       gather_dtype=gather_dtype,
                                       pwp_scale=pwp_scale).reshape(*lead, N)

    assert impl == "pallas", impl
    block_m = block_m or 256
    block_n = block_n or 256
    idx, residual = matcher(a2, patterns, block_m=block_m)
    out1 = l1_gather(idx, pwp, block_m=block_m, block_n=block_n)
    cap = max(128, int(nnz_budget * M * K))
    rows, cols, signs, _ = pack_l2_coo_jit(residual, cap)
    # Per-block capacity: same budget with 4× local-imbalance headroom
    # (shared derivation with phi_l2_audit — see l2_per_block_cap).
    per_block = l2_per_block_cap(nnz_budget, block_m, K, cap)
    out2 = l2_spmm(rows, cols, signs, w.astype(jnp.float32), M,
                   block_m=block_m, block_n=block_n, cap=per_block)
    return (out1 + out2).reshape(*lead, N)
