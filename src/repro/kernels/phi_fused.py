"""Pallas TPU kernel: fused single-pass Phi matmul (paper Sec. 4.2–4.3).

The ASIC processes the two-level hierarchy *on the fly*: the matcher feeds
pattern indices straight into the L1 PWP retrieval and the ±1 residual
straight into the L2 adder trees — neither ever touches DRAM. The seed's
``impl="pallas"`` path instead launches three kernels
(``matcher_pallas`` → ``l1_gather_pallas`` → ``l2_spmm_pallas``) and
round-trips the (M, T) index and (M, K) residual tensors through HBM between
them — exactly the traffic Prosperity/SpikeX-class dataflows keep on-chip.

These kernels fuse the whole pipeline. Per K-partition:

    1. match:   Hamming-as-matmul ``H = |a|₁ + |p|₁ − 2·a·pᵀ`` on the MXU,
                argmin + the strictly-better-than-bit-sparsity rule on the
                VPU — identical math to ``matcher_pallas`` but the (bm,)
                index vector lives only in registers;
    2. L1:      one-hot(idx) @ PWP[t] — the systolic gather of
                ``l1_gather_pallas`` — accumulated into the VMEM out block;
                int8 PWPs are dequantised per selected row by the same
                one-hot selection of the (q+1,) scale vector;
    3. L2:      ``residual_t @ W[tk:(t+1)k]`` — the residual (bm, k) block
                of {−1, 0, +1} *is* the signed one-hot matrix of its own
                COO entries, so the scatter-as-contraction trick of
                ``l2_spmm_pallas`` degenerates to a single dense MXU call on
                the in-register residual. No packing, no per-block capacity,
                no dropped entries: fusion makes the L2 budget unconstrained.

The L1 and L2 contractions run at ``Precision.HIGHEST``: a TPU's default f32
matmul rounds its operands to bf16, which would break the one-hot selection
of PWP rows and the ±1 contraction against the weights. The match and the
chosen-pattern products contract binary operands and are exact at any
precision.

The K-partitions are processed in *groups* of ``group_t`` partitions
(``group_size``): a group's activation columns and weight rows are whole
(8, 128) tiles, so every dynamic slice is tile-aligned, and only one group
is unrolled in the kernel body. Each kernel emits the per-M-block L2 nnz
count (an int32 (8, 128) tile per grid program) so callers can audit what a
budgeted (capacity-``cap``) unfused pipeline *would have dropped*.

Three variants share the accumulation body (``_accumulate``):

  * ``phi_fused_pallas``          — the (bm, K) activation block, the
    (K, bn) weight stripe and the whole pattern/PWP bank stripe resident;
    the kernel loops over partition groups;
  * ``phi_fused_stream_pallas``   — K-partition groups on a third
    ("arbitrary") grid axis, so only ``group_t`` partitions of every
    operand are resident and Pallas double-buffers the group copies
    HBM→VMEM; keeps large-K layers on the fused dataflow instead of
    demoting them to the pure-XLA "coo" path;
  * ``phi_fused_prefetch_pallas`` — the paper's PWP prefetcher (Sec. 4.4:
    only ~27.73% of PWPs are referenced per M-stripe): per-M-stripe
    active-pattern index sets (``stripe_active_sets``; the static set size
    comes from the calibration usage histogram) select P of the q patterns
    per partition, and the kernel runs over each stripe's compact P-pattern
    bank. Rows whose best pattern is *not* in their stripe's active set
    fall through to the L2 residual, so the restriction changes the
    decomposition, never the product. The compact banks are gathered by
    XLA in HBM: a TPU DMA cannot address a single PWP row of the
    (8, 128)-tiled bank, so the kernel does not gather them itself.

The same bodies run natively on a TPU and in interpret mode elsewhere.
Every variant is shard_map-invocable: a shard_map body hands them plain
per-shard local operands, so no partitioning rule is needed (callers pass
``check_vma=False`` — pallas_call has no replication rule) and the
execution policy keeps the fused dataflow under SPMD serving by re-gating
on the local shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hwconst import VMEM_LIMIT_BYTES

_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
_SUBLANES = 8
# The audit counter is written as one int32 (8, 128) tile per program.
_NNZ_TILE = (_SUBLANES, _LANES)


def _compiler_params(*semantics: str) -> pltpu.CompilerParams:
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT_BYTES)


def group_size(T: int, k: int) -> int:
    """Smallest partition-group size whose slices are tile-aligned on TPU:
    ``G·k`` a multiple of 128 lanes (activation columns) and ``G`` a
    multiple of 8 sublanes (scale rows). ``T`` when no divisor of ``T``
    qualifies — the whole K is then one statically unrolled group."""
    for g in range(1, T + 1):
        if T % g == 0 and (g * k) % _LANES == 0 and g % _SUBLANES == 0:
            return g
    return T


def _partition_body(at, p, pwp_t, scale_t, w_t, acc1, acc2, nnz, *, q: int):
    """One K-partition of the fused pipeline: match → L1 → L2.

    at (bm, k) f32 binary, p (q, k) f32, pwp_t (q+1, bn), scale_t (1, q+1)
    f32, w_t (k, bn). Shared by every fused kernel and the Phi attention
    kernel, so the lowerings are the same math (and the same summation
    association) by construction. ``nnz`` accumulates in int32 — an f32
    accumulator is exact only below 2²⁴ residual entries per M-block, which
    large bm·K kernels exceed and would silently round the packer-budget
    telemetry.
    """
    # -- match (MXU): H = |a| + |p| − 2 a·pᵀ -------------------------------
    dot = jnp.dot(at, p.T, preferred_element_type=jnp.float32)      # (bm, q)
    pop_a = at.sum(-1)                                     # (bm,)
    ham = pop_a[:, None] + p.sum(-1)[None, :] - 2.0 * dot
    best = jnp.argmin(ham, axis=-1)                        # (bm,)
    use = jnp.min(ham, axis=-1) < pop_a                    # strict rule
    idx = jnp.where(use, best, q)                          # q == "none"
    # -- L1 (MXU): one-hot retrieval straight from registers ---------------
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, q + 1), 1)
    onehot = (idx[:, None] == slots).astype(jnp.float32)   # (bm, q+1)
    rows = jnp.dot(onehot, pwp_t.astype(jnp.float32), precision=_HIGHEST,
                   preferred_element_type=jnp.float32)     # (bm, bn)
    row_scale = (onehot * scale_t).sum(-1, keepdims=True)  # (bm, 1), exact
    acc1 = acc1 + rows * row_scale
    # -- L2 (MXU): in-register residual, contraction against W tile --------
    chosen = jnp.dot(onehot[:, :q], p, preferred_element_type=jnp.float32)
    residual = at - chosen                                 # (bm, k) {−1,0,+1}
    acc2 = acc2 + jnp.dot(residual, w_t.astype(jnp.float32),
                          precision=_HIGHEST,
                          preferred_element_type=jnp.float32)
    nnz = nnz + jnp.abs(residual).astype(jnp.int32).sum()
    return acc1, acc2, nnz


def _rows(ref, start, size: int, axis: int):
    """Load ``size`` entries of ``ref`` along ``axis`` (0 or 1) from
    ``start``, a Python int or a traced multiple of ``size``."""
    if not isinstance(start, int):
        start = pl.multiple_of(start, size)
    idx = pl.ds(start, size)
    return ref[idx, :] if axis == 0 else ref[:, idx]


def group_major(patterns: jax.Array, group_t: int) -> jax.Array:
    """(T, q, k) pattern bank -> (T/G, q, G·k): each group's patterns side
    by side along the lanes, the layout of the activation columns they are
    matched against (lane-dense in VMEM, where (q, k) slabs would pad k to
    128 lanes)."""
    T, q, k = patterns.shape
    return (patterns.reshape(T // group_t, group_t, q, k)
            .transpose(0, 2, 1, 3).reshape(T // group_t, q, group_t * k))


def _accumulate(a_ref, p_ref, pwp_ref, scale_ref, w_ref, carry, *,
                q: int, k: int):
    """Match → L1 → L2 over every partition the refs hold, one tile-aligned
    partition group at a time: a single group statically unrolled, several
    in a loop so only one group is unrolled in the body.

    a_ref (bm, n·G·k), p_ref (n, q, G·k) (``group_major``), pwp_ref
    (n·G, q+1, bn), scale_ref (n·G, q+1), w_ref (n·G·k, bn); carry is
    (L1 acc, L2 acc, int32 nnz)."""
    n, _, gk = p_ref.shape
    group_t = gk // k

    def group(g, carry):
        t0 = g * group_t
        a_g = _rows(a_ref, t0 * k, gk, 1)
        p_g = p_ref[g]
        scale_g = _rows(scale_ref, t0, group_t, 0)
        w_g = _rows(w_ref, t0 * k, gk, 0)
        for s in range(group_t):
            cols = slice(s * k, (s + 1) * k)
            carry = _partition_body(
                a_g[:, cols], p_g[:, cols], pwp_ref[t0 + s],
                scale_g[s:s + 1], w_g[cols, :], *carry, q=q)
        return carry

    if n == 1:
        return group(0, carry)
    return jax.lax.fori_loop(0, n, group, carry)


def _zero_carry(shape):
    return (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32),
            jnp.zeros((), jnp.int32))


def _nnz_blocks(gm: int, gn: int):
    """Audit-counter output: one (8, 128) int32 tile per (i, j) program,
    every element holding that program's count."""
    return (pl.BlockSpec(_NNZ_TILE, lambda i, j, *_: (i, j)),
            jax.ShapeDtypeStruct((gm * _SUBLANES, gn * _LANES), jnp.int32))


def _per_m_block(nnz: jax.Array) -> jax.Array:
    """(M // block_m,) counts from the per-program tiles (every N-block of
    an M-stripe counts the same residual)."""
    return nnz[::_SUBLANES, 0]


def _fused_kernel(a_ref, p_ref, pwp_ref, scale_ref, w_ref, out_ref, nnz_ref,
                  *, q: int, k: int):
    # L1 and L2 accumulate separately and are added once at the end — the
    # same association the unfused lowerings use (out1 + out2). Since every
    # partial product is exact (one-hot selections; ±1 residual entries),
    # the fused output is then BITWISE identical to the "coo" path under
    # dyadic weights, which lets serving stacks A/B dispatch modes with
    # exact-equality regression tests instead of tolerances.
    acc1, acc2, nnz = _accumulate(a_ref, p_ref, pwp_ref, scale_ref, w_ref,
                                  _zero_carry(out_ref.shape), q=q, k=k)
    out_ref[...] = acc1 + acc2
    nnz_ref[...] = jnp.full(nnz_ref.shape, nnz, jnp.int32)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def phi_fused_pallas(
    a: jax.Array,
    patterns: jax.Array,
    pwp: jax.Array,
    pwp_scale: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-pass Phi matmul.

    a:         (M, K) binary float; M must be a multiple of block_m (ops pads)
    patterns:  (T, q, k) with K = T·k
    pwp:       (T, q+1, N) f32/bf16/int8, pwp[:, q] == 0; N multiple of block_n
    pwp_scale: (T, q+1) f32 per-row dequant scales (all-ones when unquantised)
    w:         (K, N) f32/bf16

    Returns (out (M, N) f32, l2_nnz (M // block_m,) int32 — residual entries
    per M-block, the budget-audit counter).
    """
    M, K = a.shape
    T, q, k = patterns.shape
    N = w.shape[-1]
    assert K == T * k and M % block_m == 0 and N % block_n == 0, (
        a.shape, patterns.shape, w.shape, block_m, block_n)
    assert pwp.shape == (T, q + 1, N) and pwp_scale.shape == (T, q + 1)
    gm, gn = M // block_m, N // block_n
    group_t = group_size(T, k)
    nnz_spec, nnz_shape = _nnz_blocks(gm, gn)
    out, nnz = pl.pallas_call(
        functools.partial(_fused_kernel, q=q, k=k),
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
            pl.BlockSpec((T // group_t, q, group_t * k),
                         lambda i, j: (0, 0, 0)),
            pl.BlockSpec((T, q + 1, block_n), lambda i, j: (0, 0, j)),
            pl.BlockSpec((T, q + 1), lambda i, j: (0, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
                   nnz_spec],
        out_shape=[jax.ShapeDtypeStruct((M, N), jnp.float32), nnz_shape],
        interpret=interpret,
        # disjoint (i, j) output blocks: both axes may split across cores
        compiler_params=_compiler_params("parallel", "parallel"),
    )(a.astype(jnp.float32), group_major(patterns.astype(jnp.float32), group_t),
      pwp, pwp_scale.astype(jnp.float32), w)
    return out, _per_m_block(nnz)


# ------------------------------------------------------- K-streaming kernel ---
# For large K the all-resident kernel above cannot hold the (bm, K)
# activation block, (K, bn) weight stripe and T-partition pattern/PWP
# tensors in VMEM at once. The streaming variant puts the partition groups
# on a third grid axis: only one ``group_t``-partition group of every
# operand is resident, Pallas double-buffers the next group's copies while
# the current one is matched and contracted, and the L1/L2 accumulators
# live in VMEM scratch across the group axis.


def _fused_stream_kernel(a_ref, p_ref, pwp_ref, scale_ref, w_ref,
                         out_ref, nnz_ref, acc1_ref, acc2_ref, *, q: int,
                         k: int):
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _():
        acc1_ref[...] = jnp.zeros(acc1_ref.shape, jnp.float32)
        acc2_ref[...] = jnp.zeros(acc2_ref.shape, jnp.float32)
        nnz_ref[...] = jnp.zeros(nnz_ref.shape, jnp.int32)

    acc1, acc2, nnz = _accumulate(
        a_ref, p_ref, pwp_ref, scale_ref, w_ref,
        (acc1_ref[...], acc2_ref[...], jnp.zeros((), jnp.int32)), q=q, k=k)
    acc1_ref[...] = acc1
    acc2_ref[...] = acc2
    nnz_ref[...] = nnz_ref[...] + jnp.full(nnz_ref.shape, nnz, jnp.int32)

    @pl.when(g == pl.num_programs(2) - 1)
    def _():
        out_ref[...] = acc1_ref[...] + acc2_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "group_t",
                                             "interpret"))
def phi_fused_stream_pallas(
    a: jax.Array,
    patterns: jax.Array,
    pwp: jax.Array,
    pwp_scale: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    group_t: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """K-streaming fused Phi matmul: same contract as ``phi_fused_pallas``
    (and the same per-partition math and association, so the two agree
    bitwise), but only ``group_t`` K-partitions are resident per grid step,
    so shapes whose (bm, K) activation block or (K, bn) weight stripe bust
    VMEM still run fused instead of falling back to the XLA "coo" path.

    Returns (out (M, N) f32, l2_nnz (M // block_m,) int32). group_t must
    divide T; on TPU ``group_t·k`` must be a multiple of 128 and ``group_t``
    of 8 (``group_size``), or ``group_t == T``.
    """
    M, K = a.shape
    T, q, k = patterns.shape
    N = w.shape[-1]
    assert K == T * k and M % block_m == 0 and N % block_n == 0, (
        a.shape, patterns.shape, w.shape, block_m, block_n)
    assert T % group_t == 0, (T, group_t)
    assert pwp.shape == (T, q + 1, N) and pwp_scale.shape == (T, q + 1)
    gm, gn = M // block_m, N // block_n
    gk = group_t * k
    # the body walks each streamed group in tile-aligned sub-groups
    sub = group_size(T, k)
    sub = sub if group_t % sub == 0 else group_t
    nnz_spec, nnz_shape = _nnz_blocks(gm, gn)
    out, nnz = pl.pallas_call(
        functools.partial(_fused_stream_kernel, q=q, k=k),
        grid=(gm, gn, T // group_t),
        in_specs=[
            pl.BlockSpec((block_m, gk), lambda i, j, g: (i, g)),
            pl.BlockSpec((group_t // sub, q, sub * k),
                         lambda i, j, g: (g, 0, 0)),
            pl.BlockSpec((group_t, q + 1, block_n), lambda i, j, g: (g, 0, j)),
            pl.BlockSpec((group_t, q + 1), lambda i, j, g: (g, 0)),
            pl.BlockSpec((gk, block_n), lambda i, j, g: (g, j)),
        ],
        out_specs=[pl.BlockSpec((block_m, block_n), lambda i, j, g: (i, j)),
                   nnz_spec],
        out_shape=[jax.ShapeDtypeStruct((M, N), jnp.float32), nnz_shape],
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32),   # L1
                        pltpu.VMEM((block_m, block_n), jnp.float32)],  # L2
        interpret=interpret,
        # the group axis revisits the same out block: sequential
        compiler_params=_compiler_params("parallel", "parallel",
                                         "arbitrary"),
    )(a.astype(jnp.float32), group_major(patterns.astype(jnp.float32), sub),
      pwp, pwp_scale.astype(jnp.float32), w)
    return out, _per_m_block(nnz)


# ----------------------------------------------- PWP-prefetching kernel ------
# The all-resident and streaming kernels fetch the ENTIRE (T, q+1, bn) PWP
# stripe per M-stripe even though a stripe's rows reference only a fraction
# of the pattern bank (the paper measures ~27.73%). The prefetch variant
# restricts the match to a per-stripe set of P "active" patterns — P sized
# statically from the calibration usage histogram
# (``core.patterns.active_pattern_sets``), the per-stripe index sets computed
# at trace time from the live activations — so only P+1 of q+1 PWP rows per
# partition reach VMEM. Exactness is preserved unconditionally: a row
# whose best pattern is outside its stripe's active set simply matches no
# pattern and its raw bits land in the L2 residual, which is contracted
# against the resident weight stripe.


def stripe_active_sets(a2: jax.Array, patterns: jax.Array, p_active: int,
                       block_m: int, return_hist: bool = False,
                       rows: int | None = None):
    """Per-M-stripe active-pattern index sets, computed at trace time.

    a2: (M, K) binary with M a multiple of block_m; patterns: (T, q, k).
    Returns (M // block_m, T, p_active) int32 — for each stripe and
    K-partition, the ``p_active`` patterns most referenced by the stripe's
    rows (the same Hamming-as-matmul match the kernels run, reduced to
    per-stripe reference counts before any index ever reaches HBM).

    With ``return_hist`` additionally returns the (T, q+1) int32 match
    histogram of the whole call (stripe counts summed, column q counting
    unmatched row-partitions) — the runtime match telemetry the execution
    policy aggregates per site so that *later* traces can skip this
    pre-pass entirely and gather from the aggregated histogram instead
    (``dispatch`` passes it back as ``runtime_sets``). ``rows`` is the
    *unpadded* row count: ``a2`` arrives zero-padded to a ``block_m``
    multiple, and padding rows must not count as unmatched tiles (they can
    never be assigned — all-zero rows match nothing under the strict rule
    — so only the unmatched column needs the correction).
    """
    M, K = a2.shape
    T, q, k = patterns.shape
    assert M % block_m == 0 and K == T * k, (a2.shape, patterns.shape, block_m)
    gm = M // block_m
    at = a2.reshape(gm, block_m, T, k).astype(jnp.float32)
    pf = patterns.astype(jnp.float32)
    dot = jnp.einsum("gmtk,tqk->gmtq", at, pf)
    pop_a = at.sum(-1)                                     # (gm, bm, T)
    ham = pop_a[..., None] + pf.sum(-1)[None, None] - 2.0 * dot
    best = jnp.argmin(ham, axis=-1)                        # (gm, bm, T)
    use = jnp.min(ham, axis=-1) < pop_a                    # strict rule
    onehot = jax.nn.one_hot(best, q, dtype=jnp.float32) * use[..., None]
    counts = onehot.sum(axis=1)                            # (gm, T, q)
    _, top = jax.lax.top_k(counts, p_active)               # (gm, T, P)
    if not return_hist:
        return top.astype(jnp.int32)
    assigned = counts.sum(axis=0)                          # (T, q)
    unmatched = (jnp.full((T, 1), float(M if rows is None else rows)) -
                 assigned.sum(-1, keepdims=True))
    hist = jnp.concatenate([assigned, unmatched], axis=-1).astype(jnp.int32)
    return top.astype(jnp.int32), hist


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def phi_fused_prefetch_pallas(
    a: jax.Array,
    patterns: jax.Array,
    pwp: jax.Array,
    pwp_scale: jax.Array,
    w: jax.Array,
    active: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """PWP-prefetching fused Phi matmul: same contract as ``phi_fused_pallas``
    plus ``active`` (M // block_m, T, P) int32 — the per-M-stripe pattern
    index sets from ``stripe_active_sets``. Each M-stripe's program holds
    only the stripe's compact (T, P+1, bn) PWP stripe; the match is
    restricted to the active set and every other row falls through to the
    exact L2 residual path.

    Returns (out (M, N) f32, l2_nnz (M // block_m,) int32 — residual entries
    *under the restricted assignment*, ≥ the full-bank kernels' counter).
    """
    M, K = a.shape
    T, q, k = patterns.shape
    N = w.shape[-1]
    gm, gn = M // block_m, N // block_n
    p_active = active.shape[-1]
    assert K == T * k and M % block_m == 0 and N % block_n == 0, (
        a.shape, patterns.shape, w.shape, block_m, block_n)
    assert active.shape == (gm, T, p_active) and p_active <= q, active.shape
    assert pwp.shape == (T, q + 1, N) and pwp_scale.shape == (T, q + 1)
    group_t = group_size(T, k)
    # Per-stripe compact banks: P active patterns, their PWP rows and
    # scales, plus the "none" slot P (zero PWP row, the bank's none scale).
    tidx = jnp.arange(T)[None, :, None]
    pats_c = jax.vmap(lambda p: group_major(p, group_t))(
        patterns.astype(jnp.float32)[tidx, active])     # (gm, T/G, P, G·k)
    pwp_c = jnp.concatenate(
        [pwp[tidx, active], jnp.zeros((gm, T, 1, N), pwp.dtype)],
        axis=2)                                         # (gm, T, P+1, N)
    scale_c = jnp.concatenate(
        [pwp_scale[tidx, active],
         jnp.broadcast_to(pwp_scale[None, :, q, None], (gm, T, 1))],
        axis=2).astype(jnp.float32)                     # (gm, T, P+1)
    nnz_spec, nnz_shape = _nnz_blocks(gm, gn)
    out, nnz = pl.pallas_call(
        functools.partial(_fused_kernel, q=p_active, k=k),
        grid=(gm, gn),
        in_specs=[
            pl.BlockSpec((block_m, K), lambda i, j: (i, 0)),
            pl.BlockSpec((None, T // group_t, p_active, group_t * k),
                         lambda i, j: (i, 0, 0, 0)),
            pl.BlockSpec((None, T, p_active + 1, block_n),
                         lambda i, j: (i, 0, 0, j)),
            pl.BlockSpec((None, T, p_active + 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
        ],
        out_specs=[pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
                   nnz_spec],
        out_shape=[jax.ShapeDtypeStruct((M, N), jnp.float32), nnz_shape],
        interpret=interpret,
        compiler_params=_compiler_params("parallel", "parallel"),
    )(a.astype(jnp.float32), pats_c, pwp_c, scale_c, w)
    return out, _per_m_block(nnz)
