"""Phi calibration: binary k-means pattern selection (paper Alg. 1).

Patterns are selected *per K-partition* of the activation matrix. Each
activation row slice of length ``k`` is a point in {0,1}^k; the calibration
runs Hamming-metric k-means and rounds centroids back to {0,1}.

Filtering (paper Sec. 3.2): all-zero rows need no compute and one-hot rows can
never beat their own bit sparsity via a non-identical pattern (and a one-hot
pattern's PWP is just a weight row), so both are removed before clustering.

The Hamming distance is computed as a matmul — ``H(x, c) = |x| + |c| - 2 x·c``
— which is also how the TPU matcher kernel evaluates it on the MXU.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class PhiConfig:
    """Hyper-parameters of Phi sparsity (paper defaults: k=16, q=128)."""

    k: int = 16          # K-partition (pattern) length
    q: int = 128         # number of patterns per partition
    iters: int = 20      # k-means iterations
    timesteps: int = 4   # SNN timesteps (spiking-mode LMs)
    nnz_budget: float = 0.10  # static L2 capacity as fraction of M·K
    pwp_int8: bool = False    # beyond-paper: int8 PWPs w/ per-row scales
    seed: int = 0
    # Execution override for kernels.dispatch: None = the execution policy
    # picks per call (fused on single device, coo in SPMD regions); a name
    # from dispatch.IMPLS forces that lowering everywhere it is safe.
    impl: str | None = None

    def __post_init__(self) -> None:
        assert self.k >= 2 and self.q >= 1
        if self.impl is not None:
            from repro.kernels.dispatch import IMPLS  # single source of truth
            assert self.impl in IMPLS, (self.impl, IMPLS)


def _hamming(x: jax.Array, c: jax.Array) -> jax.Array:
    """Pairwise Hamming distances between binary x (n,k) and c (q,k) -> (n,q)."""
    xf = x.astype(jnp.float32)
    cf = c.astype(jnp.float32)
    return xf.sum(-1, keepdims=True) + cf.sum(-1)[None, :] - 2.0 * (xf @ cf.T)


def filter_rows(x: jax.Array) -> jax.Array:
    """Mask of rows that survive calibration filtering (not all-zero/one-hot)."""
    pop = x.sum(-1)
    return (pop >= 2)


@functools.partial(jax.jit, static_argnames=("q", "iters"))
def _kmeans_binary_jit(
    data: jax.Array, weight: jax.Array, q: int, iters: int, key: jax.Array
) -> jax.Array:
    """Weighted Hamming k-means on binary rows.

    data:   (n, k) float32 in {0,1}; rows to cluster (filtered rows get weight 0)
    weight: (n,) float32 multiplicity/validity weight per row
    Returns (q, k) binary float32 centers.
    """
    n, k = data.shape
    # Initialize from random (valid) rows — Alg. 1 line 1.
    p = weight / jnp.maximum(weight.sum(), 1e-9)
    idx0 = jax.random.choice(key, n, shape=(q,), replace=True, p=p)
    centers0 = data[idx0]

    def body(centers, _):
        d = _hamming(data, centers)                      # (n, q)
        assign = jnp.argmin(d, axis=-1)                  # (n,)
        onehot = jax.nn.one_hot(assign, q, dtype=jnp.float32) * weight[:, None]
        counts = onehot.sum(0)                           # (q,)
        sums = onehot.T @ data                           # (q, k)
        means = sums / jnp.maximum(counts, 1.0)[:, None]
        new_centers = jnp.where(means >= 0.5, 1.0, 0.0)  # Alg. 1 line 6: round
        # Empty clusters keep their previous center (deterministic, jit-safe).
        new_centers = jnp.where((counts > 0)[:, None], new_centers, centers)
        return new_centers, None

    centers, _ = jax.lax.scan(body, centers0, None, length=iters)
    return centers


def kmeans_binary(data: np.ndarray | jax.Array, q: int, iters: int = 20, seed: int = 0) -> np.ndarray:
    """Paper Alg. 1 on one partition's rows. Returns (q, k) uint8 patterns.

    Duplicate rows are collapsed to unique rows with multiplicity weights,
    which makes calibration O(unique · q) instead of O(n · q) — on binary
    k=16 slices the number of unique rows is at most 65536 and in practice
    a few hundred, so this is the paper's "linear complexity" claim realized.
    """
    x = np.asarray(data, dtype=np.uint8)
    assert x.ndim == 2
    keep = np.asarray(filter_rows(jnp.asarray(x, jnp.float32)))
    x = x[keep]
    if x.shape[0] == 0:
        return np.zeros((q, data.shape[1]), np.uint8)
    uniq, counts = np.unique(x, axis=0, return_counts=True)
    if uniq.shape[0] <= q:
        out = np.zeros((q, x.shape[1]), np.uint8)
        out[: uniq.shape[0]] = uniq
        return out
    # Pad the unique rows to a power of two with zero weight (never drawn,
    # never counted) so the jitted k-means compiles once per size class,
    # not once per distinct unique-row count.
    n = uniq.shape[0]
    pad = (1 << (n - 1).bit_length()) - n
    centers = _kmeans_binary_jit(
        jnp.asarray(np.pad(uniq, ((0, pad), (0, 0))), jnp.float32),
        jnp.asarray(np.pad(counts, (0, pad)), jnp.float32),
        q,
        iters,
        jax.random.PRNGKey(seed),
    )
    centers = np.asarray(centers, np.uint8)
    # Dedupe identical centers: duplicates waste pattern slots; replace with
    # the highest-weight unassigned unique rows (greedy refinement).
    seen: set[bytes] = set()
    slots: list[int] = []
    for i in range(q):
        b = centers[i].tobytes()
        if b in seen:
            slots.append(i)
        else:
            seen.add(b)
    if slots:
        order = np.argsort(-counts)
        fill = [r for r in order if uniq[r].tobytes() not in seen]
        for i, r in zip(slots, fill):
            centers[i] = uniq[r]
            seen.add(uniq[r].tobytes())
    return centers


def calibrate(
    acts: np.ndarray | jax.Array, cfg: PhiConfig
) -> np.ndarray:
    """Calibrate patterns for a full activation matrix.

    acts: (M, K) binary activations (any leading dims are flattened).
    Returns patterns (T, q, k) uint8 where T = K // k (independent per
    partition, paper Sec. 3.2 "unique local distributions").
    """
    a = np.asarray(acts)
    a = a.reshape(-1, a.shape[-1])
    M, K = a.shape
    assert K % cfg.k == 0, f"K={K} not divisible by k={cfg.k}"
    T = K // cfg.k
    tiles = a.reshape(M, T, cfg.k)
    pats = np.stack(
        [kmeans_binary(tiles[:, t], cfg.q, cfg.iters, cfg.seed + t) for t in range(T)]
    )
    return pats.astype(np.uint8)


# ------------------------------------------------------- pattern usage ------
# The paper's prefetcher (Sec. 4.4) fetches only the ~27.73% of PWPs a
# workload actually references per M-stripe. The software analogue is a
# calibration-time usage histogram: it gates the execution policy onto the
# ``fused_prefetch`` lowering and sizes its static gather buffer (the
# per-M-stripe active sets themselves are recomputed at trace time from the
# live activations — see ``kernels.phi_fused.stripe_active_sets``).


def pattern_usage(acts: np.ndarray | jax.Array,
                  patterns: np.ndarray | jax.Array) -> np.ndarray:
    """Per-partition pattern-reference histogram of a calibration batch.

    acts: (..., K) binary activations; patterns: (T, q, k). Returns
    (T, q+1) int64 counts — column j < q is how many row-partitions matched
    pattern j, column q counts unmatched rows (the "no pattern" slot).
    """
    from repro.core.assign import assign_patterns  # deferred: assign imports us

    T, q, k = np.asarray(patterns).shape[-3:]
    a = np.asarray(acts, np.float32).reshape(-1, np.asarray(acts).shape[-1])
    out = np.zeros((T, q + 1), np.int64)
    if a.shape[0] == 0:          # empty calibration: all-zero histogram
        return out
    idx, _ = assign_patterns(jnp.asarray(a), jnp.asarray(patterns, jnp.float32))
    idx = np.asarray(idx)
    for t in range(T):
        out[t] = np.bincount(idx[:, t], minlength=q + 1)
    return out


def active_pattern_sets(usage: np.ndarray, *, coverage: float = 0.9,
                        max_frac: float = 0.5, min_assigned: float = 0.05,
                        pad_to: int = 8) -> tuple[np.ndarray | None, float]:
    """Hot-pattern index sets from a usage histogram, or None without skew.

    Returns ``(active (T, P) int32, usage_fraction)`` where P is the
    smallest multiple of ``pad_to`` such that the top-P patterns of every
    partition cover ≥ ``coverage`` of that partition's assigned matches, and
    ``usage_fraction = (P+1)/(q+1)`` is the modelled fraction of the PWP
    bank a prefetching kernel streams. Returns ``(None, 1.0)`` when the
    histogram shows no exploitable skew:

      * empty calibration (all-zero histogram) — nothing is known;
      * assigned fraction below ``min_assigned`` — L1 is barely used, so
        there is nothing to prefetch;
      * tiny banks (q ≤ pad_to) — a gather cannot beat streaming them;
      * uniform-ish usage — covering ``coverage`` needs > ``max_frac``·q
        patterns, so the gather saves too little to pay for itself.

    Rows matching a pattern *outside* the active set fall through to the L2
    residual (which is contracted against the resident weight stripe), so
    restricting the match to the active set never loses exactness — the
    decomposition changes, the product does not.
    """
    u = np.asarray(usage, np.float64)
    assert u.ndim == 2 and u.shape[1] >= 2, u.shape
    q = u.shape[1] - 1
    assigned = u[:, :q]
    total = u.sum()
    if total <= 0 or assigned.sum() / total < min_assigned or q <= pad_to:
        return None, 1.0
    srt = np.sort(assigned, axis=1)[:, ::-1]
    csum = np.cumsum(srt, axis=1)
    tot_t = assigned.sum(axis=1)
    need = 1
    for t in range(u.shape[0]):
        if tot_t[t] > 0:
            need = max(need, int(np.searchsorted(
                csum[t], coverage * tot_t[t], side="left")) + 1)
    p_active = min(q, -(-need // pad_to) * pad_to)
    if p_active > max_frac * q:
        return None, 1.0
    order = np.argsort(-assigned, kind="stable", axis=1)
    active = np.ascontiguousarray(order[:, :p_active]).astype(np.int32)
    return active, float(p_active + 1) / float(q + 1)


def top_p_sets(usage: np.ndarray, p: int) -> np.ndarray:
    """Top-``p`` pattern indices per partition from a usage histogram.

    usage: (T, q+1) counts (column q = unmatched, ignored). Returns
    (T, p) int32 — the gather sets a prefetching consumer (the
    ``fused_prefetch`` kernel fed runtime match telemetry, or the
    simulator's PWP prefetcher) uses when the gather-buffer size ``p`` is
    already fixed. Unlike :func:`active_pattern_sets` this never refuses:
    restricting the match to *any* set is exact (missed rows fall to the
    L2 residual), so a stale or skewless histogram costs performance, not
    correctness.
    """
    u = np.asarray(usage, np.int64)
    assert u.ndim == 2 and u.shape[1] >= 2, u.shape
    q = u.shape[1] - 1
    p = max(1, min(int(p), q))
    order = np.argsort(-u[:, :q], kind="stable", axis=1)
    return np.ascontiguousarray(order[:, :p]).astype(np.int32)


def pattern_weight_products(patterns: jax.Array, w: jax.Array) -> jax.Array:
    """Offline PWP computation: (T, q, k) patterns × (K, N) weights -> (T, q+1, N).

    Slot q (the last row of each partition) is the all-zero "no pattern
    assigned" entry so the runtime gather can index it for unmatched rows.
    """
    T, q, k = patterns.shape
    K, N = w.shape
    assert T * k == K
    wt = w.reshape(T, k, N)
    # HIGHEST: a TPU's default f32 matmul rounds ``w`` to bf16, and a PWP
    # must be the exact sum of its pattern's weight rows.
    pwp = jnp.einsum("tqk,tkn->tqn", patterns.astype(w.dtype), wt,
                     precision=jax.lax.Precision.HIGHEST)
    zero = jnp.zeros((T, 1, N), w.dtype)
    return jnp.concatenate([pwp, zero], axis=1)


def quantize_pwp(pwp: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Beyond-paper: int8 PWP rows with per-(tile, pattern) scales.

    PWP entries are sums of ≤k weights, so their per-row dynamic range is
    narrow — int8 symmetric quantisation halves the dominant HBM stream of
    the L1 processor vs bf16 at ~0.4% RMS error. Returns (q8 (T,q+1,N) int8,
    scale (T,q+1) f32)."""
    scale = jnp.max(jnp.abs(pwp.astype(jnp.float32)), axis=-1) / 127.0 + 1e-12
    q8 = jnp.clip(jnp.round(pwp.astype(jnp.float32) / scale[..., None]),
                  -127, 127).astype(jnp.int8)
    return q8, scale
