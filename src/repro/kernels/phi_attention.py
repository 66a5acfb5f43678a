"""Phi-sparse flash attention: pattern-hierarchical score blocks inside the
online-softmax loop (paper Sec. 3 applied to the spiking-transformer hot
path).

The observation: a flash score block ``S = Qᵢ·Kⱼᵀ`` over *binary spike* K
rows is itself a Phi matmul with the K-block rows playing the activation
role and ``Qᵢᵀ`` playing the weight role. Each K row decomposes against the
calibrated pattern bank as ``k = pattern[idx] + residual`` (Hamming-argmin
matcher, strict better-than-bit-sparsity rule), so

    Sᵀ = K·Qᵢᵀ = onehot(idx)·(P·Qᵢᵀ)  +  residual·Qᵢᵀ
         └── L1: gathered pattern×Q products ──┘  └── L2: sparse ±1 COO ──┘

``P·Qᵢᵀ`` is the attention analogue of the PWP bank — computed once per
q-block (pre-gathered "pattern products"), after which every K row's L1
contribution is a one-hot gather and only the residual nnz pay MXU work.
Score-block FLOPs and modelled HBM bytes then scale with pattern coverage +
residual nnz instead of dense S² (see ``core.perfmodel.phi_attention_traffic``).

Exactness discipline matches the matmul line (``phi_fused.py``): one-hot
selections and ±1 residual entries make every partial product exact, so for
binary Q/K every partial sum is an exact small integer and **any**
contraction order recomposes the exact dense scores. Scale is applied after
the contraction (`models/flash.py` does the same), hence score blocks are
bitwise equal to the dense ``q·kᵀ`` and the XLA lowering — which reuses the
dense accumulator code verbatim — is bit-identical to ``flash_attention``.
The Pallas kernel keeps the same exact scores but owns its softmax
accumulator, so its output matches up to XLA fusion rounding (~1 ulp).

Two lowerings share one partition body (``phi_fused._partition_body``):

  * ``phi_flash_attention_xla`` — pure XLA; drives ``_flash_fwd_impl`` with a
    Phi ``score_fn``, so the online-softmax accumulator is *literally* the
    dense flash code. pjit-safe (SPMD regions) and the bitwise A/B anchor.
  * ``phi_flash_attention_pallas`` — fused Pallas kernel (grid over
    (B·H, q-blocks), K/V resident per program, interpret-safe off-TPU):
    match → L1 gather → L2 residual → online softmax without leaving VMEM,
    plus the residual-nnz audit counter the matmul kernels also emit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.phi_fused import _partition_body
from repro.models.flash import _flash_fwd_impl


# ------------------------------------------------------------ score block ---
def attn_score_block(kt, qi, patterns):
    """Phi-decomposed score block for one (batch, head): ``sᵀ = K·Qᵢᵀ``.

    kt (bkv, D) binary K rows, qi (bq, D), patterns (T, qp, kp) with
    T·kp ≤ D (a dense ragged tail covers D − T·kp, same contract as
    ``snn.models.phi_apply``). Returns ``(s (bq, bkv) f32, l2_nnz int32)``.
    Exact: every partial product is exact, so for binary inputs ``s``
    equals the dense ``qi @ ktᵀ`` bitwise.
    """
    T, qp, kp = patterns.shape
    bkv, bq = kt.shape[0], qi.shape[0]
    kt = kt.astype(jnp.float32)
    qi = qi.astype(jnp.float32)
    acc1 = jnp.zeros((bkv, bq), jnp.float32)
    acc2 = jnp.zeros((bkv, bq), jnp.float32)
    nnz = jnp.zeros((), jnp.int32)
    ones = jnp.ones((1, qp + 1), jnp.float32)
    for t in range(T):                                   # static unroll
        p = patterns[t].astype(jnp.float32)
        q_t = qi[:, t * kp:(t + 1) * kp]
        # attention "PWP": pattern × Qᵀ products, built once per q-block
        pwp_t = jnp.concatenate(
            [jnp.dot(p, q_t.T, preferred_element_type=jnp.float32),
             jnp.zeros((1, bq), jnp.float32)], axis=0)   # (qp+1, bq)
        acc1, acc2, nnz = _partition_body(
            kt[:, t * kp:(t + 1) * kp], p, pwp_t, ones, q_t.T,
            acc1, acc2, nnz, q=qp)
    s = acc1 + acc2                                      # (bkv, bq)
    used = T * kp
    if used < qi.shape[1]:                               # dense ragged tail
        s = s + jnp.dot(kt[:, used:], qi[:, used:].T,
                        preferred_element_type=jnp.float32)
    return s.T, nnz


# ------------------------------------------------------------- XLA fallback ---
def phi_flash_attention_xla(q, k, v, patterns, *, causal=False, window=None,
                            chunk=None, block_q=128, block_kv=128):
    """Pure-XLA Phi flash attention. q/k/v (B, S, H, D), binary spike Q/K.

    Reuses ``models.flash._flash_fwd_impl`` with a Phi ``score_fn`` — same
    padding, masking and online-softmax accumulator as the dense lowering,
    so the output is bit-identical to ``flash_attention`` with the same
    blocks. pjit-safe (no pallas_call), which is why SPMD regions resolve
    to this path.
    """
    patterns = jnp.asarray(patterns, jnp.float32)

    def score_fn(qi, kj):                                # (B,H,bq/bkv,D)
        f = lambda kb, qb: attn_score_block(kb, qb, patterns)[0]  # noqa: E731
        return jax.vmap(jax.vmap(f))(kj, qi)

    out, _ = _flash_fwd_impl(q, k, v, causal, window, chunk, block_q,
                             block_kv, score_fn=score_fn)
    return out


# ------------------------------------------------------------ Pallas kernel ---
def _attn_kernel(q_ref, k_ref, v_ref, p_ref, o_ref, nnz_ref, *, s_orig: int,
                 block_kv: int, causal: bool, window, chunk, scale: float):
    """One (batch·head, q-block) program: Phi-decomposed score blocks feeding
    the online-softmax accumulator, all resident in VMEM."""
    bq, D = q_ref.shape[1], q_ref.shape[2]
    skv = k_ref.shape[1]
    nkv = skv // block_kv
    iq = pl.program_id(1)
    qi = q_ref[0].astype(jnp.float32)                    # (bq, D)
    pats = p_ref[...]
    # 2D iota only — 1D iota does not lower on TPU
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_kv), 0)
    m = jnp.full((bq,), -jnp.inf, jnp.float32)
    den = jnp.zeros((bq,), jnp.float32)
    acc = jnp.zeros((bq, D), jnp.float32)
    nnz = jnp.zeros((), jnp.int32)
    for jk in range(nkv):                                # static unroll
        kj = k_ref[0, jk * block_kv:(jk + 1) * block_kv].astype(jnp.float32)
        vj = v_ref[0, jk * block_kv:(jk + 1) * block_kv].astype(jnp.float32)
        s_int, nnz_b = attn_score_block(kj, qi, pats)
        nnz = nnz + nnz_b
        s = s_int * scale
        kpos = jk * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_kv), 1)
        valid = kpos < s_orig                            # padded keys
        if causal:
            valid &= kpos <= qpos
        if window is not None:
            valid &= kpos > qpos - window
        if chunk is not None:
            valid &= (kpos // chunk) == (qpos // chunk)
        s = jnp.where(valid, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(jnp.isnan(p), 0.0, p)              # fully-masked rows
        corr = jnp.exp(m - m_new)
        corr = jnp.where(jnp.isnan(corr), 0.0, corr)
        den = den * corr + p.sum(-1)
        acc = acc * corr[:, None] + jnp.dot(
            p, vj, preferred_element_type=jnp.float32)
        m = m_new
    o_ref[0] = (acc / jnp.maximum(den, 1e-30)[:, None]).astype(o_ref.dtype)
    nnz_ref[...] = jnp.full(nnz_ref.shape, nnz, jnp.int32)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "chunk", "block_q", "block_kv", "interpret"))
def phi_flash_attention_pallas(q, k, v, patterns, *, causal=False,
                               window=None, chunk=None, block_q=128,
                               block_kv=128, interpret=False):
    """Fused Pallas lowering. q/k/v (B, S, H, D) binary spike Q/K.

    Grid (B·H, num_q_blocks); each program holds its q-block plus the full
    (padded) K/V panels and the pattern bank in VMEM — the
    ``ops._attn_vmem_bytes`` model gates shapes where that does not fit.
    Returns ``(out (B, S, H, D), l2_nnz (B·H, num_q_blocks) int32)`` — the
    same residual-nnz audit stream the fused matmul kernels emit.
    """
    B, S, H, D = q.shape
    scale = D ** -0.5
    bq, bkv = min(block_q, S), min(block_kv, S)
    sq, skv = S + (-S) % bq, S + (-S) % bkv
    nq = sq // bq

    def fold(x, to):
        x = jnp.moveaxis(x, 2, 1).reshape(B * H, S, D).astype(jnp.float32)
        return jnp.pad(x, ((0, 0), (0, to - S), (0, 0)))

    qf, kf, vf = fold(q, sq), fold(k, skv), fold(v, skv)
    pats = jnp.asarray(patterns, jnp.float32)
    T, qp, kp = pats.shape
    kernel = functools.partial(_attn_kernel, s_orig=S, block_kv=bkv,
                               causal=causal, window=window, chunk=chunk,
                               scale=scale)
    grid = (B * H, nq)
    out_shape = [
        jax.ShapeDtypeStruct((B * H, sq, D), jnp.float32),
        # one int32 (8, 128) tile per program: the TPU's block tiling
        jax.ShapeDtypeStruct((B * H * 8, nq * 128), jnp.int32),
    ]
    in_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, skv, D), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, skv, D), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((T, qp, kp), lambda b, i: (0, 0, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        pl.BlockSpec((8, 128), lambda b, i: (b, i)),
    ]
    o, nnz = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(qf, kf, vf, pats)
    o = o[:, :S].reshape(B, H, S, D)
    return jnp.moveaxis(o, 1, 2).astype(q.dtype), nnz[::8, ::128]
