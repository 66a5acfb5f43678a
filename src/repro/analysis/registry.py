"""Contract registry: one entry per Phi lowering, doubling as documentation
of the kernel surface.

Every impl name the execution policy can resolve (``dispatch.IMPLS`` /
``dispatch.ATTN_IMPLS``) must be covered by some entry — asserted at import
time, so a future lowering (the queued Prosperity L2 variant, say) cannot
ship without a contract. Each entry knows how to abstractly trace its
lowering over the canonical shape matrix and which Layer-1 checks apply:

  * grid/BlockSpec coverage (always, for Pallas lowerings)
  * wrapper logical-shape + pad-and-mask evidence (always)
  * exact-counter width (lowerings emitting the ``l2_nnz`` audit stream)
  * VMEM byte-model fidelity (lowerings gated by an ``ops._*_vmem_bytes``
    model), at the blocks the autotuner actually picks
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable

from repro.analysis.contracts import (
    ContractFinding,
    CounterSpec,
    PallasRecord,
    check_counters,
    check_coverage,
    check_logical_shape,
    check_padded_extent,
    check_vmem_model,
    jaxpr_dims,
    trace_abstract,
)


# ------------------------------------------------------------ shape matrix --
@dataclasses.dataclass(frozen=True)
class MatmulCase:
    name: str
    M: int
    K: int
    N: int
    T: int
    q: int

    @property
    def k(self) -> int:
        return self.K // self.T


@dataclasses.dataclass(frozen=True)
class AttnCase:
    name: str
    B: int
    S: int
    H: int
    D: int
    T: int
    qp: int
    kp: int


# Divisible base, a non-divisible M (exercises the pad-rows path in every
# matmul wrapper), and a large-K shape (the streaming kernel's territory).
MATMUL_CASES: tuple[MatmulCase, ...] = (
    MatmulCase("mm_base", M=256, K=256, N=256, T=16, q=16),
    MatmulCase("mm_tail", M=200, K=256, N=256, T=16, q=16),
    MatmulCase("mm_bigk", M=128, K=1024, N=256, T=16, q=16),
)

# Divisible base and a sequence length no block size divides (the PR-7
# flash-tail regression shape class).
ATTN_CASES: tuple[AttnCase, ...] = (
    AttnCase("attn_base", B=1, S=256, H=2, D=64, T=4, qp=8, kp=16),
    AttnCase("attn_tail", B=1, S=200, H=2, D=64, T=4, qp=8, kp=16),
)

PREFETCH_P_ACTIVE = 8   # gather-buffer size the prefetch entry traces with


@dataclasses.dataclass(frozen=True)
class LoweringContract:
    name: str
    impls: tuple[str, ...]          # dispatch impl ids this entry covers
    kind: str                       # "matmul" | "attention"
    check: Callable[..., list[ContractFinding]]


def _sds(shape, dtype=None):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, dtype or jnp.float32)


def _mm_avals(case: MatmulCase):
    return (_sds((case.M, case.K)),
            _sds((case.T, case.q, case.k)),
            _sds((case.T, case.q + 1, case.N)),
            _sds((case.K, case.N)))


def _attn_avals(case: AttnCase):
    qkv = _sds((case.B, case.S, case.H, case.D))
    return qkv, qkv, qkv, _sds((case.T, case.qp, case.kp))


def _nnz_counter(bound: Callable[[PallasRecord], int]) -> tuple[CounterSpec, ...]:
    return (CounterSpec(out_index=1, name="l2_nnz", bound=bound),)


def _mm_block_bound(rec: PallasRecord, K: int) -> int:
    """Residual entries one M-block can contribute: bm · K (every element of
    the activation block could be a ±1 residual)."""
    return int(rec.out_specs[0].block_shape[0]) * K


# ------------------------------------------------------------- matmul line --
def _check_fused(case: MatmulCase) -> list[ContractFinding]:
    from repro.kernels import ops

    bm, bn = ops.autotune_fused_blocks(case.M, case.K, case.N, case.q,
                                       case.T)
    a, pats, pwp, w = _mm_avals(case)
    (out, _nnz), recs = trace_abstract(
        lambda a_, p_, pw_, w_: ops.phi_fused(a_, p_, pw_, w_,
                                              block_m=bm, block_n=bn),
        a, pats, pwp, w)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, (case.M, case.N),
                            lowering="fused", case=case.name))
    for rec in recs:
        fs += check_coverage(rec, lowering="fused", case=case.name)
        fs += check_counters(
            rec, _nnz_counter(lambda r: _mm_block_bound(r, case.K)),
            lowering="fused", case=case.name)
        rbm = int(rec.out_specs[0].block_shape[0])
        rbn = int(rec.out_specs[0].block_shape[1])
        fs += check_vmem_model(
            rec, ops._fused_vmem_bytes(rbm, rbn, case.K, case.T, case.q),
            lowering="fused", case=case.name)
    return fs


def _check_fused_stream(case: MatmulCase) -> list[ContractFinding]:
    from repro.kernels import ops

    bm, bn, gt = ops.autotune_stream_blocks(case.M, case.K, case.N, case.q,
                                            case.T)
    a, pats, pwp, w = _mm_avals(case)
    (out, _nnz), recs = trace_abstract(
        lambda a_, p_, pw_, w_: ops.phi_fused_stream(
            a_, p_, pw_, w_, block_m=bm, block_n=bn, group_t=gt),
        a, pats, pwp, w)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, (case.M, case.N),
                            lowering="fused_stream", case=case.name))
    for rec in recs:
        fs += check_coverage(rec, lowering="fused_stream", case=case.name)
        fs += check_counters(
            rec, _nnz_counter(lambda r: _mm_block_bound(r, case.K)),
            lowering="fused_stream", case=case.name)
        rbm = int(rec.out_specs[0].block_shape[0])
        rbn = int(rec.out_specs[0].block_shape[1])
        fs += check_vmem_model(
            rec, ops._stream_vmem_bytes(rbm, rbn, case.K, case.T, case.q, gt),
            lowering="fused_stream", case=case.name)
    return fs


def _check_fused_prefetch(case: MatmulCase) -> list[ContractFinding]:
    from repro.kernels import ops

    p = min(PREFETCH_P_ACTIVE, case.q)
    bm, bn = ops.autotune_prefetch_blocks(case.M, case.K, case.N, case.q,
                                          case.T, p)
    a, pats, pwp, w = _mm_avals(case)
    (out, _nnz), recs = trace_abstract(
        lambda a_, p_, pw_, w_: ops.phi_fused_prefetch(
            a_, p_, pw_, w_, p_active=p, block_m=bm, block_n=bn),
        a, pats, pwp, w)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, (case.M, case.N),
                            lowering="fused_prefetch", case=case.name))
    for rec in recs:
        fs += check_coverage(rec, lowering="fused_prefetch", case=case.name)
        fs += check_counters(
            rec, _nnz_counter(lambda r: _mm_block_bound(r, case.K)),
            lowering="fused_prefetch", case=case.name)
        rbm = int(rec.out_specs[0].block_shape[0])
        rbn = int(rec.out_specs[0].block_shape[1])
        fs += check_vmem_model(
            rec, ops._prefetch_vmem_bytes(rbm, rbn, case.K, case.T,
                                          case.q, p),
            lowering="fused_prefetch", case=case.name)
    return fs


def _check_pallas3(case: MatmulCase) -> list[ContractFinding]:
    """The unfused matcher → L1 gather → L2 spmm pipeline ("pallas" impl).
    No byte model gates it (always-viable fallback), so the contract is
    coverage + logical shape."""
    from repro.kernels import ops

    a, pats, pwp, w = _mm_avals(case)
    out, recs = trace_abstract(
        lambda a_, w_, p_, pw_: ops.phi_matmul(a_, w_, p_, pw_,
                                               impl="pallas"),
        a, w, pats, pwp)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, (case.M, case.N),
                            lowering="pallas", case=case.name))
    for rec in recs:
        fs += check_coverage(rec, lowering="pallas", case=case.name)
    return fs


def _check_coo(case: MatmulCase) -> list[ContractFinding]:
    """Pure-XLA chunked gather/scatter lowering: no pallas calls; the
    contract is the logical output shape plus pad-and-mask evidence (rows
    are padded up to the chunk size, never floor-truncated)."""
    from repro.kernels import ops

    a, pats, pwp, w = _mm_avals(case)
    fn = lambda a_, w_, p_, pw_: ops.phi_matmul(a_, w_, p_, pw_, impl="coo")  # noqa: E731
    out, recs = trace_abstract(fn, a, w, pats, pwp)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, (case.M, case.N),
                            lowering="coo", case=case.name))
    if recs:
        fs.append(ContractFinding(
            "PHI-COV-GRID", "coo", case.name, "pallas",
            "the pure-XLA coo lowering must not launch Pallas kernels "
            "(it is the pjit-safe SPMD fallback)"))
    chunk = 2048  # PHI_CHUNK_ROWS default in _phi_matmul_coo_chunked
    # A call shorter than a chunk runs as one chunk of its own rows; the
    # pad-and-mask path shows on a call that spans chunks.
    m_tall = chunk + case.M
    if m_tall % chunk:
        padded = math.ceil(m_tall / chunk) * chunk
        dims = jaxpr_dims(fn, _sds((m_tall, case.K)), w, pats, pwp)
        fs += check_padded_extent(dims, {"chunk_rows": padded},
                                  lowering="coo", case=case.name)
    return fs


def _check_ref(case: MatmulCase) -> list[ContractFinding]:
    from repro.kernels import ops

    a, pats, pwp, w = _mm_avals(case)
    out, recs = trace_abstract(
        lambda a_, w_, p_, pw_: ops.phi_matmul(a_, w_, p_, pw_, impl="ref"),
        a, w, pats, pwp)
    return list(check_logical_shape(out, (case.M, case.N),
                                    lowering="ref", case=case.name))


# ---------------------------------------------------------- attention line --
def _check_phi_flash_pallas(case: AttnCase) -> list[ContractFinding]:
    from repro.kernels import ops

    bq, bkv = ops.autotune_attn_blocks(case.S, case.D, case.T, case.qp,
                                       case.kp)
    q, k, v, pats = _attn_avals(case)
    out, recs = trace_abstract(
        lambda q_, k_, v_, p_: ops.phi_flash_attention(
            q_, k_, v_, p_, impl="pallas", block_q=bq, block_kv=bkv),
        q, k, v, pats)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, q.shape,
                            lowering="phi_flash_pallas", case=case.name))
    for rec in recs:
        fs += check_coverage(rec, lowering="phi_flash_pallas", case=case.name)
        # per-program residual bound: every element of the padded K panel
        skv, d = rec.data_operands[1].shape[1], rec.data_operands[1].shape[2]
        fs += check_counters(
            rec, _nnz_counter(lambda r, s=skv, dd=d: s * dd),
            lowering="phi_flash_pallas", case=case.name)
        bq_eff = min(bq, case.S)
        bkv_eff = min(bkv, case.S)
        fs += check_vmem_model(
            rec, ops._attn_vmem_bytes(bq_eff, bkv_eff, case.S, case.D,
                                      case.T, case.qp, case.kp),
            lowering="phi_flash_pallas", case=case.name)
    return fs


def _check_phi_flash_xla(case: AttnCase) -> list[ContractFinding]:
    from repro.kernels import ops

    q, k, v, pats = _attn_avals(case)
    fn = lambda q_, k_, v_, p_: ops.phi_flash_attention(  # noqa: E731
        q_, k_, v_, p_, impl="xla", block_q=128, block_kv=128)
    out, recs = trace_abstract(fn, q, k, v, pats)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, q.shape,
                            lowering="phi_flash_xla", case=case.name))
    if recs:
        fs.append(ContractFinding(
            "PHI-COV-GRID", "phi_flash_xla", case.name, "pallas",
            "the pure-XLA phi_flash lowering must not launch Pallas kernels "
            "(it is the pjit-safe SPMD arm)"))
    if case.S % 128:
        padded = math.ceil(case.S / 128) * 128
        dims = jaxpr_dims(fn, q, k, v, pats)
        fs += check_padded_extent(dims, {"seq": padded},
                                  lowering="phi_flash_xla", case=case.name)
    return fs


def _check_flash(case: AttnCase) -> list[ContractFinding]:
    from repro.models import flash

    q, k, v, _ = _attn_avals(case)
    fn = lambda q_, k_, v_: flash.flash_attention(  # noqa: E731
        q_, k_, v_, block_q=128, block_kv=128)
    out, recs = trace_abstract(fn, q, k, v)
    fs: list[ContractFinding] = list(
        check_logical_shape(out, q.shape, lowering="flash", case=case.name))
    if case.S % 128:
        padded = math.ceil(case.S / 128) * 128
        dims = jaxpr_dims(fn, q, k, v)
        fs += check_padded_extent(dims, {"seq": padded},
                                  lowering="flash", case=case.name)
    return fs


# ---------------------------------------------------------------- registry --
CONTRACTS: tuple[LoweringContract, ...] = (
    LoweringContract("fused", ("fused",), "matmul", _check_fused),
    LoweringContract("fused_stream", ("fused_stream",), "matmul",
                     _check_fused_stream),
    LoweringContract("fused_prefetch", ("fused_prefetch",), "matmul",
                     _check_fused_prefetch),
    LoweringContract("pallas", ("pallas",), "matmul", _check_pallas3),
    LoweringContract("coo", ("coo",), "matmul", _check_coo),
    LoweringContract("ref", ("ref",), "matmul", _check_ref),
    LoweringContract("phi_flash_pallas", ("phi_flash",), "attention",
                     _check_phi_flash_pallas),
    LoweringContract("phi_flash_xla", ("phi_flash",), "attention",
                     _check_phi_flash_xla),
    LoweringContract("flash", ("flash",), "attention", _check_flash),
)


def _assert_complete() -> None:
    """Import-time completeness gate: every impl the dispatch policy can
    resolve must have a contract entry (ISSUE-8 satellite — a new lowering
    cannot ship unchecked)."""
    from repro.kernels.dispatch import ATTN_IMPLS, IMPLS

    covered = {impl for c in CONTRACTS for impl in c.impls}
    missing = (set(IMPLS) | set(ATTN_IMPLS)) - covered
    assert not missing, (
        f"dispatch impls {sorted(missing)} have no contract entry in "
        "repro.analysis.registry — add a LoweringContract (and shape-matrix "
        "coverage) before registering a new lowering")


_assert_complete()


def run_contracts(names: tuple[str, ...] | None = None
                  ) -> list[ContractFinding]:
    """Trace every registered lowering across its shape matrix and collect
    contract findings. ``names`` restricts to specific entries (tests)."""
    findings: list[ContractFinding] = []
    for contract in CONTRACTS:
        if names is not None and contract.name not in names:
            continue
        cases = MATMUL_CASES if contract.kind == "matmul" else ATTN_CASES
        for case in cases:
            findings.extend(contract.check(case))
    return findings
