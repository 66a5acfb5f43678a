"""Operations and bytes that the served model must do, from shapes alone.

Every count here is a lower bound on what any exact lowering has to do, so a
share of a roofline or of a peak computed from it cannot pass 100%:

* A dense GEMM (M, K) x (K, N) needs 2*M*K*N operations.
* A spiking GEMM under the Phi decomposition with k-wide patterns needs, per
  row and per K-tile of k columns, one PWP row added to the output: M*(K/k)*N
  additions (L1). The L2 residual adds more, but how much depends on the data,
  so the bound leaves it out. It is never the dense 2*M*K*N: Phi's L1 does
  about k times fewer operations than the dense product.
* A spiking GEMM has to read the smaller of W and the PWP bank
  ((K/k) * (q+1) * N entries), the spikes (one bit each) and write its output
  once per token after rate decoding (M/T rows at 2 bytes, bf16).

The model-level count of each architecture (``model_ops`` of its module in
``chipbench/refs/``) adds what its layers do besides the weight GEMMs, such
as the attention products and the LM head, which stay dense when spiking.
"""
from __future__ import annotations


def dense_gemm(M: int, K: int, N: int, weight_bytes: int = 4) -> dict:
    """Least work of a dense GEMM with a (K, N) weight of ``weight_bytes``
    per entry, activations and output at 2 bytes (bf16)."""
    return {"ops": 2 * M * K * N,
            "bytes": K * N * weight_bytes + 2 * M * K + 2 * M * N}


def phi_gemm(M: int, K: int, N: int, *, k: int, q: int, timesteps: int,
             weight_bytes: int = 4) -> dict:
    """Least work of one spiking Phi GEMM of ``M`` spike rows (timesteps x
    tokens), as the module docstring sets out."""
    if K % k:
        raise ValueError(f"K={K} is not a multiple of the pattern width {k}")
    tiles = K // k
    ops = M * tiles * N
    w = K * N * weight_bytes
    bank = tiles * (q + 1) * N * weight_bytes
    spikes = M * K // 8
    out = 2 * (M // timesteps) * N
    return {"ops": ops, "bytes": min(w, bank) + spikes + out}


def gemm_ops(tokens: int, K: int, N: int, phi: tuple[int, int, int] | None = None
             ) -> int:
    """Least operations of one weight GEMM over ``tokens`` tokens: dense,
    or with ``phi`` = (timesteps, q, k) spiking under Phi, on timesteps x
    tokens spike rows."""
    if phi is None:
        return dense_gemm(tokens, K, N)["ops"]
    t, q, k = phi
    return phi_gemm(t * tokens, K, N, k=k, q=q, timesteps=t)["ops"]
