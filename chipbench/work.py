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

The model-level count (``model_ops``) adds the attention products and the
LM head, which stay dense in both variants.
"""
from __future__ import annotations

import dataclasses


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


@dataclasses.dataclass(frozen=True)
class Shapes:
    """What the model's work depends on (from the configuration file)."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    spiking: dict | None = None     # {"timesteps", "q", "k"} or None

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   spiking=c.get("spiking"))

    def gemms(self) -> list[tuple[str, int, int]]:
        """(name, K, N) of one layer's weight GEMMs."""
        d, hd = self.d_model, self.head_dim
        return [("wq", d, self.heads * hd), ("wk", d, self.kv_heads * hd),
                ("wv", d, self.kv_heads * hd), ("wo", self.heads * hd, d),
                ("w1", d, self.d_ff), ("w3", d, self.d_ff), ("w2", self.d_ff, d)]


def layer_gemm_ops(s: Shapes, tokens: int) -> int:
    """Least operations of one layer's weight GEMMs over ``tokens`` tokens."""
    total = 0
    for _, K, N in s.gemms():
        if s.spiking:
            sp = s.spiking
            total += phi_gemm(sp["timesteps"] * tokens, K, N, k=sp["k"],
                              q=sp["q"], timesteps=sp["timesteps"])["ops"]
        else:
            total += dense_gemm(tokens, K, N)["ops"]
    return total


def attention_ops(s: Shapes, context: int) -> int:
    """Score and value products of one token attending to ``context``
    positions, over all layers (causal: only positions it may see)."""
    return 4 * s.layers * s.heads * s.head_dim * context


def model_ops(s: Shapes, *, prompt_lens=(), decode_contexts=()) -> int:
    """Least operations for prefilling prompts of ``prompt_lens`` tokens and
    decoding one token at each context length in ``decode_contexts``: the
    layers' GEMMs, attention over the visible positions, and the LM head
    (prefill produces one logits row per prompt, decode one per token)."""
    head = 2 * s.d_model * s.vocab
    ops = 0
    for p in prompt_lens:
        ops += s.layers * layer_gemm_ops(s, p)
        ops += 4 * s.layers * s.heads * s.head_dim * p * (p + 1) // 2
        ops += head
    n = len(decode_contexts)
    if n:
        ops += s.layers * layer_gemm_ops(s, 1) * n
        ops += sum(attention_ops(s, c) for c in decode_contexts)
        ops += head * n
    return ops
