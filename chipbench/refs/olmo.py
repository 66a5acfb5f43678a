"""Plain reference of an OLMo-style decoder: a float32 ``jax.numpy`` forward
written from the architecture's equations (the interface is set out in
``chipbench/refs/__init__.py``).

Equations (OLMo, arXiv:2402.00838, as configured): token embedding; per
layer, non-parametric LayerNorm (eps 1e-5), Q/K/V projections, rotary
embedding on the two halves of each head (theta from the configuration),
causal softmax attention scaled by 1/sqrt(head_dim), output projection and
residual; non-parametric LayerNorm, SwiGLU MLP (silu(x W1) * (x W3)) W2 and
residual; final LayerNorm and the LM head (the embedding's transpose where
the configuration ties them, as the weights hold it). Every weight GEMM is
``reference.gemm``: spiking where the configuration says so, in int8 for
the control.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, work
from chipbench.reference import padded_len  # noqa: F401  (the interface's)

LN_EPS = 1e-5
STACK = "decoder/stack/p0/"


@dataclasses.dataclass(frozen=True)
class Arch:
    """What the forward and the work count need from the configuration."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    timesteps: int = 0          # 0: not spiking
    lif_decay: float = 0.5
    lif_threshold: float = 1.0
    phi_q: int = 0              # Phi patterns per tile, and the tile width
    phi_k: int = 0

    def gemms(self) -> list[tuple[str, int, int]]:
        """(name, K, N) of one layer's weight GEMMs."""
        d, hd = self.d_model, self.head_dim
        return [("wq", d, self.heads * hd), ("wk", d, self.kv_heads * hd),
                ("wv", d, self.kv_heads * hd), ("wo", self.heads * hd, d),
                ("w1", d, self.d_ff), ("w3", d, self.d_ff), ("w2", self.d_ff, d)]


def arch(c: dict) -> Arch:
    sp = c.get("spiking") or {}
    heads = c["num_attention_heads"]
    return Arch(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                heads=heads, kv_heads=c["num_key_value_heads"],
                head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                rope_theta=float(c["rope_theta"]),
                timesteps=int(sp.get("timesteps", 0)),
                lif_decay=float(sp.get("lif_decay", 0.5)),
                lif_threshold=float(sp.get("lif_threshold", 1.0)),
                phi_q=int(sp.get("q", 0)), phi_k=int(sp.get("k", 0)))


def leaves(a: Arch) -> dict[str, tuple[tuple[int, ...], str, None]]:
    L, d, hd, ff = a.layers, a.d_model, a.head_dim, a.d_ff
    p = STACK
    shapes = {
        "embed": (a.vocab, d), "head": (d, a.vocab),
        p + "wq": (L, d, a.heads * hd), p + "wk": (L, d, a.kv_heads * hd),
        p + "wv": (L, d, a.kv_heads * hd), p + "wo": (L, a.heads * hd, d),
        p + "mlp/w1": (L, d, ff), p + "mlp/w3": (L, d, ff),
        p + "mlp/w2": (L, ff, d),
    }
    return {path: (shape, "float32", None) for path, shape in shapes.items()}


def program_fields(c: dict) -> dict:
    return {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["intermediate_size"], "vocab": c["vocab_size"],
            "rope_theta": c["rope_theta"], "norm": "nonparam_ln",
            "mlp_type": "swiglu", "qkv_bias": c["attention_bias"],
            "param_dtype": jnp.dtype(c["param_dtype"]),
            "compute_dtype": jnp.dtype(c["compute_dtype"])}


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)


def _layer(a: Arch, quant, x, lw):
    S = x.shape[0]
    gemm = partial(reference.gemm, a, quant=quant)
    h = _ln(x)
    q = gemm(h, lw["wq"]).reshape(S, a.heads, a.head_dim)
    k = gemm(h, lw["wk"]).reshape(S, a.kv_heads, a.head_dim)
    v = gemm(h, lw["wv"]).reshape(S, a.kv_heads, a.head_dim)
    q, k = reference.rope(q, a.rope_theta), reference.rope(k, a.rope_theta)
    rep = a.heads // a.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * a.head_dim ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision="highest").reshape(S, -1)
    x = x + gemm(o, lw["wo"])
    h = _ln(x)
    m = jax.nn.silu(gemm(h, lw["w1"])) * gemm(h, lw["w3"])
    return x + gemm(m, lw["w2"]), None


@partial(jax.jit, static_argnums=(0, 1))
def forward(a: Arch, quant: str | None, w: dict, tokens):
    """Logits (S, vocab) in float32 of one sequence ``tokens`` (S,)."""
    stack = {n: w[STACK + n] for n in ("wq", "wk", "wv", "wo")}
    stack.update({n: w[STACK + "mlp/" + n] for n in ("w1", "w2", "w3")})
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(partial(_layer, a, quant), x, stack)
    return reference.matmul(_ln(x), w["head"], quant)


def gaps(a: Arch, draw, prompt: np.ndarray, served: list[int],
         quant: str | None = None) -> np.ndarray:
    w = draw(leaves(a))
    return reference.gaps(lambda qt, tokens: forward(a, qt, w, tokens),
                          prompt, served, quant)


def model_ops(a: Arch, *, prompt_lens=(), decode_contexts=()) -> int:
    """The layers' weight GEMMs, attention over the visible positions
    (causal), and the LM head: prefill produces one logits row per prompt,
    decode one per token."""
    phi = (a.timesteps, a.phi_q, a.phi_k) if a.timesteps else None

    def layer_gemms(tokens):
        return sum(work.gemm_ops(tokens, K, N, phi) for _, K, N in a.gemms())

    attn = 4 * a.layers * a.heads * a.head_dim      # per visible position
    head = 2 * a.d_model * a.vocab
    ops = 0
    for p in prompt_lens:
        ops += a.layers * layer_gemms(p) + attn * p * (p + 1) // 2 + head
    n = len(decode_contexts)
    if n:
        ops += a.layers * layer_gemms(1) * n + attn * sum(decode_contexts) + head * n
    return ops
