"""Plain reference of the served model: a float32 ``jax.numpy`` forward of
an OLMo-style decoder, written from the architecture's equations and
importing nothing of the program under test.

Equations (OLMo, arXiv:2402.00838, as configured): token embedding; per
layer, non-parametric LayerNorm (eps 1e-5), Q/K/V projections, rotary
embedding on the two halves of each head (theta from the configuration),
causal softmax attention scaled by 1/sqrt(head_dim), output projection and
residual; non-parametric LayerNorm, SwiGLU MLP (silu(x W1) * (x W3)) W2 and
residual; final LayerNorm and the LM head (the embedding's transpose where
the configuration ties them, as the weights hold it). All matmuls at
``precision="highest"``.

Spiking variant (the configuration's ``spiking`` block): every weight GEMM
input is rate-coded into ``timesteps`` binary spike trains by a leaky
integrate-and-fire neuron (v <- decay*v + x; spike where v >= threshold;
hard reset), each train is multiplied by W densely, and the output is the
mean over timesteps times 2*threshold. The Phi decomposition is exact, so a
dense product of the same spikes is its reference.

Control (``quant="int8"``): the same forward with every weight matmul in
int8: activations scaled per row, weights per output column, symmetric,
products exact and rescaled in float32. Spikes are exact in int8.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5


@dataclasses.dataclass(frozen=True)
class Arch:
    """What the forward needs from the configuration file."""

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

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        sp = c.get("spiking") or {}
        heads = c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=heads, kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or c["hidden_size"] // heads,
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   rope_theta=float(c["rope_theta"]),
                   timesteps=int(sp.get("timesteps", 0)),
                   lif_decay=float(sp.get("lif_decay", 0.5)),
                   lif_threshold=float(sp.get("lif_threshold", 1.0)))


def weight_leaves(a: Arch) -> dict[str, tuple[tuple[int, ...], str]]:
    """{path: (shape, dtype)} of the weights, under the same paths the
    harness uses for the served model's parameter tree."""
    L, d, hd, ff = a.layers, a.d_model, a.head_dim, a.d_ff
    p = "decoder/stack/p0/"
    f32 = "float32"
    return {
        "embed": ((a.vocab, d), f32), "head": ((d, a.vocab), f32),
        p + "wq": ((L, d, a.heads * hd), f32),
        p + "wk": ((L, d, a.kv_heads * hd), f32),
        p + "wv": ((L, d, a.kv_heads * hd), f32),
        p + "wo": ((L, a.heads * hd, d), f32),
        p + "mlp/w1": ((L, d, ff), f32), p + "mlp/w3": ((L, d, ff), f32),
        p + "mlp/w2": ((L, ff, d), f32),
    }


def _ln(x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS)


def _quant_rows(x, axis):
    """Symmetric int8 quantisation along ``axis`` (absmax / 127), returned
    dequantised in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _matmul(x, w, quant: str | None):
    if quant == "int8":
        x = _quant_rows(x, -1)
        w = _quant_rows(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def _gemm(a: Arch, x, w, quant):
    """A weight GEMM, spiking when the architecture says so."""
    if not a.timesteps:
        return _matmul(x, w, quant)
    v = jnp.zeros_like(x)
    out = 0.0
    for _ in range(a.timesteps):
        v = v * a.lif_decay + x
        s = (v >= a.lif_threshold).astype(jnp.float32)
        v = v * (1.0 - s)
        out = out + _matmul(s, w, quant)
    return out / a.timesteps * (2.0 * a.lif_threshold)


def _rope(x, theta: float):
    """x (S, H, D): rotate the two halves by position-dependent angles."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(a: Arch, quant, x, lw):
    S = x.shape[0]
    h = _ln(x)
    q = _gemm(a, h, lw["wq"], quant).reshape(S, a.heads, a.head_dim)
    k = _gemm(a, h, lw["wk"], quant).reshape(S, a.kv_heads, a.head_dim)
    v = _gemm(a, h, lw["wv"], quant).reshape(S, a.kv_heads, a.head_dim)
    q, k = _rope(q, a.rope_theta), _rope(k, a.rope_theta)
    rep = a.heads // a.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") * a.head_dim ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                   precision="highest").reshape(S, -1)
    x = x + _gemm(a, o, lw["wo"], quant)
    h = _ln(x)
    m = jax.nn.silu(_gemm(a, h, lw["w1"], quant)) * _gemm(a, h, lw["w3"], quant)
    return x + _gemm(a, m, lw["w2"], quant), None


@partial(jax.jit, static_argnums=(0, 1))
def forward(a: Arch, quant: str | None, w: dict, tokens):
    """Logits (S, vocab) in float32 of one sequence ``tokens`` (S,)."""
    p = "decoder/stack/p0/"
    stack = {n: w[p + n] for n in ("wq", "wk", "wv", "wo")}
    stack.update({n: w[p + "mlp/" + n] for n in ("w1", "w2", "w3")})
    x = w["embed"][tokens]
    x, _ = jax.lax.scan(partial(_layer, a, quant), x, stack)
    return _matmul(_ln(x), w["head"], quant)


def padded_len(n: int, floor: int = 256) -> int:
    """Sequence length a forward of ``n`` tokens is run at: the next power
    of two, at least ``floor`` (the model is causal, so the pad tail changes
    nothing before it, and few lengths means few compiles)."""
    b = floor
    while b < n:
        b *= 2
    return b


def gaps(a: Arch, w: dict, prompt: np.ndarray, served: list[int],
         quant: str | None = None) -> np.ndarray:
    """For each served token, how far its logit lies below the reference's
    best at its position. With ``quant`` set, the gap is that of the token
    the control puts first there instead (the control's reading)."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)]).astype(np.int32)
    n = len(seq)
    padded = np.zeros(padded_len(n), np.int32)
    padded[:n] = seq
    pos = np.arange(len(prompt) - 1, n)
    ref = forward(a, None, w, jnp.asarray(padded))[pos]
    if quant is None:
        pick = jnp.asarray(np.asarray(served, np.int32))
    else:
        pick = forward(a, quant, w, jnp.asarray(padded))[pos].argmax(-1)
    took = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return np.asarray(ref.max(-1) - took)
