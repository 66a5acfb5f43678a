"""Plain reference of a Granite 4.0-H decoder (``granitemoehybrid`` without
experts): a float32 ``jax.numpy`` forward written from the architecture's
equations (the interface is set out in ``chipbench/refs/__init__.py``).

Equations (HF ``modeling_granitemoehybrid``, as configured): token
embedding times ``embedding_multiplier``; per layer, as ``layer_types``
says, a Mamba-2 or an attention mixer, then the MLP, each on the RMSNorm
(eps ``rms_norm_eps``, with gain) of the residual stream, and each output
added to it times ``residual_multiplier``; final RMSNorm, the LM head (the
embedding's transpose where the configuration ties them, as the weights
hold it), logits divided by ``logits_scaling``.

* Mamba-2 (arXiv:2405.21060, one group): projections z, x, B, C and dt; a
  causal depthwise conv of width ``mamba_d_conv`` with bias, then silu, on
  x, B and C; dt = softplus(dt + dt_bias), A = -exp(A_log); the plain
  per-token recurrence h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,
  y_t = h_t C_t + D x_t; then the gated norm RMSNorm(y * silu(z)) and the
  output projection.
* Attention: grouped-query, no positional encoding
  (``position_embedding_type`` "nope"), causal softmax of q k^T scaled by
  ``attention_multiplier``.
* MLP (``shared_intermediate_size``; no experts): (silu(x W1) * (x W3)) W2.

Departures from the HF code, none of which changes the mathematics: the
fused in_proj is held as one weight per quantity (z, x, B, C, dt) and the
MLP's fused input_linear as W1 and W3 (the program's parameter tree); the
conv taps are indexed oldest first (tap i multiplies x[t - (k-1) + i]);
the recurrence replaces HF's chunked scan. Every matmul runs in float32 at
``highest``, every weight GEMM through ``reference.gemm`` (int8 for the
control). The weights are drawn once for all the sampled requests (6.8 GB
in bf16 at full size, beside nothing else once the program's state is
freed): the layers alternate between three stacked runs, so a draw per run
would draw thirteen times a request.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference, work
from chipbench.reference import padded_len  # noqa: F401  (the interface's)

HI = "highest"


@dataclasses.dataclass(frozen=True)
class Arch:
    """What the forward and the work counts need from the configuration."""

    layer_types: tuple[str, ...]
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    param_dtype: str
    compute_dtype: str
    timesteps: int = 0          # reference.gemm's: not spiking

    @property
    def inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    def runs(self) -> list[tuple[str, int]]:
        """(kind, length) of the runs of same-kind layers in one period (the
        shortest that tiles ``layer_types``)."""
        t = self.layer_types
        period = next(p for p in range(1, len(t) + 1)
                      if len(t) % p == 0 and t == t[:p] * (len(t) // p))
        out: list[list] = []
        for k in t[:period]:
            if out and out[-1][0] == k:
                out[-1][1] += 1
            else:
                out.append([k, 1])
        return [(k, n) for k, n in out]

    def where(self) -> list[tuple[str, int]]:
        """(run name, index in the run's stack) of each layer in order."""
        runs = self.runs()
        period = sum(n for _, n in runs)
        out = []
        for layer in range(len(self.layer_types)):
            t, o = divmod(layer, period)
            start = 0
            for i, (_, n) in enumerate(runs):
                if o < start + n:
                    out.append((f"r{i}", t * n + o - start))
                    break
                start += n
        return out

    def gemms(self, kind: str) -> list[tuple[int, int]]:
        """(K, N) of a layer's weight GEMMs, its MLP's included."""
        d, hd = self.d_model, self.head_dim
        mlp = [(d, self.d_ff), (d, self.d_ff), (self.d_ff, d)]
        if kind == "mamba":
            return [(d, self.inner), (d, self.inner), (d, self.ssm_state),
                    (d, self.ssm_state), (d, self.ssm_heads),
                    (self.inner, d)] + mlp
        return [(d, self.heads * hd), (d, self.kv_heads * hd),
                (d, self.kv_heads * hd), (self.heads * hd, d)] + mlp


def arch(c: dict) -> Arch:
    if c.get("num_local_experts") or c.get("mamba_n_groups", 1) != 1:
        raise ValueError("refs/granite.py has no experts and one Mamba-2 group")
    if c["position_embedding_type"] != "nope":
        raise ValueError("refs/granite.py has no positional encoding")
    heads, d = c["num_attention_heads"], c["hidden_size"]
    a = Arch(layer_types=tuple(c["layer_types"]), d_model=d, heads=heads,
             kv_heads=c["num_key_value_heads"], head_dim=d // heads,
             d_ff=c["shared_intermediate_size"], vocab=c["vocab_size"],
             ssm_heads=c["mamba_n_heads"], ssm_head_dim=c["mamba_d_head"],
             ssm_state=c["mamba_d_state"], conv=c["mamba_d_conv"],
             eps=float(c["rms_norm_eps"]),
             embedding_multiplier=float(c["embedding_multiplier"]),
             residual_multiplier=float(c["residual_multiplier"]),
             attention_multiplier=float(c["attention_multiplier"]),
             logits_scaling=float(c["logits_scaling"]),
             param_dtype=c["param_dtype"], compute_dtype=c["compute_dtype"])
    if a.inner != c["mamba_expand"] * d or len(a.layer_types) != c["num_hidden_layers"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand x "
                         "hidden_size, and layer_types one per layer")
    return a


def leaves(a: Arch) -> dict[str, tuple]:
    """Every weight under the program's paths: run ``r<i>`` of one period
    stacks its layers of every period on axis 0, period major."""
    d, hd, ff, pt = a.d_model, a.head_dim, a.d_ff, a.param_dtype
    inner, N, H, kc = a.inner, a.ssm_state, a.ssm_heads, a.conv
    runs = a.runs()
    periods = len(a.layer_types) // sum(n for _, n in runs)
    # The embedding is drawn at 0.002, not 0.02: at 0.02, times the
    # embedding multiplier and read back through the tied head, a token's
    # own logit stands above every other, the model only repeats its input,
    # and the int8 control served the reference's best token at every
    # sampled position (on a TPU v5e, 3 seeds of 4): no check.
    out = {"embed": ((a.vocab, d), pt, 0.002), "head": ((d, a.vocab), pt, None),
           "ln_f/w": ((d,), "float32", "ones")}
    for i, (kind, n) in enumerate(runs):
        L, p = periods * n, f"decoder/r{i}/"
        out.update({p + "ln1/w": ((L, d), "float32", "ones"),
                    p + "ln2/w": ((L, d), "float32", "ones"),
                    p + "mlp/w1": ((L, d, ff), pt, None),
                    p + "mlp/w3": ((L, d, ff), pt, None),
                    p + "mlp/w2": ((L, ff, d), pt, None)})
        if kind == "attention":
            out.update({p + "wq": ((L, d, a.heads * hd), pt, None),
                        p + "wk": ((L, d, a.kv_heads * hd), pt, None),
                        p + "wv": ((L, d, a.kv_heads * hd), pt, None),
                        p + "wo": ((L, a.heads * hd, d), pt, None)})
            continue
        m = p + "mamba/"
        out.update({m + "wz": ((L, d, inner), pt, None),
                    m + "wx": ((L, d, inner), pt, None),
                    m + "wB": ((L, d, N), pt, None),
                    m + "wC": ((L, d, N), pt, None),
                    m + "wdt": ((L, d, H), pt, None),
                    m + "conv_x": ((L, kc, inner), pt, None),
                    m + "conv_B": ((L, kc, N), pt, None),
                    m + "conv_C": ((L, kc, N), pt, None),
                    m + "conv_x_bias": ((L, inner), pt, 0.25),
                    m + "conv_B_bias": ((L, N), pt, 0.25),
                    m + "conv_C_bias": ((L, N), pt, 0.25),
                    m + "A_log": ((L, H), "float32", 1.0),
                    m + "D": ((L, H), "float32", 1.0),
                    # a sixth of the heads below -3: dt < 0.05, so they
                    # hold their state over tens of tokens
                    m + "dt_bias": ((L, H), "float32", 3.0),
                    m + "norm_w": ((L, inner), pt, "ones"),
                    m + "wo": ((L, inner, d), pt, None)})
    return out


def program_fields(c: dict) -> dict:
    return {"n_layers": c["num_hidden_layers"], "d_model": c["hidden_size"],
            "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"],
            "d_ff": c["shared_intermediate_size"], "vocab": c["vocab_size"],
            "layer_kinds": tuple(c["layer_types"]), "position": "none",
            "attention_multiplier": c["attention_multiplier"],
            "embedding_multiplier": c["embedding_multiplier"],
            "residual_multiplier": c["residual_multiplier"],
            "logits_scaling": c["logits_scaling"], "norm_eps": c["rms_norm_eps"],
            "ssm_heads": c["mamba_n_heads"], "ssm_headdim": c["mamba_d_head"],
            "ssm_state": c["mamba_d_state"], "ssm_expand": c["mamba_expand"],
            "conv_kernel": c["mamba_d_conv"], "ssm_chunk": c["mamba_chunk_size"],
            "norm": "rmsnorm", "mlp_type": "swiglu",
            "qkv_bias": c["attention_bias"],
            "param_dtype": jnp.dtype(c["param_dtype"]),
            "compute_dtype": jnp.dtype(c["compute_dtype"])}


def _rmsnorm(a: Arch, x, w):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + a.eps) * w


def _conv(u, taps, bias):
    """Causal depthwise conv with bias: u (S, C), taps (k, C)."""
    k, S = taps.shape[0], u.shape[0]
    up = jnp.pad(u, ((k - 1, 0), (0, 0)))
    return sum(up[i:i + S] * taps[i] for i in range(k)) + bias


def _mamba(a: Arch, quant, x, w):
    gemm = partial(reference.gemm, a, quant=quant)
    S, H, P, N = x.shape[0], a.ssm_heads, a.ssm_head_dim, a.ssm_state
    z, xs, B, C = (gemm(x, w[n]) for n in ("wz", "wx", "wB", "wC"))
    dt = jax.nn.softplus(gemm(x, w["wdt"]) + w["dt_bias"])
    xs, B, C = (jax.nn.silu(_conv(u, w[n], w[n + "_bias"]))
                for u, n in ((xs, "conv_x"), (B, "conv_B"), (C, "conv_C")))
    A = -jnp.exp(w["A_log"])
    xh = xs.reshape(S, H, P)

    def step(h, t):
        x_t, B_t, C_t, dt_t = t
        h = h * jnp.exp(dt_t * A)[:, None, None] + \
            (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, C_t, precision=HI)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32), (xh, B, C, dt))
    y = (y + w["D"][:, None] * xh).reshape(S, H * P)
    return gemm(_rmsnorm(a, y * jax.nn.silu(z), w["norm_w"]), w["wo"])


def _attention(a: Arch, quant, x, w):
    gemm = partial(reference.gemm, a, quant=quant)
    S, hd = x.shape[0], a.head_dim
    q = gemm(x, w["wq"]).reshape(S, a.heads, hd)
    k = gemm(x, w["wk"]).reshape(S, a.kv_heads, hd)
    v = gemm(x, w["wv"]).reshape(S, a.kv_heads, hd)
    rep = a.heads // a.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * a.attention_multiplier
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v, precision=HI)
    return gemm(o.reshape(S, -1), w["wo"])


@partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(a: Arch, quant, kind: str, x, run: dict, j):
    """Layer ``j`` of a run's stacked weights ``run`` on the stream x (S, d)."""
    gemm = partial(reference.gemm, a, quant=quant)
    w = {n: v[j].astype(jnp.float32) for n, v in run.items()}
    mixer = _mamba if kind == "mamba" else _attention
    r = a.residual_multiplier
    x = x + r * mixer(a, quant, _rmsnorm(a, x, w["ln1/w"]), w)
    h = _rmsnorm(a, x, w["ln2/w"])
    return x + r * gemm(jax.nn.silu(gemm(h, w["mlp/w1"])) * gemm(h, w["mlp/w3"]),
                        w["mlp/w2"])


def forward(a: Arch, quant: str | None, w: dict, tokens):
    """Logits (S, vocab) in float32 of one sequence ``tokens`` (S,)."""
    x = w["embed"][tokens].astype(jnp.float32) * a.embedding_multiplier
    kinds = {f"r{i}": k for i, (k, _) in enumerate(a.runs())}
    for name, j in a.where():
        prefix = f"decoder/{name}/"
        run = {p[len(prefix):].removeprefix("mamba/"): v for p, v in w.items()
               if p.startswith(prefix)}
        x = _layer(a, quant, kinds[name], x, run, j)
    x = _rmsnorm(a, x, w["ln_f/w"])
    return reference.matmul(x, w["head"].astype(jnp.float32), quant) / a.logits_scaling


def gaps(a: Arch, draw, prompt: np.ndarray, served: list[int],
         quant: str | None = None) -> np.ndarray:
    w = draw(leaves(a))
    return reference.gaps(lambda qt, tokens: forward(a, qt, w, tokens),
                          prompt, served, quant)


def model_ops(a: Arch, *, prompt_lens=(), decode_contexts=()) -> int:
    """The layers' weight GEMMs, each Mamba-2 layer's conv and state
    products (2 operations for the update x_t B_t^T into h and 2 for the
    read-out h C_t, per state entry and token: the recurrence's count,
    below the chunked form's), attention over the visible positions
    (causal), and the LM head: prefill produces one logits row per prompt,
    decode one per token."""
    per_token = sum(work.gemm_ops(1, K, N) for kind in a.layer_types
                    for K, N in a.gemms(kind))
    n_mamba = a.layer_types.count("mamba")
    per_token += n_mamba * (4 * a.inner * a.ssm_state
                            + 2 * a.conv * (a.inner + 2 * a.ssm_state))
    attn = 4 * a.layer_types.count("attention") * a.heads * a.head_dim
    head = 2 * a.d_model * a.vocab
    ops = 0
    for p in prompt_lens:
        ops += per_token * p + attn * p * (p + 1) // 2 + head
    n = len(decode_contexts)
    if n:
        ops += per_token * n + attn * sum(decode_contexts) + head * n
    return ops


def decode_bytes(a: Arch, *, weights: int, state_bytes: int, kv_tokens: int) -> int:
    """Least bytes of one decode step: every weight read once at
    ``weights`` bytes an entry (the embedding table aside, of which a step
    reads a row a token), the recurrent state of the step's slots
    (``state_bytes``) read and written, and the KV of ``kv_tokens`` live
    tokens, in the compute dtype, read by each attention layer."""
    n = sum(math.prod(shape) for path, (shape, *_) in leaves(a).items()
            if path != "embed")
    kv = (2 * a.kv_heads * a.head_dim * jnp.dtype(a.compute_dtype).itemsize
          * a.layer_types.count("attention"))
    return n * weights + 2 * state_bytes + kv_tokens * kv
