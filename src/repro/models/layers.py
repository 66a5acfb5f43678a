"""Transformer building blocks: norms, RoPE, attention variants, MLPs.

All functions are mixed-precision aware (norms/softmax in f32, matmuls in
``cfg.compute_dtype``) and annotate activations with logical sharding axes.
Attention provides three masking families required by the assigned archs —
full causal, sliding-window (banded, O(S·W)), and chunked-local — plus a
single-token decode path against a KV cache (ring-buffered for SWA).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ParamSpec, shard
from repro.models.config import ModelConfig


# ------------------------------------------------------------------ norms ---
def rmsnorm(x: jax.Array, w: jax.Array | None, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    return y.astype(x.dtype)


def nonparam_ln(x: jax.Array, _w=None, eps: float = 1e-5) -> jax.Array:
    """OLMo's non-parametric LayerNorm: standard LN without γ/β."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


def norm_spec(cfg: ModelConfig, layers: int | None = None) -> dict:
    if cfg.norm == "nonparam_ln":
        return {}
    shape = (cfg.d_model,) if layers is None else (layers, cfg.d_model)
    axes = ("embed",) if layers is None else ("layers", "embed")
    return {"w": ParamSpec(shape, axes, init="ones")}


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "nonparam_ln":
        return nonparam_ln(x)
    return rmsnorm(x, p.get("w"), cfg.norm_eps)


# ------------------------------------------------------------------- RoPE ---
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs       # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention ---
def _repeat_kv(k: jax.Array, rep: int) -> jax.Array:
    if rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None], (b, s, h, rep, d)).reshape(b, s, h * rep, d)


def attention_dense(q, k, v, *, causal: bool = True, q_offset: int | jax.Array = 0,
                    window: int | None = None, kv_len: jax.Array | None = None):
    """Materialised-scores attention. q (B,Sq,H,D), k/v (B,Skv,H,D)."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qpos = jnp.arange(q.shape[1]) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    if kv_len is not None:
        s = jnp.where((kpos < kv_len)[None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_kv: int = 1024, window: int | None = None):
    """Blockwise online-softmax attention (pure-jnp flash) for long prefill.

    O(S²) full-causal or O(S·W) sliding-window; scores never materialise
    beyond (B, H, bq, bkv). q, k, v: (B, S, H, D) with H already GQA-repeated.
    """
    B, S, H, D = q.shape
    scale = D ** -0.5
    nq = S // block_q

    if window is not None:
        # Banded: each q block attends a single contiguous KV slice of width
        # window + block_q (clamped at 0) — true O(S·W) compute.
        span = window + block_q

        def q_block(iq):
            q0 = iq * block_q
            qi = jax.lax.dynamic_slice_in_dim(q, q0, block_q, 1)
            start = jnp.clip(q0 + block_q - span, 0, S - span)
            kj = jax.lax.dynamic_slice_in_dim(k, start, span, 1)
            vj = jax.lax.dynamic_slice_in_dim(v, start, span, 1)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(jnp.float32), kj.astype(jnp.float32)) * scale
            qpos = q0 + jnp.arange(block_q)
            kpos = start + jnp.arange(span)
            mask = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            p = jax.nn.softmax(s, -1)
            p = jnp.where(jnp.isnan(p), 0.0, p)
            return jnp.einsum("bhqk,bkhd->bqhd", p, vj.astype(jnp.float32)).astype(q.dtype)

        out = jax.lax.map(jax.checkpoint(q_block), jnp.arange(nq))  # (nq, B, bq, H, D)
        return jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)

    nkv = S // block_kv

    def q_block(iq):
        q0 = iq * block_q
        qi = jax.lax.dynamic_slice_in_dim(q, q0, block_q, 1).astype(jnp.float32)
        qpos = q0 + jnp.arange(block_q)

        def kv_step(carry, ikv):
            m, den, acc = carry
            k0 = ikv * block_kv
            kj = jax.lax.dynamic_slice_in_dim(k, k0, block_kv, 1).astype(jnp.float32)
            vj = jax.lax.dynamic_slice_in_dim(v, k0, block_kv, 1).astype(jnp.float32)
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj) * scale
            if causal:
                kpos = k0 + jnp.arange(block_kv)
                s = jnp.where((kpos[None, :] <= qpos[:, None])[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            den_new = den * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vj)
            return (m_new, den_new, acc_new), None

        m0 = jnp.full((B, H, block_q), -jnp.inf)
        l0 = jnp.zeros((B, H, block_q))
        a0 = jnp.zeros((B, H, block_q, D))
        (m, den, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(den, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2).astype(q.dtype)       # (B, bq, H, D)

    # Checkpoint per q-block: without this, autodiff through the online-
    # softmax scan materialises every (bq, bkv) score block for the backward
    # pass — O(S²) saves that defeat flash attention. With it, the backward
    # recomputes scores blockwise: O(S·D) residuals (flash-backward-by-remat).
    q_block = jax.checkpoint(q_block)
    out = jax.lax.map(q_block, jnp.arange(nq))               # (nq, B, bq, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)


def chunked_local_attention(q, k, v, chunk: int):
    """llama4-style local attention: causal within fixed chunks."""
    B, S, H, D = q.shape
    if S <= chunk:
        return attention_dense(q, k, v, causal=True)
    if S % chunk:  # pad to a chunk multiple; causal masking hides the pad
        pad = chunk - S % chunk
        pz = [(0, 0), (0, pad), (0, 0), (0, 0)]
        out = chunked_local_attention(jnp.pad(q, pz), jnp.pad(k, pz), jnp.pad(v, pz), chunk)
        return out[:, :S]
    n = S // chunk
    qc = q.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(lambda t: attention_dense(t[0], t[1], t[2], causal=True), (qc, kc, vc))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def attention_prefill(cfg: ModelConfig, layer_idx, q, k, v, *, layer_global: bool):
    """Dispatch by attention type and sequence length. q/k/v (B,S,H*,D)."""
    from repro.models import flash as flash_mod

    k = _repeat_kv(k, q.shape[2] // k.shape[2])
    v = _repeat_kv(v, q.shape[2] // v.shape[2])
    S = q.shape[1]
    window = cfg.window if cfg.attn_type == "swa" else None
    chunk = (cfg.chunk if (cfg.attn_type == "chunked_interleaved" and not layer_global)
             else None)
    if S <= 1024:  # small sequences: materialised scores are cheapest
        if chunk is not None:
            return chunked_local_attention(q, k, v, chunk)
        return attention_dense(q, k, v, causal=True, window=window)
    if window is not None and S > 8192:
        # long SWA prefill (inference-only shapes): banded O(S·W) forward
        return flash_attention(q, k, v, window=window,
                               block_q=min(512, S), block_kv=min(1024, S))
    if cfg.attn_impl == "naive":
        if chunk is not None:
            return chunked_local_attention(q, k, v, chunk)
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=min(512, S), block_kv=min(1024, S))
    # Route the flash branch through the Phi execution policy: dense LM Q/K
    # are not spikes, so the site records ``dense_qk_keeps_flash`` and the
    # policy hands back the dense custom-VJP flash lowering — the decision
    # row is what documents that spiking Q/K would resolve ``phi_flash``
    # here. Site name is static (layer_idx may be a tracer under
    # scan-over-layers).
    from repro.kernels import dispatch

    B, _, H, D = q.shape
    dispatch.get_policy().resolve_attention(
        site="lm.attn_prefill", s=S, d=D, heads=H, batch=B,
        spike_qk=False, has_patterns=False)
    return flash_mod.flash_attention(q, k, v, True, window, chunk,
                                     min(cfg.flash_block_q, S),
                                     min(cfg.flash_block_kv, S))


def attention_decode(q, k_cache, v_cache, pos, *, mode: str = "full"):
    """One-token decode. q (B,1,H,D); caches (B,Smax,Hkv,D); pos (B,) int32.

    mode:
      "full"       — linear cache, slot == position: valid = kpos ≤ pos.
      "ring"       — SWA ring buffer of size Smax == window: every filled
                     slot is in-window by construction.
      "chunk_ring" — llama4 local-attention ring of size Smax == chunk:
                     slot s holds the latest position ≡ s (mod chunk); the
                     slots belonging to the current chunk are exactly
                     s ≤ pos mod chunk.
    """
    rep = q.shape[2] // k_cache.shape[2]
    k = _repeat_kv(k_cache, rep)
    v = _repeat_kv(v_cache, rep)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    smax = k.shape[1]
    kpos = jnp.arange(smax)[None, :]                          # (1, Smax)
    p_ = pos[:, None]                                         # (B, 1)
    if mode == "full":
        valid = kpos <= p_
    elif mode == "ring":
        valid = (kpos <= p_) | (p_ >= smax)
    elif mode == "chunk_ring":
        valid = kpos <= (p_ % smax)
    else:
        raise ValueError(mode)
    s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------- matmul fn ---
def default_mm(a: jax.Array, p: dict, name: str) -> jax.Array:
    """Default GEMM: matmul fns receive the layer param dict + weight name so
    alternative impls (Phi spiking mode) can find per-weight side state."""
    return a @ p[name].astype(a.dtype)


# -------------------------------------------------------------------- MLP ---
def mlp_specs(cfg: ModelConfig, layers: int | None = None, d_ff: int | None = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    L = () if layers is None else (layers,)
    A = () if layers is None else ("layers",)
    dt = cfg.param_dtype
    sp = {
        "w1": ParamSpec(L + (d, ff), A + ("fsdp", "mlp"), dt),
        "w2": ParamSpec(L + (ff, d), A + ("mlp", "fsdp"), dt),
    }
    if cfg.mlp_type == "swiglu":
        sp["w3"] = ParamSpec(L + (d, ff), A + ("fsdp", "mlp"), dt)
    return sp


def mlp_apply(cfg: ModelConfig, p: dict, x: jax.Array, matmul=None) -> jax.Array:
    mm = matmul or default_mm
    h = mm(x, p, "w1")
    h = shard(h, "batch", "seq", "act_mlp")
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(h) * mm(x, p, "w3")
    else:
        h = jax.nn.gelu(h)
    out = mm(h, p, "w2")
    return shard(out, "batch", "seq", "act_embed")
