"""Mamba2 (state-space duality) block: chunked SSD prefill + recurrent decode.

Follows arXiv:2405.21060. The chunked algorithm computes attention-like
intra-chunk terms with MXU-friendly (Q×Q) matmuls and carries inter-chunk
SSM states with a short sequential scan of length S/chunk — the TPU-native
middle point between the quadratic dual form and the pure recurrence.

The fused in_proj of the reference implementation is split into per-quantity
weights (wz/wx/wB/wC/wdt) so each output lands directly on its logical
sharding axis (heads → 'model'; B/C state projections replicated); the math
is identical to the fused layout. As in the published block, the depthwise
conv has a bias and the output is gated before its norm:
``rmsnorm(y * silu(z))``.

Right-padded prefill is exact: given the true length ``valid_len``, ``dt``
is zero past it, so the pad neither decays the state (exp(0·A) = 1) nor
writes to it (x·dt = 0), and the conv rings are read at the true length.
A prompt padded to a bucket then hands decode the raw prompt's state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ParamSpec, shard
from repro.models.config import ModelConfig
from repro.models.layers import rmsnorm


def mamba_specs(cfg: ModelConfig, layers: int | None = None) -> dict:
    d, inner, N, H = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    kc = cfg.conv_kernel
    L = () if layers is None else (layers,)
    A = () if layers is None else ("layers",)
    dt = cfg.param_dtype
    return {
        "wz": ParamSpec(L + (d, inner), A + ("fsdp", "heads"), dt),
        "wx": ParamSpec(L + (d, inner), A + ("fsdp", "heads"), dt),
        "wB": ParamSpec(L + (d, N), A + ("fsdp", "state"), dt),
        "wC": ParamSpec(L + (d, N), A + ("fsdp", "state"), dt),
        "wdt": ParamSpec(L + (d, H), A + ("fsdp", "heads"), dt),
        "conv_x": ParamSpec(L + (kc, inner), A + ("conv", "heads"), dt, scale=0.5),
        "conv_B": ParamSpec(L + (kc, N), A + ("conv", "state"), dt, scale=0.5),
        "conv_C": ParamSpec(L + (kc, N), A + ("conv", "state"), dt, scale=0.5),
        "conv_x_bias": ParamSpec(L + (inner,), A + ("heads",), dt, init="zeros"),
        "conv_B_bias": ParamSpec(L + (N,), A + ("state",), dt, init="zeros"),
        "conv_C_bias": ParamSpec(L + (N,), A + ("state",), dt, init="zeros"),
        "A_log": ParamSpec(L + (H,), A + ("heads",), jnp.float32, init="zeros"),
        "D": ParamSpec(L + (H,), A + ("heads",), jnp.float32, init="ones"),
        "dt_bias": ParamSpec(L + (H,), A + ("heads",), jnp.float32, init="zeros"),
        "norm_w": ParamSpec(L + (inner,), A + ("heads",), dt, init="ones"),
        "wo": ParamSpec(L + (inner, d), A + ("heads", "fsdp"), dt),
    }


def causal_conv1d(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv with bias, then silu. x (B,S,C), w (k,C), b (C,)."""
    k = w.shape[0]
    S = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = sum(xp[:, i : i + S] * w[i].astype(x.dtype) for i in range(k))
    return jax.nn.silu(y + b.astype(x.dtype))


def gated_norm(cfg: ModelConfig, y: jax.Array, z: jax.Array, w: jax.Array) -> jax.Array:
    """``rmsnorm(y * silu(z)) * w`` in f32, returned in y's dtype."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return rmsnorm(g, w, cfg.norm_eps).astype(y.dtype)


def _segsum(dA: jax.Array) -> jax.Array:
    """Lower-triangular pairwise decay sums: out[..., i, j] = Σ_{j<s<=i} dA[s]."""
    Q = dA.shape[-1]
    cs = jnp.cumsum(dA, -1)
    diff = cs[..., :, None] - cs[..., None, :]                 # (..., i, j)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """SSD over a sequence. Returns (y, final_state).

    x (B,S,H,P); dt (B,S,H) post-softplus; A (H,) negative;
    Bm/Cm (B,S,N) (single group broadcast over heads).
    """
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    nc = S // chunk
    xc = x.reshape(Bb, nc, chunk, H, P)
    dtc = dt.reshape(Bb, nc, chunk, H)
    Bc = Bm.reshape(Bb, nc, chunk, N).astype(jnp.float32)
    Cc = Cm.reshape(Bb, nc, chunk, N).astype(jnp.float32)
    dA = (dtc * A[None, None, None, :]).astype(jnp.float32)   # (B,nc,Q,H) ≤ 0
    dA = jnp.moveaxis(dA, -1, 2)                               # (B,nc,H,Q)
    dA_cum = jnp.cumsum(dA, -1)                                # (B,nc,H,Q)
    xdt = (xc * dtc[..., None]).astype(jnp.float32)            # (B,nc,Q,H,P)

    # Intra-chunk (attention-like, MXU):
    Lmat = jnp.exp(_segsum(dA))                                # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)             # (B,nc,Q,Q)
    att = scores[:, :, None] * Lmat                            # (B,nc,H,Q,Q)
    y_diag = jnp.einsum("bchqk,bckhp->bcqhp", att, xdt)

    # Per-chunk input states:
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)          # (B,nc,H,Q)
    states = jnp.einsum("bckn,bchk,bckhp->bchpn", Bc, decay_states, xdt)

    # Inter-chunk recurrence (sequential over nc chunks):
    chunk_decay = jnp.exp(dA_cum[..., -1])                     # (B,nc,H)

    def step(s_prev, inp):
        st, dec = inp
        s_new = s_prev * dec[..., None, None] + st
        return s_new, s_prev

    s0 = jnp.zeros((Bb, H, P, N), jnp.float32)
    final, s_prevs = jax.lax.scan(
        step, s0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0))
    )
    s_prevs = jnp.moveaxis(s_prevs, 0, 1)                      # (B,nc,H,P,N)

    y_off = jnp.einsum("bcqn,bchpn,bchq->bcqhp", Cc, s_prevs, jnp.exp(dA_cum))
    y = (y_diag + y_off).reshape(Bb, S, H, P)
    return y.astype(x.dtype), final


def mamba_prefill(cfg: ModelConfig, p: dict, x: jax.Array, matmul=None,
                  valid_len: jax.Array | None = None):
    """x (B,S,D) -> (y (B,S,D), (ssm_state, conv_states)).

    ``valid_len`` ((B,) int32): each row's true length of a right-padded
    batch; the states returned are those at that length (module docstring).
    """
    mm = matmul or (lambda a, pp, name: a @ pp[name].astype(a.dtype))
    B, S, _ = x.shape
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    z = mm(x, p, "wz")
    x_pre = mm(x, p, "wx")
    B_pre = mm(x, p, "wB")
    C_pre = mm(x, p, "wC")
    dt = mm(x, p, "wdt").astype(jnp.float32)
    xin = shard(causal_conv1d(x_pre, p["conv_x"], p["conv_x_bias"]),
                "batch", "seq", "act_heads")
    Bm = causal_conv1d(B_pre, p["conv_B"], p["conv_B_bias"])
    Cm = causal_conv1d(C_pre, p["conv_C"], p["conv_C_bias"])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    end = jnp.full((B,), S, jnp.int32) if valid_len is None else valid_len
    if valid_len is not None:
        dt = jnp.where((jnp.arange(S)[None, :] < valid_len[:, None])[..., None],
                       dt, 0.0)
    A = -jnp.exp(p["A_log"])
    xh = xin.reshape(B, S, H, P)
    chunk = min(cfg.ssm_chunk, S)
    while S % chunk:  # largest divisor of S not exceeding the configured chunk
        chunk -= 1
    y, state = ssd_chunked(xh, dt, A, Bm, Cm, chunk)
    y = y + p["D"][None, None, :, None].astype(y.dtype) * xh
    y = y.reshape(B, S, H * P)
    out = mm(gated_norm(cfg, y, z, p["norm_w"]), p, "wo")
    # conv ring states for decode handoff: the (k-1) pre-conv inputs before
    # position ``end``, zeros where the sequence is shorter (the conv's pad)
    kc = cfg.conv_kernel
    idx = (end[:, None] + jnp.arange(kc - 1))[..., None]

    def ring(u):
        up = jnp.pad(u, ((0, 0), (kc - 1, 0), (0, 0)))
        return jnp.take_along_axis(up, idx, axis=1)

    conv_states = {"x": ring(x_pre), "B": ring(B_pre), "C": ring(C_pre)}
    return shard(out, "batch", "seq", "act_embed"), (state, conv_states)


def _conv_decode(x_t: jax.Array, state: jax.Array, w: jax.Array, b: jax.Array):
    """x_t (B,C); state (B,k-1,C) past inputs. Returns (y_t, new_state)."""
    full = jnp.concatenate([state, x_t[:, None]], 1)           # (B,k,C)
    y = (full * w[None].astype(full.dtype)).sum(1) + b.astype(full.dtype)
    return jax.nn.silu(y), full[:, 1:]


def mamba_decode(cfg: ModelConfig, p: dict, x_t: jax.Array, state, matmul=None):
    """One-token recurrent step. x_t (B,D); state = (ssm (B,H,P,N), conv dict)."""
    mm = matmul or (lambda a, pp, name: a @ pp[name].astype(a.dtype))
    ssm, conv = state
    B = x_t.shape[0]
    H, P, N = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    z = mm(x_t, p, "wz")
    xin, cx = _conv_decode(mm(x_t, p, "wx"), conv["x"], p["conv_x"], p["conv_x_bias"])
    Bm, cB = _conv_decode(mm(x_t, p, "wB"), conv["B"], p["conv_B"], p["conv_B_bias"])
    Cm, cC = _conv_decode(mm(x_t, p, "wC"), conv["C"], p["conv_C"], p["conv_C_bias"])
    dt = jax.nn.softplus(mm(x_t, p, "wdt").astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A)                                        # (B,H)
    xh = xin.reshape(B, H, P).astype(jnp.float32)
    xdt = xh * dt[..., None]
    ssm_new = ssm * dA[..., None, None] + jnp.einsum("bn,bhp->bhpn", Bm.astype(jnp.float32), xdt)
    y = jnp.einsum("bn,bhpn->bhp", Cm.astype(jnp.float32), ssm_new)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(B, H * P).astype(x_t.dtype)
    out = mm(gated_norm(cfg, y, z, p["norm_w"]), p, "wo")
    return out, (ssm_new, {"x": cx, "B": cB, "C": cC})

