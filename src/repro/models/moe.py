"""Mixture-of-Experts: top-k routing with two execution paths.

``moe_impl="dense"`` — per-expert masked dense evaluation. Exact (infinite
capacity) and mesh-free; the correctness oracle and the smoke-test path.

``moe_impl="ep"`` — production expert parallelism under ``shard_map``:
  tokens stay batch-sharded on ('pod','data'); experts are sharded on
  'model' (EP) and the expert hidden dim on 'data' (ZeRO-3-style, gathered
  per layer). Dataflow per device:

    route → local capacity-dispatch → all_to_all('model') →
    all_gather(expert weights, 'data') → grouped FFN →
    all_to_all('model') back → combine with gates

  Capacity is static (ceil(k·tokens·cf/E)); overflowing tokens are dropped
  (standard token-dropping MoE) — the EP-vs-dense test uses cf large enough
  that nothing drops.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import ParamSpec, current_mesh
from repro.models.config import ModelConfig


def moe_specs(cfg: ModelConfig, layers: int | None = None) -> dict:
    E, d, ff = cfg.n_experts, cfg.d_model, cfg.d_ff
    L = () if layers is None else (layers,)
    A = () if layers is None else ("layers",)
    dt = cfg.param_dtype
    sp = {
        "router": ParamSpec(L + (d, E), A + ("embed", None), dt, scale=0.02),
        "w1": ParamSpec(L + (E, d, ff), A + ("experts", "embed", "expert_mlp"), dt),
        "w2": ParamSpec(L + (E, ff, d), A + ("experts", "expert_mlp", "embed"), dt),
    }
    if cfg.mlp_type == "swiglu":
        sp["w3"] = ParamSpec(L + (E, d, ff), A + ("experts", "embed", "expert_mlp"), dt)
    if cfg.shared_expert:
        sp["sw1"] = ParamSpec(L + (d, ff), A + ("fsdp", "mlp"), dt)
        sp["sw2"] = ParamSpec(L + (ff, d), A + ("mlp", "fsdp"), dt)
        if cfg.mlp_type == "swiglu":
            sp["sw3"] = ParamSpec(L + (d, ff), A + ("fsdp", "mlp"), dt)
    return sp


def _route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """x (..., D) -> (gates (..., k), idx (..., k) int32). Softmax-then-topk,
    renormalised (Mixtral-style); top-1 degenerates to a plain argmax gate."""
    logits = (x.astype(jnp.float32) @ router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, -1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx.astype(jnp.int32)


def _expert_ffn(cfg: ModelConfig, p: dict, toks: jax.Array) -> jax.Array:
    """toks (E, C, D) grouped per expert -> (E, C, D)."""
    ct = cfg.compute_dtype
    h = jnp.einsum("ecd,edf->ecf", toks.astype(ct), p["w1"].astype(ct))
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(h) * jnp.einsum("ecd,edf->ecf", toks.astype(ct), p["w3"].astype(ct))
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, p["w2"].astype(ct))


def _shared_expert(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    ct = cfg.compute_dtype
    h = x.astype(ct) @ p["sw1"].astype(ct)
    if cfg.mlp_type == "swiglu":
        h = jax.nn.silu(h) * (x.astype(ct) @ p["sw3"].astype(ct))
    else:
        h = jax.nn.gelu(h)
    return h @ p["sw2"].astype(ct)


# ------------------------------------------------------------- dense path ---
def moe_dense(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Oracle: evaluate every expert densely, combine by gates. (..., D)."""
    gates, idx = _route(cfg, p["router"], x)
    onehot = jax.nn.one_hot(idx, cfg.n_experts, dtype=jnp.float32)   # (..., k, E)
    comb = (gates[..., None] * onehot).sum(-2)                        # (..., E)
    toks = jnp.broadcast_to(x[None], (cfg.n_experts,) + x.shape)
    toks = toks.reshape(cfg.n_experts, -1, x.shape[-1])
    outs = _expert_ffn(cfg, p, toks)                                  # (E, N, D)
    outs = outs.reshape((cfg.n_experts,) + x.shape)
    out = jnp.einsum("e...,e...d->...d", jnp.moveaxis(comb, -1, 0), outs)
    if cfg.shared_expert:
        out = out + _shared_expert(cfg, p, x)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- EP path ---
def _dispatch(x_flat, idx, gates, E: int, cap: int):
    """x (N,D), idx/gates (N,k) -> buf (E,cap,D), (slot (N,k), keep (N,k))."""
    N, k = idx.shape
    flat_e = idx.reshape(-1)                                          # (N·k,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    pos = (jnp.cumsum(oh, 0) - 1) * oh
    pos = pos.sum(-1)                                                 # rank within expert
    keep = pos < cap
    posc = jnp.clip(pos, 0, cap - 1)
    src = jnp.repeat(jnp.arange(N), k)
    buf = jnp.zeros((E, cap, x_flat.shape[-1]), x_flat.dtype)
    buf = buf.at[flat_e, posc].add(
        x_flat[src] * keep[:, None].astype(x_flat.dtype), mode="drop"
    )
    return buf, (flat_e, posc, keep, src)


def _combine(out_buf, route, gates, N: int):
    flat_e, posc, keep, src = route
    k = gates.shape[-1]
    vals = out_buf[flat_e, posc] * (keep * gates.reshape(-1)).astype(out_buf.dtype)[:, None]
    out = jnp.zeros((N, out_buf.shape[-1]), out_buf.dtype)
    return out.at[src].add(vals)


def moe_ep(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    """Expert-parallel MoE via shard_map. x (B, S, D)."""
    mesh = current_mesh()
    if mesh is None:  # no mesh: fall back to the oracle
        return moe_dense(cfg, p, x)
    axis_names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_names)
    tp = mesh.shape["model"]
    dp = math.prod(mesh.shape[a] for a in batch_axes)
    fsdp_ax = "data" if "data" in axis_names else None

    B, S, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    n_loc = (B // dp) * S
    cap = max(1, math.ceil(k * n_loc / E * cfg.capacity_factor))
    e_loc = E // tp

    def f(x_loc, router_w, w1, w2, w3):
        # x_loc (B/dp, S, D); w1 (e_loc, D, F/fsdp); router_w (D, E)
        xf = x_loc.reshape(-1, D)
        gates, idx = _route(cfg, router_w, xf)
        buf, route = _dispatch(xf, idx, gates, E, cap)                # (E,cap,D)
        # all_to_all over 'model': exchange expert dim for peer dim. The
        # tiled split==concat form is its own transpose, so the VJP is
        # layout-stable (asymmetric split/concat axes break grad tracing).
        buf = buf.reshape(tp, e_loc, cap, D)
        buf = jax.lax.all_to_all(buf, "model", 0, 0, tiled=True)      # dim0 -> src peer
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, D)
        # ZeRO-3 gather of the fsdp-sharded expert hidden dim
        if fsdp_ax is not None and mesh.shape[fsdp_ax] > 1:
            w1f = jax.lax.all_gather(w1, fsdp_ax, axis=2, tiled=True)
            w2f = jax.lax.all_gather(w2, fsdp_ax, axis=1, tiled=True)
            w3f = jax.lax.all_gather(w3, fsdp_ax, axis=2, tiled=True) if w3 is not None else None
        else:
            w1f, w2f, w3f = w1, w2, w3
        pp = {"w1": w1f, "w2": w2f}
        if w3f is not None:
            pp["w3"] = w3f
        out = _expert_ffn(cfg, pp, buf)                               # (e_loc, tp·cap, D)
        out = out.reshape(e_loc, tp, cap, D).transpose(1, 0, 2, 3)    # (dst peer, e_loc, …)
        out = jax.lax.all_to_all(out, "model", 0, 0, tiled=True)
        out = out.reshape(E, cap, D)
        y = _combine(out.astype(jnp.float32), route, gates, xf.shape[0])
        return y.reshape(x_loc.shape).astype(x_loc.dtype)

    bspec = P(batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None), None, None)
    wspec1 = P("model", None, fsdp_ax)
    wspec2 = P("model", fsdp_ax, None)
    out = shard_map(
        f,
        mesh=mesh,
        in_specs=(bspec, P(None, None), wspec1, wspec2,
                  wspec1 if "w3" in p else None),
        out_specs=bspec,
        check_vma=False,
    )(x, p["router"], p["w1"], p["w2"], p.get("w3"))
    if cfg.shared_expert:  # plain dense MLP — runs under pjit, not shard_map
        out = out + _shared_expert(cfg, {k: p[k] for k in ("sw1", "sw2", "sw3") if k in p}, x).astype(out.dtype)
    return out


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.moe_impl == "ep":
        return moe_ep(cfg, p, x)
    return moe_dense(cfg, p, x)
