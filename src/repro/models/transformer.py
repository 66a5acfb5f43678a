"""Decoder stack: grouped scan-over-layers for all architecture families.

Layers are scanned (stacked params, single trace) for compile-time and HLO
size; heterogeneous interleavings (llama4 dense/MoE + chunked/global
attention, zamba2 shared-attention insertion) scan over *groups* whose size
is the LCM of the interleave periods, with the group's member layers unrolled
inside the body. Remat (``cfg.remat``) wraps the group body.

Layer-typed stacks (``cfg.layer_types``, granite-4.0-h): one period of the
pattern is cut into runs of same-kind layers ([5 mamba, 1 attention,
4 mamba]); each run keeps its layers' own weights stacked on one axis, and
the stack scans over periods with an inner scan over each run. Every layer,
Mamba-2 or attention, is followed by the MLP. The decode state is one
subtree per run on the same axes: KV caches (or page pools) for attention
runs, the recurrent state (SSM state, conv rings) per slot for Mamba-2 runs.

GQA under TP=16 with awkward head counts (paper-exact math, §DESIGN):
  * Q heads are zero-masked padding up to a TP multiple — padded heads
    compute dead attention that is masked before the out-projection, so
    their parameters receive zero gradient and outputs are exact.
  * KV heads with n_kv < TP keep their *logical* weights (replicated over the
    model axis — the projection is tiny) and the K/V activations are
    repeated to the padded head count before sharding.
"""
from __future__ import annotations

import math
import jax
import jax.numpy as jnp

from repro.distributed.sharding import ParamSpec, shard
from repro.kernels import dispatch, paged_attention
from repro.models import layers as ll
from repro.models import mamba2, moe
from repro.models.config import ModelConfig


# ------------------------------------------------------------------ specs ---
def group_size(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return max(cfg.hybrid_attn_every, 1)
    g = 1
    if cfg.n_experts and cfg.moe_interleave > 1:
        g = math.lcm(g, cfg.moe_interleave)
    if cfg.attn_type == "chunked_interleaved":
        g = math.lcm(g, cfg.global_every)
    return g


def _kv_replicated(cfg: ModelConfig) -> bool:
    return cfg.n_kv_heads < cfg.tp


def _attn_specs(cfg: ModelConfig, n: int) -> dict:
    """Attention specs; kv weights logical (replicated) when n_kv < tp."""
    d, hd = cfg.d_model, cfg.hd
    hq = cfg.q_heads_padded
    hkv = cfg.n_kv_heads if _kv_replicated(cfg) else cfg.kv_heads_padded
    kv_ax = None if _kv_replicated(cfg) else "kv_heads"
    dt = cfg.param_dtype
    L, A = ((n,), ("layers",)) if n else ((), ())
    sp = {
        "wq": ParamSpec(L + (d, hq * hd), A + ("fsdp", "heads"), dt),
        "wk": ParamSpec(L + (d, hkv * hd), A + ("fsdp", kv_ax), dt),
        "wv": ParamSpec(L + (d, hkv * hd), A + ("fsdp", kv_ax), dt),
        "wo": ParamSpec(L + (hq * hd, d), A + ("heads", "fsdp"), dt),
    }
    if cfg.qkv_bias:
        sp["bq"] = ParamSpec(L + (hq * hd,), A + ("heads",), dt, init="zeros")
        sp["bk"] = ParamSpec(L + (hkv * hd,), A + (kv_ax,), dt, init="zeros")
        sp["bv"] = ParamSpec(L + (hkv * hd,), A + (kv_ax,), dt, init="zeros")
    return sp


def _position_specs(cfg: ModelConfig, pos: int, n_groups: int) -> dict:
    """Specs of group-position ``pos`` (stacked over n_groups)."""
    sp: dict = dict(_attn_specs(cfg, n_groups))
    sp["ln1"] = ll.norm_spec(cfg, n_groups)
    sp["ln2"] = ll.norm_spec(cfg, n_groups)
    if cfg.is_moe_layer(pos):
        sp["moe"] = moe.moe_specs(cfg, n_groups)
        if cfg.dense_residual_ff:
            sp["dres"] = ll.mlp_specs(cfg, n_groups, d_ff=cfg.dense_residual_ff)
    else:
        sp["mlp"] = ll.mlp_specs(cfg, n_groups)
        if cfg.dense_residual_ff:  # arctic: dense residual on every layer
            sp["dres"] = ll.mlp_specs(cfg, n_groups, d_ff=cfg.dense_residual_ff)
    return sp


def layer_runs(cfg: ModelConfig) -> list[tuple[str, int]]:
    """(kind, length) of each run of same-kind layers in one period of a
    layer-typed stack (the shortest period that tiles ``cfg.layer_kinds``)."""
    kinds = cfg.layer_kinds
    n = len(kinds)
    period = next(p for p in range(1, n + 1)
                  if n % p == 0 and kinds == kinds[:p] * (n // p))
    runs: list[list] = []
    for k in kinds[:period]:
        if runs and runs[-1][0] == k:
            runs[-1][1] += 1
        else:
            runs.append([k, 1])
    return [(k, n_) for k, n_ in runs]


def _layered_specs(cfg: ModelConfig) -> dict:
    """Run ``r<i>`` stacks its layers of every period on one axis, period
    major: layer j of period t is entry ``t * length + j``."""
    runs = layer_runs(cfg)
    periods = cfg.n_layers // sum(n for _, n in runs)
    out = {}
    for i, (kind, n) in enumerate(runs):
        L = periods * n
        sp = ({"mamba": mamba2.mamba_specs(cfg, L)} if kind == "mamba"
              else _attn_specs(cfg, L))
        sp.update(ln1=ll.norm_spec(cfg, L), ln2=ll.norm_spec(cfg, L),
                  mlp=ll.mlp_specs(cfg, L))
        out[f"r{i}"] = sp
    return out


def decoder_specs(cfg: ModelConfig) -> dict:
    if cfg.layer_kinds:
        return _layered_specs(cfg)
    g = group_size(cfg)
    if cfg.family == "ssm":
        return {
            "mamba": mamba2.mamba_specs(cfg, cfg.n_layers),
            "ln": ll.norm_spec(cfg, cfg.n_layers),
        }
    if cfg.family == "hybrid":
        n_main = (cfg.n_layers // g) * g
        n_sites = cfg.n_layers // g
        tail = cfg.n_layers - n_main
        r = 64  # LoRA rank for per-site adaptation of the shared block
        d, hd = cfg.d_model, cfg.hd
        hq = cfg.q_heads_padded
        sp = {
            "mamba": mamba2.mamba_specs(cfg, n_main),
            "ln": ll.norm_spec(cfg, n_main),
            "shared": {
                "attn": _attn_specs(cfg, 0),
                "ln1": ll.norm_spec(cfg),
                "ln2": ll.norm_spec(cfg),
                "mlp": ll.mlp_specs(cfg),
            },
            "lora_a": ParamSpec((n_sites, d, r), ("layers", "fsdp", None), cfg.param_dtype, scale=0.02),
            "lora_b": ParamSpec((n_sites, r, hq * hd), ("layers", None, "heads"), cfg.param_dtype, init="zeros"),
        }
        if tail:
            sp["mamba_tail"] = mamba2.mamba_specs(cfg, tail)
            sp["ln_tail"] = ll.norm_spec(cfg, tail)
        return sp
    # attention families
    n_groups = cfg.n_layers // g
    return {"stack": {f"p{i}": _position_specs(cfg, i, n_groups) for i in range(g)}}


# ---------------------------------------------------------------- forward ---
def _head_mask(cfg: ModelConfig) -> jax.Array:
    m = jnp.zeros((cfg.q_heads_padded,), jnp.float32).at[: cfg.n_heads].set(1.0)
    return m


def _qkv(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array, matmul=None,
         lora: tuple[jax.Array, jax.Array] | None = None):
    B, S, _ = x.shape
    mm = matmul or ll.default_mm
    q = mm(x, p, "wq")
    if lora is not None:  # zamba2 per-site adaptation of the shared block
        a, b = lora
        q = q + (x @ a.astype(x.dtype)) @ b.astype(x.dtype)
    k = mm(x, p, "wk")
    v = mm(x, p, "wv")
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(q.dtype), k + p["bk"].astype(k.dtype), v + p["bv"].astype(v.dtype)
    hq = cfg.q_heads_padded
    hkv_stored = k.shape[-1] // cfg.hd
    q = q.reshape(B, S, hq, cfg.hd)
    k = k.reshape(B, S, hkv_stored, cfg.hd)
    v = v.reshape(B, S, hkv_stored, cfg.hd)
    if hkv_stored < cfg.kv_heads_padded:  # replicate logical KV heads
        k = ll._repeat_kv(k, cfg.kv_heads_padded // hkv_stored)
        v = ll._repeat_kv(v, cfg.kv_heads_padded // hkv_stored)
    if cfg.position == "rope":
        q = ll.rope(q, positions, cfg.rope_theta)
        k = ll.rope(k, positions, cfg.rope_theta)
    if cfg.attention_multiplier is not None:
        # Every attention lowering scales scores by hd ** -0.5; pre-scaling
        # q makes the product attention_multiplier (granite: 1/64 · 8 = 1/8,
        # a power of two, so exact in any float format).
        q = q * (cfg.attention_multiplier * cfg.hd ** 0.5)
    q = shard(q, "batch", "seq", "act_heads", None)
    k = shard(k, "batch", "seq", "act_heads", None)
    v = shard(v, "batch", "seq", "act_heads", None)
    return q, k, v


def _residual(cfg: ModelConfig, x: jax.Array, out: jax.Array) -> jax.Array:
    """x plus a sublayer's output, scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    return x + out.astype(x.dtype)


def attn_block_prefill(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
                       layer_global: bool, matmul=None, lora=None, want_cache=False):
    mm = matmul or ll.default_mm
    h = ll.apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p, h, positions, matmul, lora)
    o = ll.attention_prefill(cfg, 0, q, k, v, layer_global=layer_global)
    o = o * _head_mask(cfg)[None, None, :, None].astype(o.dtype)
    o = o.reshape(x.shape[0], x.shape[1], -1)
    x = _residual(cfg, x, mm(o, p, "wo"))
    x = shard(x, "batch", "saved_seq", "act_embed")
    cache = None
    if want_cache:
        win = _cache_window(cfg, layer_global)
        S = k.shape[1]
        if win is not None and S > win:
            # Ring cache: position p must land at slot p % win.
            k = jnp.roll(k[:, -win:], (S - win) % win, axis=1)
            v = jnp.roll(v[:, -win:], (S - win) % win, axis=1)
        cache = (k, v)
    return x, cache


def _cache_window(cfg: ModelConfig, layer_global: bool) -> int | None:
    if cfg.attn_type == "swa":
        return cfg.window
    if cfg.attn_type == "chunked_interleaved" and not layer_global:
        return cfg.chunk
    return None


def attn_block_decode(cfg: ModelConfig, p: dict, x: jax.Array, pos: jax.Array,
                      kv: tuple[jax.Array, jax.Array], layer_global: bool,
                      matmul=None, lora=None):
    """x (B,1,D); pos (B,) int32; kv caches (B,Smax,Hkv,hd)."""
    mm = matmul or ll.default_mm
    h = ll.apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p, h, pos[:, None], matmul, lora)
    k_cache, v_cache = kv
    smax = k_cache.shape[1]
    win = _cache_window(cfg, layer_global)
    if win is not None and smax == win:
        mode = "chunk_ring" if cfg.attn_type == "chunked_interleaved" else "ring"
        slot = pos % smax
    else:
        mode = "full"
        slot = jnp.minimum(pos, smax - 1)

    def upd(cache, new):
        bidx = jnp.arange(cache.shape[0])
        return cache.at[bidx, slot].set(new[:, 0].astype(cache.dtype))

    k_cache, v_cache = upd(k_cache, k), upd(v_cache, v)
    o = ll.attention_decode(q, k_cache, v_cache, pos, mode=mode)
    o = o * _head_mask(cfg)[None, None, :, None].astype(o.dtype)
    o = o.reshape(x.shape[0], 1, -1)
    x = _residual(cfg, x, mm(o, p, "wo"))
    return x, (k_cache, v_cache)


def attn_block_decode_paged(cfg: ModelConfig, p: dict, x: jax.Array,
                            pos: jax.Array, kv: tuple[jax.Array, jax.Array],
                            page_table: jax.Array, matmul=None, lora=None):
    """One-token decode against a *paged* KV cache (full attention only).

    x (B,1,D); pos (B,) int32; kv pools (P+1, page_size, Hkv, hd) — the last
    physical page is the scratch target for unmapped lanes; page_table
    (B, Lp) int32 maps logical page -> physical pool page, -1 = unmapped.

    Writes scatter the new K/V row through the table
    (``pool[table[b, pos // ps], pos % ps]``). Reads take one of two
    lowerings, chosen by the execution policy at site
    ``lm.attn_decode_paged`` from the backend and the pool's shape:

    * ``paged_kernel`` (TPU): ``kernels/paged_attention.py`` reads only the
      pages holding positions 0..pos of each slot, straight from the pool.
      The lengths are ``pos + 1``; a lane whose write page is unmapped (an
      inactive slot) gets length 1 and reads the scratch page, and its
      output is discarded by the engine.
    * ``gather`` (elsewhere): gather every logical page back into a
      (B, Lp*ps, Hkv, hd) view that is shape-identical to the contiguous
      cache, so the unchanged ``ll.attention_decode`` masks it exactly as
      before. Unmapped logical pages are clamped to physical page 0 in the
      view — every position they cover satisfies ``kpos > pos`` and is
      masked to an exact zero by the softmax, which is what makes paged
      decode bitwise identical to contiguous decode (see
      ``serve/page_manager.py``).
    """
    mm = matmul or ll.default_mm
    h = ll.apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p, h, pos[:, None], matmul, lora)
    k_pool, v_pool = kv
    ps = k_pool.shape[1]
    lp = pos // ps
    phys = jnp.take_along_axis(page_table, lp[:, None], axis=1)[:, 0]
    mapped = phys >= 0
    # Unmapped lane (inactive slot / freed table row): scatter into the
    # reserved scratch page instead of wrapping to a live page via -1.
    phys = jnp.where(mapped, phys, k_pool.shape[0] - 1)
    off = pos % ps

    def upd(pool, new):
        return pool.at[phys, off].set(new[:, 0].astype(pool.dtype))

    k_pool, v_pool = upd(k_pool, k), upd(v_pool, v)
    B, _, heads, hd = q.shape
    dec = dispatch.get_policy().resolve_paged_decode(
        site="lm.attn_decode_paged", batch=B, heads=heads,
        kv_heads=k_pool.shape[2], head_dim=hd, page_size=ps,
        logical_pages=page_table.shape[1], dtype=k_pool.dtype)
    if dec.impl == "paged_kernel":
        lengths = jnp.where(mapped, pos + 1, 1)
        o = paged_attention.paged_decode_attention(
            q[:, 0], k_pool, v_pool, page_table, lengths,
            pages_per_block=dec.blocks[0],
            interpret=jax.default_backend() != "tpu")[:, None]
    else:
        view_table = jnp.maximum(page_table, 0)

        def view(pool):
            g = pool[view_table]                  # (B, Lp, ps, Hkv, hd)
            return g.reshape(g.shape[0], -1, g.shape[3], g.shape[4])

        o = ll.attention_decode(q, view(k_pool), view(v_pool), pos,
                                mode="full")
    o = o * _head_mask(cfg)[None, None, :, None].astype(o.dtype)
    o = o.reshape(x.shape[0], 1, -1)
    x = _residual(cfg, x, mm(o, p, "wo"))
    return x, (k_pool, v_pool)


def _ffn(cfg: ModelConfig, p: dict, x: jax.Array, pos_in_group: int, matmul=None):
    h = ll.apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        out = moe.moe_apply(cfg, p["moe"], h)
    else:
        out = ll.mlp_apply(cfg, p["mlp"], h, matmul)
    if "dres" in p:  # arctic parallel dense residual
        out = out + ll.mlp_apply(cfg, p["dres"], h, matmul)
    return shard(_residual(cfg, x, out), "batch", "saved_seq", "act_embed")


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
              if cfg.remat == "dots" else jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(fn, policy=policy)


# ------------------------------------------------------- attention families --
def _attn_stack_prefill(cfg: ModelConfig, params: dict, x: jax.Array,
                        positions: jax.Array, matmul=None, want_cache=False):
    g = group_size(cfg)

    def group_body(x, gp):
        caches = []
        for i in range(g):
            p = gp[f"p{i}"]
            x, cache = attn_block_prefill(cfg, p, x, positions, cfg.is_global_layer(i),
                                          matmul, want_cache=want_cache)
            x = _ffn(cfg, p, x, i, matmul)
            caches.append(cache)
        if want_cache:
            return x, tuple(caches)
        return x, None

    body = _maybe_remat(cfg, group_body)
    x, caches = jax.lax.scan(body, x, params["stack"])
    return x, caches


def _attn_stack_decode(cfg: ModelConfig, params: dict, x: jax.Array, pos: jax.Array,
                       caches, matmul=None):
    g = group_size(cfg)

    def group_body(x, inp):
        gp, gcaches = inp
        new_caches = []
        for i in range(g):
            p = gp[f"p{i}"]
            x, kv = attn_block_decode(cfg, p, x, pos, gcaches[i], cfg.is_global_layer(i), matmul)
            x = _ffn(cfg, p, x, i, matmul)
            new_caches.append(kv)
        return x, tuple(new_caches)

    x, new_caches = jax.lax.scan(group_body, x, (params["stack"], caches))
    return x, new_caches


def _attn_stack_decode_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                             pos: jax.Array, pools, page_table: jax.Array,
                             matmul=None):
    g = group_size(cfg)

    def group_body(x, inp):
        gp, gpools = inp
        new_pools = []
        for i in range(g):
            p = gp[f"p{i}"]
            x, kv = attn_block_decode_paged(cfg, p, x, pos, gpools[i],
                                            page_table, matmul)
            x = _ffn(cfg, p, x, i, matmul)
            new_pools.append(kv)
        return x, tuple(new_pools)

    x, new_pools = jax.lax.scan(group_body, x, (params["stack"], pools))
    return x, new_pools


# ------------------------------------------------------------ ssm families --
def _ssm_stack_prefill(cfg: ModelConfig, params: dict, x: jax.Array, matmul=None,
                       want_state=False):
    def body(x, lp):
        p, ln = lp
        h = ll.apply_norm(cfg, ln, x)
        out, state = mamba2.mamba_prefill(cfg, p, h, matmul)
        x = shard(x + out.astype(x.dtype), "batch", "saved_seq", "act_embed")
        return x, state if want_state else None

    x, states = jax.lax.scan(_maybe_remat(cfg, body), x, (params["mamba"], params["ln"]))
    return x, states


def _ssm_stack_decode(cfg: ModelConfig, params: dict, x: jax.Array, states, matmul=None):
    def body(x, inp):
        p, ln, st = inp
        h = ll.apply_norm(cfg, ln, x[:, 0])
        out, new_st = mamba2.mamba_decode(cfg, p, h, st, matmul)
        return x + out[:, None].astype(x.dtype), new_st

    x, new_states = jax.lax.scan(body, x, (params["mamba"], params["ln"], states))
    return x, new_states


# --------------------------------------------------------- hybrid (zamba2) --
def _hybrid_prefill(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
                    matmul=None, want_cache=False):
    g = group_size(cfg)
    n_sites = cfg.n_layers // g

    def site_body(x, inp):
        mamba_g, ln_g, lora_a, lora_b = inp

        def inner(x, lp):
            p, ln = lp
            h = ll.apply_norm(cfg, ln, x)
            out, _ = mamba2.mamba_prefill(cfg, p, h, matmul)
            return shard(x + out.astype(x.dtype), "batch", "saved_seq", "act_embed"), None

        x, _ = jax.lax.scan(inner, x, (mamba_g, ln_g))
        sp = params["shared"]
        merged = dict(sp["attn"])
        merged["ln1"] = sp["ln1"]
        x, cache = attn_block_prefill(cfg, merged, x, positions, True, matmul,
                                      lora=(lora_a, lora_b), want_cache=want_cache)
        h = ll.apply_norm(cfg, sp["ln2"], x)
        x = x + ll.mlp_apply(cfg, sp["mlp"], h, matmul).astype(x.dtype)
        return x, cache

    # reshape main stack into (n_sites, g, ...)
    main = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["mamba"])
    lns = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["ln"])
    x, caches = jax.lax.scan(_maybe_remat(cfg, site_body), x,
                             (main, lns, params["lora_a"], params["lora_b"]))
    if "mamba_tail" in params:
        def tail_body(x, lp):
            p, ln = lp
            h = ll.apply_norm(cfg, ln, x)
            out, _ = mamba2.mamba_prefill(cfg, p, h, matmul)
            return x + out.astype(x.dtype), None
        x, _ = jax.lax.scan(tail_body, x, (params["mamba_tail"], params["ln_tail"]))
    return x, caches


def _hybrid_prefill_with_states(cfg, params, x, positions, matmul=None):
    """Prefill that also returns decode states (ssm + kv) — for serving."""
    # For clarity, run prefill twice-structured: collect mamba states per layer
    g = group_size(cfg)
    n_sites = cfg.n_layers // g

    def site_body(x, inp):
        mamba_g, ln_g, lora_a, lora_b = inp

        def inner(x, lp):
            p, ln = lp
            h = ll.apply_norm(cfg, ln, x)
            out, st = mamba2.mamba_prefill(cfg, p, h, matmul)
            return x + out.astype(x.dtype), st

        x, sts = jax.lax.scan(inner, x, (mamba_g, ln_g))
        sp = params["shared"]
        merged = dict(sp["attn"])
        merged["ln1"] = sp["ln1"]
        x, cache = attn_block_prefill(cfg, merged, x, positions, True, matmul,
                                      lora=(lora_a, lora_b), want_cache=True)
        h = ll.apply_norm(cfg, sp["ln2"], x)
        x = x + ll.mlp_apply(cfg, sp["mlp"], h, matmul).astype(x.dtype)
        return x, (sts, cache)

    main = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["mamba"])
    lns = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["ln"])
    x, (mstates, kv) = jax.lax.scan(site_body, x, (main, lns, params["lora_a"], params["lora_b"]))
    tail_states = None
    if "mamba_tail" in params:
        def tail_body(x, lp):
            p, ln = lp
            h = ll.apply_norm(cfg, ln, x)
            out, st = mamba2.mamba_prefill(cfg, p, h, matmul)
            return x + out.astype(x.dtype), st
        x, tail_states = jax.lax.scan(tail_body, x, (params["mamba_tail"], params["ln_tail"]))
    return x, {"mamba": mstates, "kv": kv, "tail": tail_states}


def _hybrid_decode(cfg: ModelConfig, params: dict, x: jax.Array, pos: jax.Array,
                   states, matmul=None):
    g = group_size(cfg)
    n_sites = cfg.n_layers // g

    def site_body(x, inp):
        mamba_g, ln_g, lora_a, lora_b, msts, kv = inp

        def inner(x, lp):
            p, ln, st = lp
            h = ll.apply_norm(cfg, ln, x[:, 0])
            out, new_st = mamba2.mamba_decode(cfg, p, h, st, matmul)
            return x + out[:, None].astype(x.dtype), new_st

        x, new_msts = jax.lax.scan(inner, x, (mamba_g, ln_g, msts))
        sp = params["shared"]
        merged = dict(sp["attn"])
        merged["ln1"] = sp["ln1"]
        x, new_kv = attn_block_decode(cfg, merged, x, pos, kv, True, matmul,
                                      lora=(lora_a, lora_b))
        h = ll.apply_norm(cfg, sp["ln2"], x)
        x = x + ll.mlp_apply(cfg, sp["mlp"], h, matmul).astype(x.dtype)
        return x, (new_msts, new_kv)

    main = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["mamba"])
    lns = jax.tree.map(lambda a: a.reshape((n_sites, g) + a.shape[1:]), params["ln"])
    x, (new_m, new_kv) = jax.lax.scan(
        site_body, x, (main, lns, params["lora_a"], params["lora_b"],
                       states["mamba"], states["kv"]))
    new_tail = None
    if "mamba_tail" in params:
        def tail_body(x, lp):
            p, ln, st = lp
            h = ll.apply_norm(cfg, ln, x[:, 0])
            out, new_st = mamba2.mamba_decode(cfg, p, h, st, matmul)
            return x + out[:, None].astype(x.dtype), new_st
        x, new_tail = jax.lax.scan(tail_body, x, (params["mamba_tail"], params["ln_tail"], states["tail"]))
    return x, {"mamba": new_m, "kv": new_kv, "tail": new_tail}


# ------------------------------------------- layer-typed (granite-4.0-h) --
def _by_period(cfg: ModelConfig, tree):
    """Split every leaf's leading layer axis into (periods, run length)."""
    periods = cfg.n_layers // sum(n for _, n in layer_runs(cfg))
    return jax.tree.map(
        lambda a: a.reshape((periods, a.shape[0] // periods) + a.shape[1:]), tree)


def _by_layer(tree):
    """Inverse of ``_by_period``."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), tree)


def _layer_prefill(cfg, kind, p, x, positions, matmul, want_state, valid_len):
    if kind == "mamba":
        h = ll.apply_norm(cfg, p["ln1"], x)
        out, st = mamba2.mamba_prefill(cfg, p["mamba"], h, matmul, valid_len)
        x = _residual(cfg, x, out)
    else:
        x, st = attn_block_prefill(cfg, p, x, positions, True, matmul,
                                   want_cache=want_state)
    return _ffn(cfg, p, x, 0, matmul), (st if want_state else None)


def _layer_decode(cfg, kind, p, x, pos, st, matmul, page_table):
    if kind == "mamba":
        h = ll.apply_norm(cfg, p["ln1"], x[:, 0])
        out, st = mamba2.mamba_decode(cfg, p["mamba"], h, st, matmul)
        x = _residual(cfg, x, out[:, None])
    elif page_table is None:
        x, st = attn_block_decode(cfg, p, x, pos, st, True, matmul)
    else:
        x, st = attn_block_decode_paged(cfg, p, x, pos, st, page_table, matmul)
    return _ffn(cfg, p, x, 0, matmul), st


def _layered_prefill(cfg: ModelConfig, params: dict, x: jax.Array,
                     positions: jax.Array, matmul=None, want_cache=False,
                     valid_len=None):
    runs = layer_runs(cfg)

    def period(x, pp):
        states = {}
        for i, (kind, _) in enumerate(runs):
            def layer(x, p, kind=kind):
                return _layer_prefill(cfg, kind, p, x, positions, matmul,
                                      want_cache, valid_len)
            x, states[f"r{i}"] = jax.lax.scan(layer, x, pp[f"r{i}"])
        return x, states

    x, states = jax.lax.scan(_maybe_remat(cfg, period), x, _by_period(cfg, params))
    return x, (_by_layer(states) if want_cache else None)


def _layered_decode(cfg: ModelConfig, params: dict, x: jax.Array, pos: jax.Array,
                    state, matmul=None, page_table=None):
    """One token through every layer; attention runs read and write a
    contiguous cache, or the page pools through ``page_table``."""
    runs = layer_runs(cfg)

    def period(x, inp):
        pp, ps = inp
        new = {}
        for i, (kind, _) in enumerate(runs):
            def layer(x, lp, kind=kind):
                return _layer_decode(cfg, kind, lp[0], x, pos, lp[1], matmul,
                                     page_table)
            x, new[f"r{i}"] = jax.lax.scan(layer, x, (pp[f"r{i}"], ps[f"r{i}"]))
        return x, new

    x, new = jax.lax.scan(period, x, (_by_period(cfg, params),
                                      _by_period(cfg, state)))
    return x, _by_layer(new)


def pads_exactly(cfg: ModelConfig) -> bool:
    """Whether a right-padded prefill leaves the raw prompt's decode state,
    so that prompts can be bucketed and the KV paged: every layer is causal
    full attention, or a Mamba-2 layer of a layer-typed stack (its prefill
    masks the pad, and its state lives per slot). Ring caches (swa/chunked)
    and the ssm/zamba2 stacks fold the pad into their state."""
    if cfg.attn_type != "full":
        return False
    return bool(cfg.layer_kinds) or cfg.family not in ("ssm", "hybrid")


# ------------------------------------------------------------------ facade --
def stack_prefill(cfg: ModelConfig, params: dict, x: jax.Array, positions: jax.Array,
                  matmul=None, want_cache=False, valid_len=None):
    """``valid_len`` ((B,) int32), the rows' true lengths of a right-padded
    batch, reaches the layers whose state would take in the pad (Mamba-2 in
    a layer-typed stack); attention needs nothing, being causal."""
    if cfg.layer_kinds:
        return _layered_prefill(cfg, params, x, positions, matmul, want_cache,
                                valid_len)
    if cfg.family == "ssm":
        return _ssm_stack_prefill(cfg, params, x, matmul, want_state=want_cache)
    if cfg.family == "hybrid":
        if want_cache:
            return _hybrid_prefill_with_states(cfg, params, x, positions, matmul)
        return _hybrid_prefill(cfg, params, x, positions, matmul)
    return _attn_stack_prefill(cfg, params, x, positions, matmul, want_cache)


def stack_decode(cfg: ModelConfig, params: dict, x: jax.Array, pos: jax.Array,
                 caches, matmul=None):
    if cfg.layer_kinds:
        return _layered_decode(cfg, params, x, pos, caches, matmul)
    if cfg.family == "ssm":
        return _ssm_stack_decode(cfg, params, x, caches, matmul)
    if cfg.family == "hybrid":
        return _hybrid_decode(cfg, params, x, pos, caches, matmul)
    return _attn_stack_decode(cfg, params, x, pos, caches, matmul)


def stack_decode_paged(cfg: ModelConfig, params: dict, x: jax.Array,
                       pos: jax.Array, pools, page_table: jax.Array,
                       matmul=None):
    """Paged-cache decode facade, for the configs of ``pads_exactly``: ring
    caches (swa/chunked) are already O(window), and the ssm/zamba2 stacks
    keep dense slots (the engine's capability gate). In a layer-typed stack
    the attention layers' KV is paged and the Mamba-2 state is per slot."""
    if not pads_exactly(cfg):
        raise ValueError(
            f"paged decode supports full-attention and layer-typed stacks "
            f"only, not family={cfg.family!r} attn_type={cfg.attn_type!r}")
    if cfg.layer_kinds:
        return _layered_decode(cfg, params, x, pos, pools, matmul, page_table)
    return _attn_stack_decode_paged(cfg, params, x, pos, pools, page_table,
                                    matmul)
