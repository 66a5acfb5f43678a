"""LM facade: embeddings + decoder stack + head, for all assigned archs.

Entry points (all pure functions of (cfg, params, batch)):
  * ``train_logits``  — full-sequence forward for training / evaluation.
  * ``train_loss``    — masked token cross-entropy (f32).
  * ``prefill``       — forward that also returns decode state (KV caches /
                        SSM states) and last-position logits.
  * ``decode_step``   — one-token step against the decode state.

Phi spiking mode (``cfg.spiking`` + ``cfg.phi``): every decoder GEMM operand
is rate-coded into ``phi.timesteps`` binary spike trains by a local LIF
neuron; each timestep's matmul is the Phi decomposition (L1 PWP retrieval +
L2 ±1 COO correction) via the ``kernels.dispatch`` execution policy, which
picks the kernel lowering per call (the model layer never names one: fused
single-pass on a single device, the pjit-safe XLA path inside SPMD regions,
or the ``cfg.phi.impl`` override). Given identical spikes,
Phi mode is exact w.r.t. spiking-dense mode (the paper's losslessness claim,
tested); rate-coded spiking itself approximates the analog model, as in all
spiking-transformer work the paper evaluates.

Modality frontends are stubs per the assignment: pixtral receives
pre-computed patch embeddings, musicgen pre-computed (codebook-summed) frame
embeddings; both enter the decoder as ordinary positions.
"""
from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.patterns import PhiConfig
from repro.distributed.sharding import ParamSpec, is_spec, shard
from repro.kernels import dispatch
from repro.models import layers as ll
from repro.models import transformer
from repro.models.config import ModelConfig
from repro.snn.lif import LIFConfig, lif_update


# ------------------------------------------------------------------ specs ---
def lm_specs(cfg: ModelConfig) -> dict:
    dt = cfg.param_dtype
    sp = {
        "embed": ParamSpec((cfg.vocab, cfg.d_model), ("vocab", "fsdp"), dt, scale=0.02),
        "head": ParamSpec((cfg.d_model, cfg.vocab), ("fsdp", "vocab"), dt),
        "ln_f": ll.norm_spec(cfg),
        "decoder": transformer.decoder_specs(cfg),
    }
    if cfg.phi is not None:
        sp["decoder"] = _inject_phi_specs(cfg, sp["decoder"])
    return sp


_PHI_WEIGHTS = ("wq", "wk", "wv", "wo", "w1", "w2", "w3",
                "wz", "wx", "wB", "wC", "wdt")


def _inject_phi_specs(cfg: ModelConfig, tree: Any) -> Any:
    """Add per-weight Phi state (patterns + PWP) next to each spiking GEMM."""
    phi = cfg.phi

    def eligible(v) -> bool:
        if not is_spec(v) or v.shape[-2] % phi.k:
            return False
        # plain 2D GEMM weight, possibly layer-stacked (expert tensors are
        # contracted by einsum, not the injectable mm — excluded by ndim/axes)
        return len(v.shape) == 2 or (len(v.shape) == 3 and v.axes[0] == "layers")

    def walk(node):
        if not isinstance(node, dict):
            return node
        out = dict(node)
        for k, v in node.items():
            if isinstance(v, dict):
                out[k] = walk(v)
            elif k in _PHI_WEIGHTS and eligible(v):
                K, N = v.shape[-2], v.shape[-1]
                T = K // phi.k
                lead = v.shape[:-2]
                lead_ax = v.axes[:-2]
                # PWPs are 8× the weight bytes (the paper's memory-traffic
                # challenge): shard the K-tile dim on 'pwp_tiles' (-> 'data',
                # even in serve mode where weights replicate over data) and N
                # on the weight's own N axis; shape_aware_spec drops
                # duplicate mesh axes (e.g. w2's fsdp N under train rules).
                entry = {
                    "patterns": ParamSpec(
                        lead + (T, phi.q, phi.k), lead_ax + ("pattern", None, None),
                        jnp.int8, init="zeros"),
                    "pwp": ParamSpec(
                        lead + (T, phi.q + 1, N), lead_ax + ("pwp_tiles", None, v.axes[-1]),
                        jnp.int8 if phi.pwp_int8 else cfg.param_dtype, init="zeros"),
                    # Calibration pattern-usage histogram (replicated; tiny).
                    # Rides in the params tree so it survives checkpoints;
                    # the execution policy reads it from its host-side
                    # registry (usage must be concrete at trace time).
                    "usage": ParamSpec(
                        lead + (T, phi.q + 1), lead_ax + (None, None),
                        jnp.int32, init="zeros"),
                }
                if phi.pwp_int8:
                    entry["pwp_scale"] = ParamSpec(
                        lead + (T, phi.q + 1), lead_ax + ("pwp_tiles", None),
                        jnp.float32, init="zeros")
                out["phi_" + k] = entry
        return out

    return walk(tree)


def _phi_eligible(phi: PhiConfig, name: str, w: Any) -> bool:
    """Whether the GEMM ``name`` with per-layer weight ``w`` carries Phi
    state: the rule ``_inject_phi_specs`` applies to the spec tree, read off
    the weight itself (layer-stacked weights arrive sliced to 2D)."""
    return name in _PHI_WEIGHTS and w.ndim == 2 and w.shape[-2] % phi.k == 0


def split_phi_state(tree: Any) -> tuple[Any, dict]:
    """Split a params(-spec) tree into (trainable, phi_state).

    ``phi_*`` subtrees (patterns / PWPs / scales) are calibration-derived
    state, not trainable parameters: the int8 patterns are non-differentiable
    (``jax.grad`` rejects integer inputs) and PWPs are recomputed from the
    weights by (re)calibration, not descended on. The optimizer and grad
    transforms must only ever see the trainable half.
    """
    if not isinstance(tree, dict):
        return tree, {}
    train: dict = {}
    frozen: dict = {}
    for k, v in tree.items():
        if k.startswith("phi_"):
            frozen[k] = v
        elif isinstance(v, dict):
            t, f = split_phi_state(v)
            train[k] = t
            if f:
                frozen[k] = f
        else:
            train[k] = v
    return train, frozen


def merge_phi_state(train: Any, frozen: dict) -> Any:
    """Inverse of ``split_phi_state``: graft the phi state back in."""
    if not frozen:
        return train
    out = dict(train)
    for k, v in frozen.items():
        if k in out and isinstance(out.get(k), dict) and not k.startswith("phi_"):
            out[k] = merge_phi_state(out[k], v)
        else:
            out[k] = v
    return out


# ---------------------------------------------------------- spiking matmul ---
# Logical (K, N) axes of every Phi-eligible weight — used to derive the
# shard_map specs of the distributed spiking matmul.
_WEIGHT_AXES = {
    "wq": ("fsdp", "heads"), "wk": ("fsdp", "kv_heads"), "wv": ("fsdp", "kv_heads"),
    "wo": ("heads", "fsdp"), "w1": ("fsdp", "mlp"), "w3": ("fsdp", "mlp"),
    "w2": ("mlp", "fsdp"), "wz": ("fsdp", "heads"), "wx": ("fsdp", "heads"),
    "wB": ("fsdp", "state"), "wC": ("fsdp", "state"), "wdt": ("fsdp", "heads"),
}


def _phi_sharded_matmul(cfg, spikes, w, patterns, pwp, name, budget, pwp_scale=None):
    """Distributed Phi matmul under shard_map.

    Column-parallel weights (K replicated): rows stay batch-sharded, PWP/W
    N-sharded on 'model' — no communication. Row-parallel weights (K on
    'model', e.g. wo/w2 in serve mode): each device computes the partial sum
    of its K-tiles (its PWP slice + its COO columns) and a psum('model')
    completes the reduction — the Phi analogue of Megatron row-parallelism.

    Which kernel lowering runs is NOT decided here: every path hands the
    call to ``kernels.dispatch`` and the execution policy resolves the impl
    from context — fused on a single device, mesh-aware re-gating on the
    local per-shard shape inside the shard_map body (``spmd_local_*``
    reasons), an explicit ``cfg.phi.impl`` override everywhere it is safe.
    The site's calibration usage histogram is sliced along the K-partition
    axis before tracing (``dispatch.shard_usage_histogram``): under
    row-parallel ``k_ax`` each shard owns T/nk of the T K-partitions, so
    the policy gates on the max over shard slices; under column-parallel
    the bank replicates and the histogram passes through whole.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import axis_size, current_mesh, resolve_spec

    override = cfg.phi.impl if cfg.phi is not None else None
    mesh = current_mesh()
    if mesh is None:
        return dispatch.phi_matmul(spikes, w, patterns, pwp,
                                   site=f"lm.{name}", config_override=override,
                                   nnz_budget=budget,
                                   pwp_scale=pwp_scale)
    axes = _WEIGHT_AXES[name]

    def _ax(logical, dim):
        p = resolve_spec((logical,))
        ax = p[0] if len(p) else None
        if ax is None:
            return None
        return ax if dim % axis_size(mesh, ax) == 0 else None  # divisibility fallback

    k_ax = _ax(axes[0], w.shape[0])
    n_ax = _ax(axes[1], w.shape[1])
    bd = _ax("batch", spikes.shape[1])

    def _names(ax):
        return set(ax if isinstance(ax, tuple) else (ax,)) if ax is not None else set()

    # A PartitionSpec may use each mesh axis at most once. Batch sharding of
    # the spike rows wins; a weight K/N axis that would reuse one of its mesh
    # axes (e.g. fsdp→data colliding with batch→data under TRAIN_RULES) is
    # dropped — the weight simply replicates over that axis.
    if _names(k_ax) & _names(bd):
        k_ax = None
    if _names(n_ax) & (_names(bd) | _names(k_ax)):
        n_ax = None
    # spikes = (T, B, …, K): timestep leads, batch is dim 1.
    mid = (None,) * (spikes.ndim - 3)

    # Per-shard usage view for the mesh-aware gate: the body is traced once
    # for all shards, so slice the calibration histogram down to the local
    # T/nk K-partitions (max over shard slices — conservative, and exactness
    # never depends on the set choice: out-of-set matches fall through to
    # the L2 correction).
    nk = axis_size(mesh, k_ax)
    usage = dispatch.shard_usage_histogram(
        dispatch.get_policy().usage_for(f"lm.{name}"), nk)

    def body(s_loc, w_loc, pats_loc, pwp_loc, scale_loc):
        flat = s_loc.reshape(-1, s_loc.shape[-1])
        # The policy sees the shard_map axis env and re-gates on the local
        # per-shard problem (Pallas lowerings when viable, coo otherwise).
        out = dispatch.phi_matmul(flat, w_loc, pats_loc, pwp_loc,
                                  site=f"lm.{name}.spmd",
                                  config_override=override,
                                  nnz_budget=budget,
                                  pwp_scale=scale_loc,
                                  usage=usage)
        if k_ax is not None:
            out = jax.lax.psum(out, k_ax)
        return out.reshape(s_loc.shape[:-1] + (w_loc.shape[-1],))

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, bd, *mid, k_ax), P(k_ax, n_ax),
                  P(k_ax, None, None), P(k_ax, None, n_ax),
                  P(k_ax, None) if pwp_scale is not None else None),
        out_specs=P(None, bd, *mid, n_ax),
        check_vma=False,
    )(spikes, w, patterns, pwp, pwp_scale)


def make_matmul(cfg: ModelConfig):
    """Returns the GEMM implementation for this config (dense / spiking-Phi)."""
    if not cfg.spiking:
        return None  # default dense mm

    phi = cfg.phi or PhiConfig()
    lif = LIFConfig(decay=0.5, threshold=1.0)
    spike_impl = getattr(cfg, "spike_impl", "phi")

    def mm(x: jax.Array, p: dict, name: str) -> jax.Array:
        w = p[name]
        phi_p = p.get("phi_" + name)
        # Rate-code the operand into T binary spike trains (local LIF).
        xf = x.astype(jnp.float32)

        def step(v, _):
            s, v2 = lif_update(v, xf, lif)
            return v2, s

        _, spikes = jax.lax.scan(step, jnp.zeros_like(xf), None, length=phi.timesteps)
        # spikes: (T, ..., K)
        if phi_p is None:
            out = jnp.einsum("t...k,kn->t...n", spikes.astype(cfg.compute_dtype),
                             w.astype(cfg.compute_dtype))
        elif spike_impl != "phi":
            # Oracle comparison mode (cfg.spike_impl names a lowering, e.g.
            # "ref"): a per-call override — the one context where the model
            # layer intentionally pins the impl.
            out = dispatch.phi_matmul(spikes, w.astype(jnp.float32),
                                      phi_p["patterns"],
                                      phi_p["pwp"].astype(jnp.float32),
                                      site=f"lm.{name}.oracle",
                                      override=spike_impl)
        else:
            pwp_v = phi_p["pwp"]
            if pwp_v.dtype != jnp.int8:
                pwp_v = pwp_v.astype(jnp.float32)
            out = _phi_sharded_matmul(
                cfg, spikes, w.astype(jnp.float32), phi_p["patterns"],
                pwp_v, name, phi.nnz_budget, pwp_scale=phi_p.get("pwp_scale"))
        # rate decoding: average over timesteps, rescale by threshold
        return (out.mean(0) * (2.0 * lif.threshold)).astype(x.dtype)

    return mm


def _capture_phi_spikes(cfg: ModelConfig, params: dict,
                        sample_batch: dict) -> dict[str, list]:
    """Shared spike-capture pass of the phi-LM paths.

    Runs the forward with dense math and an instrumented matmul that
    rate-codes every Phi-eligible GEMM operand and emits the spike trains
    through ``io_callback``. Returns {call-site key: [spike arrays]} with
    keys ``f"{weight_name}#{occurrence}"`` — the scheme the params-tree
    walks of ``calibrate_lm_phi`` and ``capture_lm_phi_traces`` mirror.
    """
    import numpy as np
    from jax.experimental import io_callback

    captured: dict[str, list] = {}
    trace_counter: dict[str, int] = {}
    lif = LIFConfig()
    phi = cfg.phi

    def capture_mm(x, p, name):
        w = p[name]
        if _phi_eligible(phi, name, w):
            key = f"{name}#{trace_counter.get(name, 0)}"
            trace_counter[name] = trace_counter.get(name, 0) + 1
            xf = x.astype(jnp.float32)

            def step(v, _):
                s, v2 = lif_update(v, xf, lif)
                return v2, s

            _, spikes = jax.lax.scan(step, jnp.zeros_like(xf), None, length=phi.timesteps)
            io_callback(
                lambda s, key=key: captured.setdefault(key, []).append(np.asarray(s)),
                None, spikes, ordered=True)
        return x @ w.astype(x.dtype)

    # capture pass (dense math, spike stats only)
    out, _ = _forward(cfg.with_(spiking=False), params, sample_batch, matmul=capture_mm)
    # ordered io_callbacks run asynchronously: flush them before reading
    # ``captured``, or the consumer walk races an empty dict.
    jax.block_until_ready(out)
    jax.effects_barrier()
    return captured


def capture_lm_phi_traces(cfg: ModelConfig, params: dict,
                          sample_batch: dict) -> list:
    """Capture simulator traces from a *calibrated* phi-LM's real spikes.

    Re-runs the spike-capture pass and pairs each call site's pooled spike
    rows with the ``phi_*`` pattern bank already in the params tree,
    yielding one ``repro.sim.LayerTrace`` per Phi GEMM site (stacked-layer
    sites use the pooled patterns, like calibration did). The LM-side hook
    for the cycle-approximate accelerator simulator.
    """
    import numpy as np
    from repro.sim.trace import trace_from_acts

    captured = _capture_phi_spikes(cfg, params, sample_batch)
    traces = []
    walk_counter: dict[str, int] = {}

    def walk(node):
        if not isinstance(node, dict):
            return
        for k, v in node.items():
            if isinstance(v, dict) and not k.startswith("phi_"):
                walk(v)
            if "phi_" + k in node:
                key = f"{k}#{walk_counter.get(k, 0)}"
                walk_counter[k] = walk_counter.get(k, 0) + 1
                if key not in captured:
                    continue
                phi_p = node["phi_" + k]
                pats = np.asarray(phi_p["patterns"])
                if pats.ndim == 4:      # stacked layers: pooled patterns
                    pats = pats[0]
                w = np.asarray(node[k])
                spk = np.concatenate(
                    [s.reshape(-1, w.shape[-2]) for s in captured[key]])
                traces.append(trace_from_acts(
                    f"lm.{key}", spk, pats.astype(np.uint8), w.shape[-1]))

    walk(params)
    return traces


def calibrate_lm_phi(cfg: ModelConfig, params: dict, sample_batch: dict) -> dict:
    """Build the Phi state from real spike statistics.

    ``params`` may hold the zero-initialised Phi state of ``lm_specs(cfg)``
    or the weights alone; either way every Phi-eligible GEMM of ``cfg``
    gets its ``phi_*`` entry (patterns, PWPs, usage histogram).

    The capture pass runs the forward with an instrumented matmul that emits
    each GEMM's spike trains through ``io_callback``. Under scan-over-layers
    each traced call site fires once per layer iteration, so the captured
    list per call site holds every layer's spikes; patterns are calibrated on
    the pooled spikes (shared across a stack's layers — PWPs are still
    per-layer via vmap against each layer's weights). Call sites are keyed by
    (weight name, occurrence), which matches the parameter-tree traversal
    order by construction (both follow dict insertion order).
    """
    import numpy as np
    from repro.core.patterns import calibrate as _calib, pattern_usage, \
        pattern_weight_products

    stats: dict[str, Any] = {}
    phi = cfg.phi
    captured = _capture_phi_spikes(cfg, params, sample_batch)

    walk_counter: dict[str, int] = {}

    def walk(node, spec):
        if not isinstance(node, dict):
            return node
        out = dict(node)
        for k, v in list(node.items()):
            if isinstance(v, dict) and not k.startswith("phi_"):
                out[k] = walk(v, spec.get(k, {}))
            if "phi_" + k in spec:
                key = f"{k}#{walk_counter.get(k, 0)}"
                walk_counter[k] = walk_counter.get(k, 0) + 1
                if key not in captured:
                    continue
                w = np.asarray(node[k], np.float32)
                spk = np.concatenate([s.reshape(-1, w.shape[-2]) for s in captured[key]])
                pats = _calib(spk, phi)
                # Pattern-usage histogram of the calibration spikes: stored
                # in the params tree (checkpoint persistence) AND registered
                # with the execution policy so its usage gate can size the
                # fused_prefetch PWP gather at trace time (in-graph params
                # are tracers there; the registry copy is concrete).
                usage = pattern_usage(spk, pats)
                dispatch.get_policy().register_usage(f"lm.{k}", usage)
                if w.ndim == 2:
                    pwp = pattern_weight_products(jnp.asarray(pats), jnp.asarray(w))
                    usage_arr = usage
                else:  # stacked layers: pooled patterns, per-layer PWPs
                    pwp = jax.vmap(
                        lambda wl: pattern_weight_products(jnp.asarray(pats), wl)
                    )(jnp.asarray(w))
                    pats = np.broadcast_to(pats, (w.shape[0],) + pats.shape)
                    usage_arr = np.broadcast_to(usage, (w.shape[0],) + usage.shape)
                from repro.core.assign import phi_stats
                stats[key] = phi_stats(spk, pats[0] if pats.ndim == 4 else pats)
                out["phi_" + k] = {
                    "patterns": jnp.asarray(pats, jnp.int8),
                    "pwp": jnp.asarray(pwp, cfg.param_dtype),
                    "usage": jnp.asarray(
                        np.clip(usage_arr, 0, np.iinfo(np.int32).max),
                        jnp.int32),
                }
        return out

    new_params = walk(params, lm_specs(cfg))
    return new_params, stats


def calibrate_lm_phi_budgeted(cfg: ModelConfig, params: dict,
                              sample_batch: dict) -> tuple[ModelConfig, dict, float]:
    """``calibrate_lm_phi``, then size the budgeted lowerings' L2 capacity
    from what calibration saw: twice the densest site's residual density
    plus 0.05, at most 0.9. Returns (cfg with that ``nnz_budget``, params,
    the densest site's L2 density)."""
    import dataclasses

    params, stats = calibrate_lm_phi(cfg, params, sample_batch)
    maxd = max(s.l2_density for s in stats.values())
    cfg = cfg.with_(phi=dataclasses.replace(
        cfg.phi, nnz_budget=min(0.9, 2 * maxd + 0.05)))
    return cfg, params, maxd


# ---------------------------------------------------------------- forward ---
def _embed_inputs(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """Token + stub-frontend embedding -> (B, S_total, D) in compute dtype."""
    parts = []
    if cfg.frontend == "patches":
        parts.append(batch["patch_embeds"].astype(cfg.compute_dtype))
    if cfg.frontend == "frames":
        x = batch["frame_embeds"].astype(cfg.compute_dtype)
        return shard(x, "batch", "seq", "act_embed")
    parts.append(_embed_tokens(cfg, params, batch["tokens"]))
    x = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return shard(x, "batch", "seq", "act_embed")


def _embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    """Token embeddings in the compute dtype, times ``embedding_multiplier``."""
    x = params["embed"][tokens].astype(cfg.compute_dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def _logits(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    x = ll.apply_norm(cfg, params["ln_f"], x)
    logits = x.astype(cfg.compute_dtype) @ params["head"].astype(cfg.compute_dtype)
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return shard(logits, "batch", "seq", "act_vocab")


def _forward(cfg: ModelConfig, params: dict, batch: dict, matmul=None,
             want_cache: bool = False, valid_len: jax.Array | None = None):
    x = _embed_inputs(cfg, params, batch)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    mm = matmul if matmul is not None else make_matmul(cfg)
    x, caches = transformer.stack_prefill(cfg, params["decoder"], x, positions,
                                          matmul=mm, want_cache=want_cache,
                                          valid_len=valid_len)
    return x, caches


def train_logits(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    x, _ = _forward(cfg, params, batch)
    return _logits(cfg, params, x)


def train_loss(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    """Masked next-token cross-entropy. labels: (B, S_total) int32, -1 = pad."""
    logits = train_logits(cfg, params, batch)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    take = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return -(take * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def prefill(cfg: ModelConfig, params: dict, batch: dict):
    """Returns (last-position logits (B, V), decode state)."""
    x, caches = _forward(cfg, params, batch, want_cache=True)
    logits = _logits(cfg, params, x[:, -1:])
    return logits[:, 0], caches


def prefill_padded(cfg: ModelConfig, params: dict, batch: dict,
                   last_pos: jax.Array):
    """Prefill a right-padded prompt batch, reading logits at the TRUE last
    token ``last_pos`` ((B,) int32, 0-based) instead of the padded end.

    Right-padding is exact under causal *full* attention: rows at
    positions < true length never attend to the pad tail, and decode later
    masks (then progressively overwrites) the junk cache slots past
    ``last_pos``. The Mamba-2 layers of a layer-typed stack are told the
    true length and return the state there. Ring/windowed caches (swa /
    chunked) and the ssm/zamba2 stacks fold the pad tokens into state —
    callers gate on ``transformer.pads_exactly`` (the serve engine's prompt
    bucketing does).
    """
    x, caches = _forward(cfg, params, batch, want_cache=True,
                         valid_len=last_pos.astype(jnp.int32) + 1)
    idx = last_pos.astype(jnp.int32)[:, None, None]
    sel = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
    logits = _logits(cfg, params, sel)
    return logits[:, 0], caches


def decode_step(cfg: ModelConfig, params: dict, token: jax.Array, pos: jax.Array,
                caches, embeds: jax.Array | None = None):
    """token (B,) int32 (or embeds (B, D) for frame frontends); pos (B,) int32."""
    if embeds is not None:
        x = embeds[:, None].astype(cfg.compute_dtype)
    else:
        x = _embed_tokens(cfg, params, token)[:, None]
    x = shard(x, "batch", None, "act_embed")
    mm = make_matmul(cfg)
    x, new_caches = transformer.stack_decode(cfg, params["decoder"], x, pos, caches,
                                             matmul=mm)
    logits = _logits(cfg, params, x)
    return logits[:, 0], new_caches


def decode_step_paged(cfg: ModelConfig, params: dict, token: jax.Array,
                      pos: jax.Array, pools: Any, page_table: jax.Array):
    """One-token decode against a paged KV cache.

    Identical to ``decode_step`` except the attention caches are the shared
    page pools from ``init_paged_state`` plus the engine's page table
    ((B, logical_pages) int32, -1 = unmapped) — see
    ``serve/page_manager.py`` for the layout and the bitwise-exactness
    contract — and, in a layer-typed stack, the Mamba-2 layers' state per
    slot. Configs of ``transformer.pads_exactly`` only (gated in
    ``transformer.stack_decode_paged``).
    """
    x = _embed_tokens(cfg, params, token)[:, None]
    x = shard(x, "batch", None, "act_embed")
    mm = make_matmul(cfg)
    x, new_pools = transformer.stack_decode_paged(
        cfg, params["decoder"], x, pos, pools, page_table, matmul=mm)
    logits = _logits(cfg, params, x)
    return logits[:, 0], new_pools


# ----------------------------------------------------------- input specs ---
def input_batch_specs(cfg: ModelConfig, batch: int, seq: int, with_labels: bool,
                      dtype=jnp.int32) -> dict:
    """ShapeDtypeStruct stand-ins for a model input batch (dry-run pattern)."""
    sp: dict = {}
    if cfg.frontend == "patches":
        P = cfg.frontend_positions
        sp["tokens"] = jax.ShapeDtypeStruct((batch, seq - P), dtype)
        sp["patch_embeds"] = jax.ShapeDtypeStruct((batch, P, cfg.d_model), cfg.compute_dtype)
    elif cfg.frontend == "frames":
        sp["frame_embeds"] = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), cfg.compute_dtype)
    else:
        sp["tokens"] = jax.ShapeDtypeStruct((batch, seq), dtype)
    if with_labels:
        sp["labels"] = jax.ShapeDtypeStruct((batch, seq), dtype)
    return sp


def dummy_batch(cfg: ModelConfig, batch: int, seq: int, with_labels: bool,
                key: jax.Array | None = None) -> dict:
    key = key if key is not None else jax.random.PRNGKey(0)
    out = {}
    for k, s in input_batch_specs(cfg, batch, seq, with_labels).items():
        if jnp.issubdtype(s.dtype, jnp.integer):
            hi = 2 if k == "labels" else cfg.vocab
            out[k] = jax.random.randint(key, s.shape, 0, min(hi, cfg.vocab), s.dtype)
        else:
            out[k] = jax.random.normal(key, s.shape, jnp.float32).astype(s.dtype) * 0.5
    return out


def extend_caches(cfg: ModelConfig, caches: Any, new_len: int) -> Any:
    """Grow linear KV caches to ``new_len`` slots (ring caches stay fixed).

    Prefill returns caches sized to the prompt; the serving engine extends
    them to the generation budget before decoding.
    """

    def pad_kv(kv, win):
        k, v = kv
        cur = k.shape[-3]
        target = min(new_len, win) if win is not None else new_len
        if target <= cur:
            return (k, v)
        pad = [(0, 0)] * k.ndim
        pad[-3] = (0, target - cur)
        return (jnp.pad(k, pad), jnp.pad(v, pad))

    if cfg.layer_kinds:
        return jax.tree.map(
            lambda kind, c: pad_kv(c, None) if kind == "kv" else c,
            state_kinds(cfg), caches)
    if cfg.family == "ssm":
        return caches
    if cfg.family == "hybrid":
        out = dict(caches)
        out["kv"] = pad_kv(caches["kv"], None)
        return out
    g = transformer.group_size(cfg)
    return tuple(
        pad_kv(caches[i], transformer._cache_window(cfg, cfg.is_global_layer(i)))
        for i in range(g)
    )


# ------------------------------------------------------------ cache specs ---
def decode_state_specs(cfg: ModelConfig, batch: int, context: int) -> Any:
    """ShapeDtypeStruct tree matching what ``prefill`` returns — derived via
    ``jax.eval_shape`` on prefill itself so it can never drift."""
    from repro.distributed.sharding import specs_to_sds

    params_sds = specs_to_sds(lm_specs(cfg))
    batch_sds = input_batch_specs(cfg, batch, context, with_labels=False)
    out = jax.eval_shape(partial(prefill, cfg), params_sds, batch_sds)
    return out[1]


def init_decode_state(cfg: ModelConfig, batch: int, context: int) -> Any:
    """Concrete zero-initialised decode state (serving engine cold start)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        decode_state_specs(cfg, batch, context),
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def state_kinds(cfg: ModelConfig) -> Any:
    """How each part of the decode state of a ``transformer.pads_exactly``
    config is held, as a prefix of its tree: ``"kv"`` (a sequence axis,
    paged in the engine's page pool) or ``"slot"`` (a layer-typed stack's
    Mamba-2 state, one entry per slot). Every leaf has its layers on axis 0
    and its slots (or pool pages) on axis 1."""
    if not cfg.layer_kinds:
        return "kv"
    return {f"r{i}": "kv" if kind == "attention" else "slot"
            for i, (kind, _) in enumerate(transformer.layer_runs(cfg))}


def state_leaves(cfg: ModelConfig, state: Any, kind: str) -> list:
    """The leaves of decode state ``state`` held as ``kind`` ("kv"/"slot")."""
    kinds = state_kinds(cfg)
    if not isinstance(kinds, dict):
        return jax.tree.leaves(state) if kind == "kv" else []
    return [leaf for name, k in kinds.items() if k == kind
            for leaf in jax.tree.leaves(state[name])]


def paged_state_specs(cfg: ModelConfig, num_pages: int, page_size: int, *,
                      slots: int = 1) -> Any:
    """ShapeDtypeStruct tree of the paged decode state: every KV leaf's
    (batch, seq) axes become (num_pages + 1, page_size) — one pool shared by
    all slots, plus the reserved scratch page (see
    ``serve/page_manager.py``) — and a layer-typed stack's recurrent state
    keeps ``slots`` entries. Derived from ``decode_state_specs`` at
    batch=1/context=page_size so layout can never drift from prefill's."""
    if not transformer.pads_exactly(cfg):
        raise ValueError(
            f"paged state supports full-attention and layer-typed stacks "
            f"only, not family={cfg.family!r} attn_type={cfg.attn_type!r}")
    specs = decode_state_specs(cfg, 1, page_size)

    def mk(kind, tree):
        n = num_pages + 1 if kind == "kv" else slots
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((s.shape[0], n) + s.shape[2:], s.dtype),
            tree)

    return jax.tree.map(mk, state_kinds(cfg), specs)


def init_paged_state(cfg: ModelConfig, num_pages: int, page_size: int, *,
                     slots: int = 1) -> Any:
    """Concrete zero-initialised paged decode state (paged serving cold
    start)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_state_specs(cfg, num_pages, page_size, slots=slots),
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
