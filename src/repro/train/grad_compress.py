"""int8 error-feedback gradient compression for the cross-pod hop.

At 2+ pods the data-parallel gradient all-reduce crosses the (slow) DCI.
Standard trick (1-bit Adam lineage; Seide et al., Karimireddy et al.):
all-reduce full-precision *within* the pod (fast ICI) but exchange int8
quantised gradients *across* pods, feeding the quantisation error back into
the next step so convergence is preserved.

Realised with a *partial-manual* shard_map over only the 'pod' axis: inside,
each pod computes the gradient of its own local-batch mean loss (the 'data'
and 'model' axes stay auto/pjit-managed, so FSDP/TP collectives remain
intra-pod); the cross-pod reduction is then an explicit int8 psum('pod').
The error-feedback residual is carried in the optimizer state under "ef".
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def _quantize(x: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _compress_reduce(g: jax.Array, e: jax.Array, npod: int):
    """Per-pod gradient + error feedback -> cross-pod int8 mean + new error."""
    x = g.astype(jnp.float32) + e
    q, scale = _quantize(x)
    deq = q.astype(jnp.float32) * scale
    new_e = x - deq
    tot = jax.lax.psum(q.astype(jnp.float32) * scale, "pod")
    return (tot / npod).astype(g.dtype), new_e


def pod_compressed_grads(loss_fn, params, batch, ef, mesh):
    """Returns (loss, grads, new_ef): grads are the cross-pod int8-EF mean of
    per-pod gradients; loss is the cross-pod mean loss.

    loss_fn(params, batch) must be a *mean* over the batch it sees.
    """
    npod = mesh.shape["pod"]

    def _strip_pod(v):
        if isinstance(v, tuple):
            out = tuple(a for a in v if a != "pod")
            return out if len(out) > 1 else (out[0] if out else None)
        return None if v == "pod" else v

    def inner(params, batch, ef):
        # Inside the pod-manual region the model's sharding constraints must
        # not mention 'pod' (it is a Manual axis here).
        from repro.distributed import sharding as shd

        inner_rules = {k: _strip_pod(v) for k, v in shd.current_rules().items()}
        with shd.use_rules(inner_rules, shd.current_mesh()):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        out = jax.tree.map(partial(_compress_reduce, npod=npod), grads, ef)
        def is_pair(x):
            return isinstance(x, tuple)
        new_grads = jax.tree.map(lambda o: o[0], out, is_leaf=is_pair)
        new_ef = jax.tree.map(lambda o: o[1], out, is_leaf=is_pair)
        return jax.lax.pmean(loss, "pod"), new_grads, new_ef

    def pspec(tree, podded: bool):
        return jax.tree.map(
            lambda x: P(*(("pod",) if podded else (None,)) + (None,) * (x.ndim - 1)),
            tree)

    def rep(tree):
        return jax.tree.map(lambda x: P(), tree)
    return shard_map(
        inner, mesh=mesh, axis_names={"pod"},
        in_specs=(rep(params), pspec(batch, True), rep(ef)),
        out_specs=(P(), rep(params), rep(params)),
        check_vma=False,
    )(params, batch, ef)
