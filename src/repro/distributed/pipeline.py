"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

The production dry-run uses the 'pod' axis as pure DP (2 pods benchmark
better as DP at this scale — EXPERIMENTS.md), but at deeper pod counts PP
over the DCI is the standard alternative; this module provides the
schedulable primitive and its correctness contract.

``pipeline_apply`` runs a stage function over ``n_stages`` mesh shards:
stage s holds the layer slice ``params[s]``; microbatches enter stage 0 and
flow stage-to-stage via ``ppermute`` on a classic GPipe fill/drain schedule
(n_micro + n_stages − 1 ticks). Activations live only on the wire and in the
per-stage working register — O(1) activation memory per stage per tick.

Bubble fraction = (S−1)/(M+S−1); the test asserts exact equivalence with
sequential layer execution.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params, x_micro: jax.Array, mesh: Mesh,
                   axis: str = "pod"):
    """Run a layer-sliced computation as a pipeline over ``axis``.

    stage_fn(stage_params, x) -> y           (one stage's computation)
    params: pytree with leading dim == n_stages (sliced per stage)
    x_micro: (n_micro, micro_batch, ...) microbatched input (replicated)
    Returns (n_micro, micro_batch, ...) outputs (replicated).
    """
    S = mesh.shape[axis]
    M = x_micro.shape[0]
    ticks = M + S - 1

    def body(params_loc, xm):
        # params_loc: stage slice with leading dim 1 — squeeze it.
        p_loc = jax.tree.map(lambda a: a[0], params_loc)
        sid = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(S - 1)]

        zero = jnp.zeros_like(xm[0])
        outs0 = jnp.zeros_like(xm)

        def tick(carry, t):
            wire, outs = carry
            # stage 0 injects microbatch t (when available)
            inject = jnp.where(t < M, t, 0)
            x_in = jnp.where(sid == 0, xm[inject], wire)
            y = stage_fn(p_loc, x_in)
            # last stage emits its result for microbatch (t − S + 1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (sid == S - 1) & (t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, outs[out_idx]), out_idx, 0)
            # forward the wire to the next stage
            wire = jax.lax.ppermute(y, axis, perm)
            return (wire, outs), None

        (wire, outs), _ = jax.lax.scan(tick, (zero, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast via psum of masked
        outs = jnp.where(sid == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    pspec = jax.tree.map(lambda _: P(axis), params)
    return shard_map(
        body, mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )(params, x_micro)


def bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
