"""Numerics that every architecture's plain reference and its control share.
Each architecture's forward lives in ``chipbench/refs/<module>.py`` (the
interface is set out in ``chipbench/refs/__init__.py``); nothing here or
there imports the program under test.

* Every matmul runs in float32 at ``precision="highest"``.
* Spiking (the configuration's ``spiking`` block): every weight GEMM input
  is rate-coded into ``timesteps`` binary spike trains by a leaky
  integrate-and-fire neuron (v <- decay*v + x; spike where v >= threshold;
  hard reset), each train is multiplied by W densely, and the output is the
  mean over timesteps times 2*threshold. The Phi decomposition is exact, so
  a dense product of the same spikes is its reference.
* Control (``quant="int8"``): every weight matmul in int8: activations
  scaled per row, weights per output column, symmetric, products exact and
  rescaled in float32. Spikes are exact in int8.
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
import numpy as np


def quant_rows(x, axis):
    """Symmetric int8 quantisation along ``axis`` (absmax / 127), returned
    dequantised in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def matmul(x, w, quant: str | None):
    """``x @ w`` at ``highest``, or in int8 where ``quant`` says so."""
    if quant == "int8":
        x = quant_rows(x, -1)
        w = quant_rows(w, 0)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(x, w, precision="highest")


def gemm(a, x, w, quant):
    """A weight GEMM, spiking when the architecture says so. ``a`` has
    ``timesteps`` (0: not spiking), ``lif_decay`` and ``lif_threshold``."""
    if not a.timesteps:
        return matmul(x, w, quant)
    v = jnp.zeros_like(x)
    out = 0.0
    for _ in range(a.timesteps):
        v = v * a.lif_decay + x
        s = (v >= a.lif_threshold).astype(jnp.float32)
        v = v * (1.0 - s)
        out = out + matmul(s, w, quant)
    return out / a.timesteps * (2.0 * a.lif_threshold)


def rope(x, theta: float):
    """x (S, H, D): rotate the two halves by position-dependent angles."""
    S, _, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def padded_len(n: int, floor: int = 256) -> int:
    """Sequence length a forward of ``n`` tokens is run at: the next power
    of two, at least ``floor`` (the model is causal, so the pad tail changes
    nothing before it, and few lengths means few compiles)."""
    b = floor
    while b < n:
        b *= 2
    return b


def gaps(logits: Callable, prompt: np.ndarray, served: list[int],
         quant: str | None = None) -> np.ndarray:
    """For each served token, how far its logit lies below the reference's
    best at its position. ``logits(quant, tokens)`` is the architecture's
    forward: (S, vocab) float32 logits of one padded sequence ``tokens``
    (S,), ``quant`` None for the reference. With ``quant`` set, the gap is
    that of the token the control puts first there instead (the control's
    reading)."""
    seq = np.concatenate([np.asarray(prompt, np.int64),
                          np.asarray(served[:-1], np.int64)]).astype(np.int32)
    n = len(seq)
    padded = np.zeros(padded_len(n), np.int32)
    padded[:n] = seq
    pos = np.arange(len(prompt) - 1, n)
    ref = logits(None, jnp.asarray(padded))[pos]
    if quant is None:
        pick = jnp.asarray(np.asarray(served, np.int32))
    else:
        pick = logits(quant, jnp.asarray(padded))[pos].argmax(-1)
    took = jnp.take_along_axis(ref, pick[:, None], 1)[:, 0]
    return np.asarray(ref.max(-1) - took)
