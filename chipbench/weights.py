"""Seeded random weights, made by the benchmark and not by the program.

A weight is named by its path in the parameter tree ("decoder/stack/p0/wq")
and drawn from a key that depends only on the seed and that path, so the
harness (which hands the weights to the system under test) and the plain
reference (which makes them again after the program's state is freed) get
the same numbers without sharing an array. Values are normal, scaled by
0.02 for the embedding and 1/sqrt(fan_in) otherwise, and rounded to the
2^-10 grid: every spiking partial sum is then exact in float32, so the Phi
lowerings equal the dense spiking product bitwise. With ``tied``, the LM
head is the embedding's transpose (``tie_word_embeddings``), whatever
separate leaf the parameter tree keeps for it.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

GRID = 1024.0


def base_key(seed: int):
    """A PRNG key from any seed that fits in 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def scale(path: str, shape: tuple[int, ...]) -> float:
    """Standard deviation of the weight at ``path``."""
    if path.split("/")[-1] == "embed":
        return 0.02
    return float(shape[-2] if len(shape) >= 2 else shape[-1]) ** -0.5


def make(seed: int, leaves: dict[str, tuple[tuple[int, ...], str]],
         tied: bool = False) -> dict:
    """{path: array} for ``leaves`` = {path: (shape, dtype)}, made on the
    default device in one jitted call."""
    names = sorted(set(leaves) - {"head"} if tied else leaves)

    def build(key):
        out = {}
        for name in names:
            shape, dtype = leaves[name]
            k = jax.random.fold_in(key, zlib.crc32(name.encode()))
            x = jax.random.normal(k, shape, jnp.float32) * scale(name, shape)
            out[name] = (jnp.round(x * GRID) / GRID).astype(dtype)
        if tied:
            out["head"] = out["embed"].T.astype(leaves["head"][1])
        return out

    return jax.jit(build)(base_key(seed))
