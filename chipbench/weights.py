"""Seeded random weights, made by the benchmark and not by the program.

A weight is named by its path in the parameter tree ("decoder/stack/p0/wq")
and drawn from a key that depends only on the seed and that path, so the
harness (which hands the weights to the system under test) and the plain
reference (which makes them again after the program's state is freed) get
the same numbers without sharing an array, and a draw of some of the leaves
gives each the same bits as a draw of all of them. A leaf is
``(shape, dtype)`` or ``(shape, dtype, init)``: ``init`` is a normal's
standard deviation, ``"ones"`` or ``"zeros"`` (a norm's gain or bias);
where it is None or left out, the draw is normal, scaled by 0.02 for the
embedding and 1/sqrt(fan_in) otherwise. Normal draws are rounded to the 2^-10 grid: every
spiking partial sum is then exact in float32, so the Phi lowerings equal
the dense spiking product bitwise. With ``tied``, the LM head is the
embedding's transpose (``tie_word_embeddings``), whatever separate leaf the
parameter tree keeps for it.
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


def make(seed: int, leaves: dict[str, tuple], tied: bool = False,
         names=None) -> dict:
    """{path: array} of ``names`` (every leaf where None) of ``leaves`` =
    {path: (shape, dtype[, init])}, made on the default device in one jitted
    call."""
    wanted = set(leaves if names is None else names)
    drawn = sorted(wanted - {"head"} if tied else wanted)

    def draw(key, name):
        shape, dtype, *init = leaves[name]
        init = init[0] if init else None
        if init == "ones":
            return jnp.ones(shape, dtype)
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        std = scale(name, shape) if init is None else float(init)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        x = jax.random.normal(k, shape, jnp.float32) * std
        return (jnp.round(x * GRID) / GRID).astype(dtype)

    def build(key):
        out = {name: draw(key, name) for name in drawn}
        if tied and "head" in wanted:
            embed = out["embed"] if "embed" in out else draw(key, "embed")
            out["head"] = embed.T.astype(leaves["head"][1])
        return out

    return jax.jit(build)(base_key(seed))
