"""Small shared utilities: PRNG, tree helpers, logging, timing."""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import time
from typing import Any, Iterator

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("repro")
if not log.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(levelname).1s] %(message)s", "%H:%M:%S"))
    log.addHandler(_h)
    log.setLevel(os.environ.get("REPRO_LOGLEVEL", "INFO"))


# The checkout root (src/repro/utils.py -> repo): the fixed home of the
# persistent compilation cache when the environment names none.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache lives at ``<repo>/.jax_cache``:
    a fixed path, because the path is part of what a later run must find
    again (ignored by git)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def key_iter(seed: int) -> Iterator[jax.Array]:
    """Infinite stream of independent PRNG keys."""
    key = jax.random.PRNGKey(seed)
    while True:
        key, sub = jax.random.split(key)
        yield sub


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (ShapeDtypeStruct or concrete)."""
    return sum(
        int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
        for x in jax.tree.leaves(tree)
        if hasattr(x, "shape")
    )


def tree_params(tree: Any) -> int:
    """Total element count of all array leaves."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree) if hasattr(x, "shape"))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024:
            return f"{n:.2f}{unit}"
        n /= 1024
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000:
            return f"{n:.2f}{unit}"
        n /= 1000
    return f"{n:.2f}Q"


class StepTimer:
    """Wall-clock timer keeping a history; used by the straggler watchdog."""

    def __init__(self) -> None:
        self.history: list[float] = []
        self._t0: float | None = None

    def __enter__(self) -> "StepTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        assert self._t0 is not None
        self.history.append(time.perf_counter() - self._t0)
        self._t0 = None

    @property
    def median(self) -> float:
        return float(np.median(self.history)) if self.history else 0.0


def asdict_json(obj: Any) -> Any:
    """dataclass/np-friendly JSON conversion."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {f.name: asdict_json(getattr(obj, f.name)) for f in dataclasses.fields(obj)}
    if isinstance(obj, dict):
        return {k: asdict_json(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [asdict_json(v) for v in obj]
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    if isinstance(obj, (np.ndarray, jax.Array)):
        return np.asarray(obj).tolist()
    return obj


def dump_json(path: str, obj: Any) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(asdict_json(obj), f, indent=1, default=str)
    os.replace(tmp, path)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b
