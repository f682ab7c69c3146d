"""Small shared utilities: pytree helpers, logging, deterministic hashing."""
from __future__ import annotations

import json
import logging
import os
import time
from typing import Any

import jax
import numpy as np

logger = logging.getLogger("repro")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s %(levelname)s] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


# the checkout root: the directory that holds src/repro/utils/__init__.py
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed place and
    return its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins
    and nothing is set here (JAX reads the variable itself); otherwise the
    cache lives in ``<checkout>/.jax_cache``, which git ignores.  The path
    never varies between runs, since it is part of each entry's key."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def tree_size_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (works on ShapeDtypeStruct too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(int(np.prod(l.shape)) * np.dtype(l.dtype).itemsize for l in leaves))


def tree_param_count(tree: Any) -> int:
    leaves = jax.tree_util.tree_leaves(tree)
    return int(sum(int(np.prod(l.shape)) for l in leaves))


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f}{unit}"
        n /= 1024.0
    return f"{n:.2f}PiB"


def human_count(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"


def stable_hash_u32(x: np.ndarray, salt: int = 0) -> np.ndarray:
    """Deterministic per-element uint32 hash (splitmix-style); used for
    synthetic feature/label generation without materializing huge tables."""
    with np.errstate(over="ignore"):
        z = (x.astype(np.uint64)
             + np.uint64((0x9E3779B97F4A7C15 * (salt + 1)) & 0xFFFFFFFFFFFFFFFF))
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        z = z ^ (z >> np.uint64(31))
    return (z & np.uint64(0xFFFFFFFF)).astype(np.uint32)


class Timer:
    def __init__(self, name: str = ""):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dt = time.perf_counter() - self.t0

    @property
    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


def write_json(path: str, obj: Any) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, default=_json_default)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)
