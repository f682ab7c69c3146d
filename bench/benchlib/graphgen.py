"""Seeded, chunked Chung-Lu power-law graphs and their feature tables.

The model is ``repro.graph.csr.powerlaw_graph``'s: both endpoints of every
edge are drawn independently with probability proportional to
``rank ** -alpha``, ranks are mapped to vertex ids by a seeded permutation,
and self-loops are dropped.  The program's generator draws all ``n *
avg_degree`` pairs at once and sorts them by source, which at hundreds of
millions of edges takes minutes on one core and twice the edge list in RAM.
This copy draws the same distribution in fixed-size chunks on a thread
pool: the source counts come from one multinomial draw, and each chunk of
destination slots has a generator of its own, keyed by ``(seed, chunk)``.
The result depends on ``seed`` alone, never on the number of threads.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from typing import Tuple

import numpy as np

CHUNK = 1 << 22  # destination slots per generator
FEAT_CHUNK = 1 << 18  # feature rows per generator


def _threads() -> int:
    return max(1, min(16, os.cpu_count() or 1))


def powerlaw_csr(n: int, avg_degree: int, alpha: float,
                 seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(indptr int64 (n+1,), indices int32 (nnz,)) of a Chung-Lu graph with
    ``n * avg_degree`` drawn edges, less the self-loops."""
    rng = np.random.default_rng([seed, 0])
    perm = rng.permutation(n)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-alpha)
    w /= w.sum()
    m = n * avg_degree
    counts = np.zeros(n, dtype=np.int64)
    counts[perm] = rng.multinomial(m, w)
    cdf = np.cumsum(w)
    cdf[-1] = 1.0
    starts = np.concatenate([[0], np.cumsum(counts)])
    n_chunks = -(-m // CHUNK)

    def chunk(c: int) -> np.ndarray:
        a, b = c * CHUNK, min((c + 1) * CHUNK, m)
        u = np.random.default_rng([seed, 1, c]).random(b - a)
        rank = np.minimum(np.searchsorted(cdf, u, side="right"), n - 1)
        dst = perm[rank]
        # the source of each slot: slots are laid out source by source
        lo = np.searchsorted(starts, a, side="right") - 1
        hi = np.searchsorted(starts, b - 1, side="right") - 1
        seg = counts[lo:hi + 1].copy()
        seg[0] -= a - starts[lo]
        seg[-1] = b - max(starts[hi], a) if hi > lo else b - a
        src = np.repeat(np.arange(lo, hi + 1), seg)
        return np.where(dst == src, -1, dst).astype(np.int32)

    with ThreadPoolExecutor(_threads()) as ex:
        dst = np.concatenate(list(ex.map(chunk, range(n_chunks))))
    loop = dst < 0
    if loop.any():
        src_of = np.searchsorted(starts, np.flatnonzero(loop),
                                 side="right") - 1
        counts -= np.bincount(src_of, minlength=n)
        dst = dst[~loop]
    indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return indptr, dst


def uniform_features(n: int, dim: int, seed: int) -> np.ndarray:
    """(n, dim) float32 rows, uniform on [0, 1), filled chunk by chunk in
    parallel; each chunk's generator is keyed by ``(seed, chunk)``."""
    out = np.empty((n, dim), dtype=np.float32)

    def fill(c: int) -> None:
        a, b = c * FEAT_CHUNK, min((c + 1) * FEAT_CHUNK, n)
        np.random.default_rng([seed, 2, c]).random(dtype=np.float32,
                                                   out=out[a:b])

    with ThreadPoolExecutor(_threads()) as ex:
        list(ex.map(fill, range(-(-n // FEAT_CHUNK))))
    return out


def train_split(n: int, fraction: float, seed: int) -> np.ndarray:
    """Sorted training vertex ids: ``round(n * fraction)`` of them."""
    k = int(round(n * fraction))
    return np.sort(np.random.default_rng([seed, 3]).choice(
        n, size=k, replace=False))


def load_or_build(cache_dir: str, n: int, avg_degree: int, alpha: float,
                  seed: int) -> Tuple[np.ndarray, np.ndarray, bool]:
    """The graph from ``cache_dir`` if an earlier run left it there, else
    generated and saved (atomically) for the next run.  Returns (indptr,
    indices, built_now)."""
    tag = f"n{n}_d{avg_degree}_a{alpha}_s{seed}"
    p_ptr = os.path.join(cache_dir, f"{tag}.indptr.npy")
    p_idx = os.path.join(cache_dir, f"{tag}.indices.npy")
    if os.path.exists(p_ptr) and os.path.exists(p_idx):
        return np.load(p_ptr), np.load(p_idx), False
    indptr, indices = powerlaw_csr(n, avg_degree, alpha, seed)
    os.makedirs(cache_dir, exist_ok=True)
    for path, arr in ((p_idx, indices), (p_ptr, indptr)):
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "wb") as f:
            np.save(f, arr)
        os.replace(tmp, path)
    return indptr, indices, True
