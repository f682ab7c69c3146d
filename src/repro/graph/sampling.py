"""Fixed-fanout neighbor sampling (GraphSAGE-style, 2-hop 25x10 default).

Two equivalent implementations:

* ``host_sample_batch``  — vectorized numpy; drives pre-sampling (the paper
  stores topology in CPU memory during pre-sampling) and the host side of the
  training pipeline.
* ``device_sample``      — pure-jnp sampler over device-resident CSR arrays
  (the unified cache's topology half lives in HBM; cached vertices sample on
  device — the TPU analogue of the paper's GPU sampling).

Both sample uniformly *with replacement* (the paper's uniform random neighbor
sampling); zero-degree vertices yield -1 padding.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import CSRGraph
from repro.obs import maybe_span


def host_sample_level(g: CSRGraph, seeds: np.ndarray, fanout: int,
                      rng: np.random.Generator,
                      rand: np.ndarray = None) -> np.ndarray:
    """(B,) seeds -> (B, fanout) sampled neighbors (-1 where deg==0).
    seeds < 0 propagate -1.  ``rand`` (B, fanout) overrides the draws so a
    caller can replay the exact level (the cache-aware sampler reuses one
    draw for its device and host halves)."""
    seeds = np.asarray(seeds, dtype=np.int64)
    valid = seeds >= 0
    sv = np.where(valid, seeds, 0)
    start = g.indptr[sv]
    deg = g.indptr[sv + 1] - start
    r = rng.integers(0, 1 << 31, size=(len(seeds), fanout)) \
        if rand is None else rand
    has = (deg > 0) & valid
    offs = r % np.maximum(deg, 1)[:, None]
    idx = start[:, None] + offs
    out = g.indices[np.minimum(idx, g.nnz - 1)].astype(np.int64)
    out = np.where(has[:, None], out, -1)
    return out


def host_sample_batch(g: CSRGraph, seeds: np.ndarray, fanouts: Sequence[int],
                      rng: np.random.Generator) -> List[np.ndarray]:
    """Multi-hop sample: returns [seeds (B,), hop1 (B,f1), hop2 (B,f1,f2), ...]."""
    levels = [np.asarray(seeds, dtype=np.int64)]
    frontier = levels[0]
    shape = (len(frontier),)
    for f in fanouts:
        nxt = host_sample_level(g, frontier.reshape(-1), f, rng)
        shape = shape + (f,)
        levels.append(nxt.reshape(shape))
        frontier = levels[-1]
    return levels


def device_sample_level(indptr: jax.Array, indices: jax.Array,
                        seeds: jax.Array, fanout: int, key: jax.Array):
    """jnp version of host_sample_level (device CSR arrays)."""
    valid = seeds >= 0
    sv = jnp.where(valid, seeds, 0)
    start = indptr[sv]
    deg = indptr[sv + 1] - start
    r = jax.random.randint(key, (seeds.shape[0], fanout), 0, 1 << 30)
    offs = r % jnp.maximum(deg, 1)[:, None]
    idx = start[:, None] + offs
    out = indices[jnp.minimum(idx, indices.shape[0] - 1)].astype(jnp.int32)
    has = (deg > 0) & valid
    return jnp.where(has[:, None], out, -1)


def device_sample(indptr: jax.Array, indices: jax.Array, seeds: jax.Array,
                  fanouts: Sequence[int], key: jax.Array):
    levels = [seeds.astype(jnp.int32)]
    frontier = levels[0]
    shape = (seeds.shape[0],)
    for i, f in enumerate(fanouts):
        k = jax.random.fold_in(key, i)
        nxt = device_sample_level(indptr, indices, frontier.reshape(-1), f, k)
        shape = shape + (f,)
        levels.append(nxt.reshape(shape))
        frontier = levels[-1]
    return levels


def cache_sample_level(g: CSRGraph, cache, seeds: np.ndarray, fanout: int,
                       rng: np.random.Generator) -> Tuple[np.ndarray, np.ndarray]:
    """One sampling level through the unified cache: topology-cache hits
    sample *on device* from the HBM-resident cache CSR
    (``CliqueCache.device_sample_cached``); only the miss rows fall back to
    the host CSR.  Both halves consume the same random draw, and the cache
    CSR stores adjacency in host order, so the composed level is
    bit-identical to ``host_sample_level`` — the host/device parity
    guarantee.

    Returns (neighbors (B, fanout) int64, topo_hit_mask (B,) bool).
    """
    seeds = np.asarray(seeds, dtype=np.int64)
    r = rng.integers(0, 1 << 31, size=(len(seeds), fanout))
    dev_out, hit = cache.device_sample_cached(seeds, fanout, rand=r)
    out = np.asarray(dev_out).astype(np.int64)
    hit = np.asarray(hit)
    if (~hit).any():
        out[~hit] = host_sample_level(g, seeds[~hit], fanout, rng,
                                      rand=r[~hit])
    return out, hit


def _mirror_sample_level(cache, seeds: np.ndarray, fanout: int,
                         rand: np.ndarray) -> np.ndarray:
    """Replay one level's draws against the *host mirror* of the topology
    cache (the union CSR ``topo_pos``/``cache_indptr``/``cache_indices``).
    Every cached vertex's adjacency is stored in host order, so for cached
    non-negative ``seeds`` this is bit-identical to ``host_sample_level``
    — without touching the host CSR (it is the stale-parent repair path of
    the chained sampler, not a host fallback)."""
    seeds = np.asarray(seeds, dtype=np.int64)
    pos = cache.topo_pos[seeds]
    start = cache.cache_indptr[pos]
    deg = cache.cache_indptr[pos + 1] - start
    offs = rand % np.maximum(deg, 1)[:, None]
    idx = np.minimum(start[:, None] + offs,
                     max(len(cache.cache_indices) - 1, 0))
    out = cache.cache_indices[idx].astype(np.int64)
    return np.where((deg > 0)[:, None], out, -1)


def cache_sample_dispatch(g: CSRGraph, cache, seeds: np.ndarray,
                          fanouts: Sequence[int], rng: np.random.Generator,
                          telemetry=None):
    """Phase 1 of the chained cache-aware sampler: draw every hop's
    randomness in host-sampler order and enqueue the whole device chain
    (``CliqueCache.device_sample_chain`` — the routed neighbor exchange
    under the sharded layout) *without reading anything back*.

    Returns a ``resolve(counter=None)`` closure that pays the single host
    sync and finishes the batch; the builder can run unrelated host work
    (label fetch, accounting) between dispatch and resolve so the chain's
    device time overlaps it.  The resolve pass repairs rows the device
    could not serve, cheapest source first:

    * negative sources (deg-0 parents / padding) are ``-1`` rows by
      definition — no CSR of any kind is consulted;
    * cached sources whose *parent* was host-filled (the device saw ``-1``
      where the host later wrote a cached id) replay their draws against
      the cache's host mirror — a topology *hit*, repaired off-device only
      because the value arrived after the chain was enqueued;
    * only genuinely uncached sources fall back to the host CSR, batched
      into one vectorized ``host_sample_level`` call per hop.

    All three replay the exact draws the device half consumed, so the
    composed levels stay bit-identical to ``host_sample_batch``; the hit
    masks match the per-hop reference path exactly.  ``counter`` (a
    ``TrafficCounter``) gets ``host_sample_syncs += 1`` iff the batch
    touched the host CSR at all — a warm epoch whose frontier fits the
    cached topology resolves with zero host sampling syncs.  With
    ``telemetry`` (a ``repro.obs.Telemetry``) the resolve's sync is the
    ``sample_sync`` span (attr ``rows``, the frontier rows read back) and
    the repair pass the ``sample_repair`` span (attrs ``mirror_rows`` and
    ``host_rows``, the rows replayed from the mirror and from the host
    CSR).
    """
    seeds = np.asarray(seeds, dtype=np.int64)
    rands = []
    n_flat = len(seeds)
    for f in fanouts:
        rands.append(rng.integers(0, 1 << 31, size=(n_flat, f)))
        n_flat *= f
    dev_outs, dev_hits = cache.device_sample_chain(seeds, fanouts, rands)

    def resolve(counter=None):
        levels = [seeds]
        hits: List[np.ndarray] = []
        frontier = seeds
        shape = (len(frontier),)
        # one sync for the whole chain
        with maybe_span(telemetry, "sample_sync") as sp:
            outs = [np.asarray(o) for o in dev_outs]
            dhits = [np.asarray(h) for h in dev_hits]
            if sp is not None:
                sp.attrs["rows"] = sum(len(o) for o in outs)
        mirror_ok = cache.cache_indices is not None
        ok = np.ones(len(frontier), dtype=bool)
        n_mirror = n_host = 0
        with maybe_span(telemetry, "sample_repair") as sp:
            for k, f in enumerate(fanouts):
                flat = frontier.reshape(-1)
                resolved = dhits[k] & ok
                out = outs[k].astype(np.int64)
                need = np.flatnonzero(~resolved)
                if len(need):
                    src = flat[need]
                    neg = src < 0
                    out[need[neg]] = -1
                    live = need[~neg]
                    if len(live):
                        cached = ((cache.topo_pos[flat[live]] >= 0)
                                  if mirror_ok
                                  else np.zeros(len(live), dtype=bool))
                        fix = live[cached]
                        if len(fix):
                            out[fix] = _mirror_sample_level(
                                cache, flat[fix], f, rands[k][fix])
                            resolved[fix] = True
                            n_mirror += len(fix)
                        host = live[~cached]
                        if len(host):
                            out[host] = host_sample_level(
                                g, flat[host], f, rng, rand=rands[k][host])
                            n_host += len(host)
                hits.append(resolved)
                shape = shape + (f,)
                levels.append(out.reshape(shape))
                frontier = levels[-1]
                ok = np.repeat(resolved, f)
            if sp is not None:
                sp.attrs["mirror_rows"] = n_mirror
                sp.attrs["host_rows"] = n_host
        if counter is not None and n_host:
            with counter.lock:
                counter.host_sample_syncs += 1
        return levels, hits

    return resolve


def cache_sample_batch(g: CSRGraph, cache, seeds: np.ndarray,
                       fanouts: Sequence[int], rng: np.random.Generator,
                       chain: bool = True, counter=None
                       ) -> Tuple[List[np.ndarray], List[np.ndarray]]:
    """Cache-aware multi-hop sample (device backend of the batch pipeline).

    Same contract as ``host_sample_batch`` plus per-level topology-hit
    masks (flattened frontier order).  With an identically-seeded ``rng``
    the returned levels are bit-identical to the host sampler's.

    ``chain=True`` (default) enqueues all hops' device halves back-to-back
    and pays a *single* host sync per batch — see
    ``cache_sample_dispatch`` for the resolve contract (stale-parent rows
    repair from the cache's host mirror, so the hit masks match the
    per-hop path exactly and only genuinely uncached rows touch the host
    CSR).

    ``chain=False`` is the legacy per-hop path (one device sync per hop via
    ``cache_sample_level``) — kept as the reference for parity tests and
    the ``pipeline_stall`` before/after benchmark.

    ``counter`` (a ``TrafficCounter``) tallies ``host_sample_syncs`` — one
    per batch whose resolution touched the host CSR, either path.
    """
    if chain:
        return cache_sample_dispatch(g, cache, seeds, fanouts, rng)(
            counter=counter)
    levels = [np.asarray(seeds, dtype=np.int64)]
    hits: List[np.ndarray] = []
    frontier = levels[0]
    shape = (len(frontier),)
    touched_host = False
    for f in fanouts:
        flat = frontier.reshape(-1)
        nxt, hit = cache_sample_level(g, cache, flat, f, rng)
        touched_host |= bool((~hit & (flat >= 0)).any())
        hits.append(hit)
        shape = shape + (f,)
        levels.append(nxt.reshape(shape))
        frontier = levels[-1]
    if counter is not None and touched_host:
        with counter.lock:
            counter.host_sample_syncs += 1
    return levels, hits


def unique_vertices(levels: List[np.ndarray]) -> np.ndarray:
    """All distinct non-negative vertex ids appearing in a sampled subgraph."""
    flat = np.concatenate([l.reshape(-1) for l in levels])
    flat = flat[flat >= 0]
    return np.unique(flat)


def dedup_levels(levels: List[np.ndarray],
                 slot: np.ndarray) -> Tuple[np.ndarray, List[np.ndarray]]:
    """``unique_vertices(levels)`` and each level's positions into it.

    ``slot`` is a caller-owned int32 scratch map with one entry per vertex
    of the graph.  It needs no initialization and may hold any values from
    earlier calls: every entry read here is written earlier in the same
    call.  Returns ``(ids, level_pos)`` with ``ids``
    sorted int64 (equal to ``unique_vertices``) and ``level_pos[l]`` the
    int64 index of each entry of ``levels[l]`` in ``ids`` (0 at padding
    entries).  Cost scales with the sampled count, not with ``len(slot)``:
    one scatter and two gathers over the sampled ids, and a sort of the
    distinct ones only."""
    flat = np.concatenate([l.reshape(-1) for l in levels])
    v = flat[flat >= 0]
    order = np.arange(len(v), dtype=np.int32)
    slot[v] = order
    # the last writer wins, so each distinct vertex keeps one occurrence
    ids = v[slot[v] == order].astype(np.int64, copy=False)
    ids.sort()
    slot[ids] = np.arange(len(ids), dtype=np.int32)
    # mode="clip" reads padding (-1) at slot[0]; the where masks it
    level_pos = [np.where(lvl >= 0, slot.take(lvl, mode="clip"), np.int64(0))
                 for lvl in levels]
    return ids, level_pos
