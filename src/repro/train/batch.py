"""Batch builders: the host/device split of Legion's per-step pipeline.

One training batch is produced in two phases with a hard boundary between
them, so the Prefetcher thread and the consumer can overlap:

  build_spec()   host thread (Prefetcher): seed shuffle, neighbor sampling,
                 hit/miss split, miss-row fetch, traffic accounting.
                 Produces a backend-agnostic ``BatchSpec`` (pure numpy).
  finalize()     consumer thread: turns a spec into the jnp tensors the
                 train step consumes.  For the device backend this is where
                 the HBM-resident cache gather runs — JAX async dispatch
                 overlaps it with the previous train step.

Two interchangeable backends (paper §4.2/§5 vs the classic CPU pipeline)::

    HostBatchBuilder                     DeviceBatchBuilder
    ----------------                     ------------------
    sample: host CSR (numpy)             sample: HBM topology cache on
                                           device (all hops enqueued
                                           back-to-back, one sync); host
                                           fills only the topo-miss rows
    gather: numpy rows, hits from        gather: one fused jitted dispatch
      the host copy of the cache           (kernels/fused_batch.py): cache
                                           gather + miss overlay + level
                                           positioning/masking
    finalize: one host->device copy      finalize: fused device phase +
      of the full batch                    small staged miss upload

Both backends draw identical randomness (the device sampler replays the
host generator's draws) and share one accounting implementation
(``CliqueCache.account_feature_gather`` / ``sample_accounting``), so for a
given seed they produce bit-identical batches and identical hit/miss
counts — `tests/test_batch.py` pins this.

Stable shapes (retrace-free finalize): the device spec's per-id layout is
**bucket-rounded** — ``ids``/``cache_pos``/``hit``/``miss_inv`` pad to the
next multiple of ``bucket`` (default 256), and miss rows stage into a
bucket-rounded pinned staging buffer reused across batches (lane-padded to
the cache table's width so no per-batch re-pad happens on device).  Every
jitted finalize therefore sees one shape per (id-bucket, miss-bucket) pair
and compiles **once per bucket instead of once per batch**; padded tail
entries are inert (ids/cache_pos/miss_inv = -1, hit = False) and are never
referenced by any level position.  ``tests/test_batch.py`` pins the
retrace count.  The ``bucket`` knob trades padding waste (at most
``bucket-1`` zero rows per batch) against compile count; the host backend
is unpadded and compile-free by construction.

A third backend, ``ShardedBatchBuilder`` (``backend="sharded"``), keeps
the device backend's host phase (and therefore its specs and accounting)
but adds per-id ownership routing so the hierarchical executor can
finalize every clique jointly under ``shard_map``: local hits gather
from the requester's own cache partition, peer hits ride the intra-clique
exchange, and only true misses are host-filled.  ``pack_sharded_specs``
stacks the per-clique spec groups into the ``(K_c, K_g, ...)`` arrays the
2-D ``(pod, clique)`` mesh shards (``tests/test_sharded.py`` pins
three-way parity, ``tests/test_hierarchy.py`` the multi-clique runs).
"""
from __future__ import annotations

import dataclasses
from collections import deque
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.unified_cache import CliqueCache, TrafficCounter
from repro.graph.csr import CSRGraph
from repro.graph.sampling import (cache_sample_batch, cache_sample_dispatch,
                                  dedup_levels, host_sample_batch)
from repro.obs import maybe_span

BACKENDS = ("host", "device", "sharded")

DEFAULT_BUCKET = 256  # id/miss shape quantum of the device spec layout


def _round_bucket(n: int, bucket: int) -> int:
    """Smallest positive multiple of ``bucket`` holding ``n`` rows."""
    return max(-(-n // bucket), 1) * bucket


@dataclasses.dataclass
class BatchSpec:
    """Backend-agnostic description of one sampled mini-batch (numpy only;
    crosses the Prefetcher thread boundary).

    Device/sharded specs use the bucket-rounded layout (see module doc):
    ``ids``/``cache_pos``/``hit``/``miss_inv`` have length
    ``_round_bucket(n_ids, bucket)`` with inert padding (-1 / False), and
    ``miss_feats`` is a bucket-rounded staging buffer whose first
    ``n_miss`` rows are real (width may exceed the graph's feature dim —
    it is lane-padded to the cache table's device width).  Host specs are
    unpadded (``n_ids == len(ids)``)."""
    labels: np.ndarray                  # (B,) int32
    levels: List[np.ndarray]            # padded level id tensors, -1 = pad
    ids: np.ndarray                     # unique vertex ids (pad rows = -1)
    level_pos: List[np.ndarray]         # per-level position into ``ids``
    # host backend: fully materialized feature rows for ``ids``
    host_feats: Optional[np.ndarray] = None
    # device backend: hit/miss split + host-staged miss rows
    cache_pos: Optional[np.ndarray] = None   # feat-cache slot per id (-1 miss)
    hit: Optional[np.ndarray] = None         # (n_pad,) bool (pad rows False)
    miss_feats: Optional[np.ndarray] = None  # (m_pad, >=D) f32 staging buffer
    # row i's source row in miss_feats (-1 = cached or padding)
    miss_inv: Optional[np.ndarray] = None
    n_ids: int = 0                      # true unique-id count (<= len(ids))
    n_miss: int = 0                     # true miss count (<= len(miss_feats))
    # cache refresh epoch this spec's slots index into: finalize gathers
    # from the matching (possibly previous) device buffer, so an online
    # refresh racing the prefetch queue can never misroute cached rows
    cache_epoch: int = 0
    # sharded backend: ownership routing per id — clique-local owning
    # device and row within the owner's shard (-1 on miss), read off
    # CliqueCache.shard_routing at spec-build time
    owner: Optional[np.ndarray] = None
    local_slot: Optional[np.ndarray] = None
    # the training step this spec was filled for (``fill_spec(step=)``);
    # the consumer's finalize spans carry it
    step: Optional[int] = None


class _StagingPool:
    """Reusable host-side miss staging buffers, keyed by (rows, width).

    The device spec stages its miss rows into one of these instead of
    allocating a fresh array per batch — the CPU-pipeline analogue of a
    pinned H2D staging area.  ``acquire`` hands out a zeroed-tail buffer;
    the consumer releases it only after the device copy *completed*:
    ``jnp.array`` copies but dispatches asynchronously, so the release
    site must ``block_until_ready()`` on the transferred array first — a
    buffer recycled mid-transfer feeds the in-flight batch rows from the
    *next* batch (a rare, timing-dependent corruption that presents as
    nondeterministic losses).  Thread-safe: build runs on prefetch
    workers, release on the consumer.
    """

    def __init__(self):
        self._free: Dict[Tuple[int, int], deque] = {}

    def acquire(self, rows: int, width: int) -> np.ndarray:
        q = self._free.setdefault((rows, width), deque())
        try:
            return q.pop()
        except IndexError:
            return np.zeros((rows, width), dtype=np.float32)

    def release(self, buf: Optional[np.ndarray]) -> None:
        if buf is not None:
            self._free.setdefault(buf.shape, deque()).append(buf)


_fused_finalize = None  # built on first use (keeps jax import lazy)


def _get_fused_finalize():
    """The whole device phase of one batch as ONE jitted dispatch: fused
    cached-row gather + miss overlay (Pallas kernel or XLA oracle), then
    per-level positioning and pad masking.  Static over the gather impl
    and feature dim only — array shapes are bucket-stable, so this
    compiles once per (id-bucket, miss-bucket) pair (`tests/test_batch.py`
    counts via ``_fused_finalize._cache_size()``)."""
    global _fused_finalize
    if _fused_finalize is None:
        import jax

        @partial(jax.jit, static_argnames=("impl", "D"))
        def fused_finalize(table, idx, miss_rows, miss_inv, labels, pos,
                           valid, *, impl: str, D: int):
            from repro.kernels import fused_batch, ref

            feats = (fused_batch.fused_gather_overlay_pallas(
                         table, idx, miss_rows, miss_inv)
                     if impl == "pallas"
                     else ref.fused_gather_overlay(table, idx, miss_rows,
                                                   miss_inv))
            if feats.shape[1] != D:
                feats = feats[:, :D]
            out = {"labels": labels}
            for li, (p, v) in enumerate(zip(pos, valid)):
                f = feats[p].reshape(v.shape + (D,))
                out[f"feats_{li}"] = f * v[..., None].astype(f.dtype)
                if li > 0:
                    out[f"mask_{li}"] = v
            return out

        _fused_finalize = fused_finalize
    return _fused_finalize


class BatchBuilder:
    """Samples and extracts one device's mini-batches (see module doc).

    Not thread-safe: a builder owns a vertex-indexed slot map (4 bytes a
    vertex, allocated on the first build) that every ``sample_spec``
    overwrites, so one builder runs on one thread at a time.  Each device
    has its own builder, and the Prefetcher's part pool never runs one
    device's part on two threads at once."""

    backend: str = "?"

    def __init__(self, g: CSRGraph, cache: Optional[CliqueCache],
                 fanouts: Sequence[int],
                 counter: Optional[TrafficCounter] = None, dev: int = 0,
                 observer=None):
        self.g = g
        self.cache = cache
        self.fanouts = tuple(fanouts)
        self.counter = counter
        self.dev = dev
        # online cache manager tap (OnlineCacheManager.observer_for): fed
        # every sampled batch's level tensors; pure recording, so attaching
        # one changes neither batches nor traffic accounting
        self.observer = observer
        # telemetry tap (repro.obs.Telemetry), attached by the train loop:
        # spans over the build's stages and over finalize/H2D staging when
        # set, a shared no-op context when None — never perturbs batches
        # or accounting
        self.telemetry = None
        # tiered feature store (core.feature_store.FeatureStore), attached
        # by the train loop: when set, HBM-miss fills route through its
        # host-RAM/SSD tiers instead of a direct g.get_features host read.
        # Rows are bitwise identical either way.
        self.store = None
        self._slot: Optional[np.ndarray] = None  # dedup_levels' scratch map

    def _dedup(self, levels: List[np.ndarray]):
        """(sorted unique ids, per-level positions into them)."""
        if self._slot is None:
            self._slot = np.empty(self.g.n, dtype=np.int32)
        return dedup_levels(levels, self._slot)

    # -- phase 1: host thread --------------------------------------------
    # Split into two sub-phases so the pipeline can sample *ahead* of the
    # feature fill (the store's lookahead window):
    #   sample_spec()  draws this step's randomness and samples the batch
    #                  (all RNG consumption happens here, in step order —
    #                  the bitwise-determinism anchor);
    #   fill_spec()    splits against the HBM cache at the *current* epoch
    #                  and fetches the miss rows (RNG-free, so deferring it
    #                  behind k more sample_spec calls changes nothing).
    # build_spec() composes the two back to back (the classic path).
    def sample_spec(self, seeds: np.ndarray,
                    rng: np.random.Generator) -> BatchSpec:
        raise NotImplementedError

    def fill_spec(self, spec: BatchSpec,
                  step: Optional[int] = None) -> BatchSpec:
        raise NotImplementedError

    def store_request_ids(self, spec: BatchSpec) -> np.ndarray:
        """The ids ``fill_spec`` will request from the tiered store — the
        sampled uniques minus the *current* HBM-resident set.  Read-only
        (no accounting, no epoch pin): it feeds the store's lookahead
        announce/prefetch hints, which stay hints — an online refresh
        between announce and fill only degrades eviction quality, never
        correctness."""
        ids = spec.ids[:spec.n_ids]
        if self.cache is None or len(self.cache.feat_ids) == 0:
            return ids
        _, hit = self.cache.split_hits(ids)
        return ids[~hit]

    def build_spec(self, seeds: np.ndarray, rng: np.random.Generator,
                   step: Optional[int] = None) -> BatchSpec:
        return self.fill_spec(self.sample_spec(seeds, rng), step=step)

    def _store_fill(self, ids: np.ndarray,
                    step: Optional[int]) -> np.ndarray:
        """Cache-less miss fetch: through the store when attached (its
        host-RAM/SSD tiers), else straight off the graph."""
        if self.store is not None:
            return self.store.gather(ids, step=step, dev=self.dev)
        return self.g.get_features(ids)

    # -- phase 2: consumer thread ----------------------------------------
    def finalize(self, spec: BatchSpec) -> Dict[str, "object"]:
        raise NotImplementedError

    def release_spec(self, spec: BatchSpec) -> None:
        """Return a spec's pooled resources without finalizing it (the
        sharded pack path consumes specs on the worker thread)."""

    def build(self, seeds: np.ndarray, rng: np.random.Generator) -> Dict:
        """Convenience: both phases back to back (benchmarks, tests)."""
        return self.finalize(self.build_spec(seeds, rng))

    def _account_sampling(self, levels: List[np.ndarray]) -> None:
        if self.observer is not None:
            self.observer.record(levels, self.fanouts)
        if self.counter is not None and self.cache is not None:
            for lvl, f in zip(levels[:-1], self.fanouts):
                self.cache.sample_accounting(lvl.reshape(-1), f,
                                             self.counter, self.dev)


class HostBatchBuilder(BatchBuilder):
    """The classic CPU pipeline: everything numpy, one H2D copy per batch.
    No jit anywhere on this path — it stays compile-free by construction
    (pinned by the retrace-count test)."""

    backend = "host"

    def sample_spec(self, seeds, rng):
        levels = host_sample_batch(self.g, seeds, self.fanouts, rng)
        if self.counter is not None:
            # every host build samples from the host CSR by construction
            with self.counter.lock:
                self.counter.host_sample_syncs += 1
        self._account_sampling(levels)
        ids, level_pos = self._dedup(levels)
        return BatchSpec(labels=self.g.get_labels(seeds), levels=levels,
                         ids=ids, level_pos=level_pos, n_ids=len(ids))

    def fill_spec(self, spec, step=None):
        ids = spec.ids
        spec.host_feats = (
            self.cache.extract_features(ids, self.dev, self.counter,
                                        store=self.store, step=step)
            if self.cache is not None else self._store_fill(ids, step))
        spec.step = step
        return spec

    @staticmethod
    def assemble(spec: BatchSpec) -> Dict[str, np.ndarray]:
        """Spec -> padded numpy batch (the pre-copy host representation)."""
        batch = {"labels": spec.labels}
        for li, (lvl, pos) in enumerate(zip(spec.levels, spec.level_pos)):
            f = spec.host_feats[pos]
            f[lvl < 0] = 0.0
            batch[f"feats_{li}"] = f
            if li > 0:
                batch[f"mask_{li}"] = lvl >= 0
        return batch

    def finalize(self, spec):
        import jax.numpy as jnp

        with maybe_span(self.telemetry, "finalize", step=spec.step,
                        dev=self.dev):
            return {k: jnp.asarray(v)
                    for k, v in self.assemble(spec).items()}


class DeviceBatchBuilder(BatchBuilder):
    """Device-resident pipeline: sampling and feature gather run against the
    HBM-resident unified cache; the host only fills misses.

    ``gather`` picks the cached-row gather implementation:
      * ``"pallas"`` — the Mosaic kernels (`fused_batch` / `gather_rows`);
        compiled on TPU, interpreted on CPU (slow there, but the real hot
        path).
      * ``"xla"``    — the jnp oracles with identical semantics.
      * ``"auto"``   — pallas on TPU, xla otherwise (default; the one
        switch is ``repro.kernels.kernel_impl``).

    ``bucket`` sets the shape quantum of the spec layout (see module doc);
    ``fused=False`` falls back to the legacy finalize chain (separate
    gather, full-table ``.at[].set`` miss overlay, one ``take`` per level,
    all at exact per-batch shapes — retraces almost every batch) and is
    kept as the ``pipeline_stall`` benchmark's *before* arm and as a
    second parity oracle.  ``sampler="stepwise"`` likewise restores the
    per-hop-sync sampling path (see ``cache_sample_batch``).
    """

    backend = "device"

    def __init__(self, g, cache, fanouts, counter=None, dev=0,
                 gather: str = "auto", observer=None, fused: bool = True,
                 bucket: int = DEFAULT_BUCKET, sampler: str = "chain"):
        if cache is None:
            raise ValueError("DeviceBatchBuilder needs a unified cache "
                             "(build a LegionPlan, or use backend='host')")
        super().__init__(g, cache, fanouts, counter, dev, observer)
        from repro.kernels import kernel_impl

        gather = "xla" if kernel_impl(gather) == "xla" else "pallas"
        if sampler not in ("chain", "stepwise"):
            raise ValueError(f"unknown sampler mode {sampler!r}")
        if bucket < 1:
            raise ValueError(f"bucket must be >= 1, got {bucket}")
        self.gather = gather
        self.fused = fused
        self.bucket = int(bucket)
        self.sampler = sampler
        self._staging = _StagingPool()

    def _staging_width(self) -> int:
        """Miss rows stage at the cache table's lane-padded device width so
        the fused kernel sees one width for both sources (columns beyond
        feat_dim stay zero for the buffer's lifetime)."""
        return CliqueCache._lane_padded(self.g.feat_dim)

    def sample_spec(self, seeds, rng):
        # spans (with telemetry): sample_dispatch, sample_sync and
        # sample_repair (inside resolve), sample_account, spec_dedup; the
        # step they carry is the enclosing spec_build's
        tele = self.telemetry
        if self.sampler == "chain":
            # dispatch the whole device chain, then fetch labels while it
            # is in flight; resolve() pays the single sync and repairs
            # stale-parent / host-miss rows (see cache_sample_dispatch)
            with maybe_span(tele, "sample_dispatch") as sp:
                resolve = cache_sample_dispatch(self.g, self.cache, seeds,
                                                self.fanouts, rng,
                                                telemetry=tele)
                labels = self.g.get_labels(seeds)
                if sp is not None:
                    sp.attrs["draws"] = int(
                        len(seeds) * np.cumprod(self.fanouts).sum())
            levels, _topo_hits = resolve(counter=self.counter)
        else:
            levels, _topo_hits = cache_sample_batch(
                self.g, self.cache, seeds, self.fanouts, rng, chain=False,
                counter=self.counter)
            labels = self.g.get_labels(seeds)
        with maybe_span(tele, "sample_account"):
            self._account_sampling(levels)
        with maybe_span(tele, "spec_dedup") as sp:
            ids, level_pos = self._dedup(levels)
            if sp is not None:
                sp.attrs["n_ids"] = len(ids)
                sp.attrs["n_sampled"] = int(sum(
                    np.count_nonzero(lvl >= 0) for lvl in levels))
        return BatchSpec(labels=labels, levels=levels, ids=ids,
                         level_pos=level_pos, n_ids=len(ids))

    def fill_spec(self, spec, step=None):
        # the hit/miss split runs HERE — at build time, after any refresh
        # hook the step barrier serialized before it — so the spec pins the
        # *current* cache epoch regardless of how far ahead it was sampled.
        # Spans (with telemetry): fill_split, then fill_miss.
        tele = self.telemetry
        ids, n_ids = spec.ids, spec.n_ids
        with maybe_span(tele, "fill_split") as sp:
            cache_pos, hit = self.cache.split_hits(ids)
            if self.counter is not None:
                self.cache.account_feature_gather(cache_pos, hit, self.dev,
                                                  self.counter)
            if self.store is not None:
                self.store.record_hbm(n_ids, int(hit.sum()))
            n_miss = int((~hit).sum())
            # bucket-rounded layout: pad rows are inert (-1 / False) and
            # never referenced by level_pos, so every downstream shape is
            # stable
            n_pad = _round_bucket(n_ids, self.bucket)
            m_pad = _round_bucket(n_miss, self.bucket)
            ids_p = np.full(n_pad, -1, dtype=np.int64)
            ids_p[:n_ids] = ids
            pos_p = np.full(n_pad, -1, dtype=np.int64)
            pos_p[:n_ids] = cache_pos
            hit_p = np.zeros(n_pad, dtype=bool)
            hit_p[:n_ids] = hit
            miss_inv = np.full(n_pad, -1, dtype=np.int32)
            miss_inv[np.flatnonzero(~hit)] = np.arange(n_miss,
                                                       dtype=np.int32)
            if sp is not None:
                sp.attrs["n_miss"] = n_miss
        with maybe_span(tele, "fill_miss", rows=n_miss):
            staging = self._staging.acquire(m_pad, self._staging_width())
            D = self.g.feat_dim
            if n_miss:
                miss_ids = ids[~hit]
                staging[:n_miss, :D] = (
                    self.store.gather(miss_ids, step=step, dev=self.dev)
                    if self.store is not None
                    else self.g.get_features(miss_ids))
            staging[n_miss:, :D] = 0.0
        spec.ids = ids_p
        spec.cache_pos = pos_p
        spec.hit = hit_p
        spec.miss_feats = staging
        spec.miss_inv = miss_inv
        spec.n_miss = n_miss
        spec.cache_epoch = self.cache.epoch
        spec.step = step
        return spec

    def release_spec(self, spec):
        self._staging.release(spec.miss_feats)
        spec.miss_feats = None

    def _table(self, epoch: int):
        """The epoch-pinned device feature table; a (1, Dp) zero dummy when
        the plan cached nothing (every row then resolves as miss/pad)."""
        import jax.numpy as jnp

        if len(self.cache.feat_ids) == 0:
            return jnp.zeros((1, self._staging_width()), jnp.float32)
        return self.cache.device_arrays(epoch)["feat_cache"]

    def finalize_args(self, spec) -> tuple:
        """The fused finalize's positional arguments for ``spec``: the
        epoch-pinned table, the staged miss rows (uploaded here, after
        which the spec's staging buffer is back in the pool) and the
        int32 maps.  ``_get_fused_finalize()(*args, impl=..., D=...)``."""
        import jax.numpy as jnp

        table = self._table(spec.cache_epoch)
        # jnp.array copies, but the copy is DISPATCHED, not done: the
        # transfer must complete before the staging buffer goes back to
        # the pool, or the next fill overwrites it mid-read.  The span
        # takes its step from the enclosing finalize span.
        with maybe_span(self.telemetry, "h2d_staging", dev=self.dev,
                        rows=spec.n_miss):
            miss = jnp.array(spec.miss_feats)
            miss.block_until_ready()
        self.release_spec(spec)
        idx = spec.cache_pos.astype(np.int32)  # -1 at miss AND pad rows
        pos = tuple(np.ascontiguousarray(p.reshape(-1).astype(np.int32))
                    for p in spec.level_pos)
        valid = tuple(lvl >= 0 for lvl in spec.levels)
        return table, idx, miss, spec.miss_inv, spec.labels, pos, valid

    def finalize(self, spec):
        if not self.fused:
            return self._finalize_unfused(spec)
        with maybe_span(self.telemetry, "finalize", step=spec.step,
                        dev=self.dev):
            return _get_fused_finalize()(*self.finalize_args(spec),
                                         impl=self.gather, D=self.g.feat_dim)

    # -- legacy (pre-fused) finalize: the benchmark's *before* arm --------
    def _gather_cached(self, idx: np.ndarray, epoch: int):
        """(n,) slot ids (-1 = miss) -> (n, D) rows, zeros at -1.
        ``epoch`` selects the double-buffered table the slots index into."""
        import jax.numpy as jnp

        from repro.kernels import ops, ref

        D = self.g.feat_dim
        if len(self.cache.feat_ids) == 0:
            return jnp.zeros((len(idx), D), jnp.float32)
        table = self.cache.device_arrays(epoch)["feat_cache"]  # lane-padded
        jidx = jnp.asarray(idx, jnp.int32)
        out = (ops.gather_rows(table, jidx) if self.gather == "pallas"
               else ref.gather_rows(table, jidx))
        return out[:, :D] if table.shape[1] != D else out

    def _finalize_unfused(self, spec):
        """The replaced chain — gather dispatch, full-table ``.at[].set``
        miss overlay, then one ``take`` per level — at exact (unpadded)
        shapes, so it retraces on nearly every batch."""
        import jax.numpy as jnp

        n, D = spec.n_ids, self.g.feat_dim
        idx = np.where(spec.hit[:n], spec.cache_pos[:n], -1)
        feats = self._gather_cached(idx, spec.cache_epoch)
        miss_rows = np.flatnonzero(spec.miss_inv[:n] >= 0)
        if len(miss_rows):
            miss = jnp.array(spec.miss_feats[:spec.n_miss, :D])
            miss.block_until_ready()  # staging must not be reused mid-copy
            feats = feats.at[jnp.asarray(miss_rows)].set(miss)
        self.release_spec(spec)
        batch = {"labels": jnp.asarray(spec.labels)}
        for li, (lvl, pos) in enumerate(zip(spec.levels, spec.level_pos)):
            f = jnp.take(feats, jnp.asarray(pos.reshape(-1)), axis=0)
            f = f.reshape(lvl.shape + (D,))
            valid = jnp.asarray(lvl >= 0)
            f = f * valid[..., None].astype(f.dtype)
            batch[f"feats_{li}"] = f
            if li > 0:
                batch[f"mask_{li}"] = valid
        return batch


class ShardedBatchBuilder(DeviceBatchBuilder):
    """Spec builder for the clique-parallel (``shard_map``) executor.

    The host phase is the device backend's (same sampler replay, same
    hit/miss split, same accounting — bit-identical specs), plus the
    ownership routing read off ``CliqueCache.shard_routing``: per cached
    id, which clique device's shard holds the row and at which local slot.
    Routing tables and the shard-stack materialization are resolved **once
    per cache epoch** (not per spec — `tests/test_sharded.py` pins this):
    the first spec build of an epoch reads the routing and materializes the
    per-device shard stack on the prefetch worker — serialized with
    refresh hooks — so the consumer-thread finalize only ever sees
    epoch-pinned buffers.  The *joint* finalize — routed gather across the
    clique, miss overlay, mesh-wide psum — lives in the train loop's
    sharded step; ``pack_sharded_specs`` stacks the per-clique spec groups
    into the mesh-ready arrays it consumes.  Calling ``finalize`` on this
    builder directly falls back to the single-device gather (identical
    rows), so spec-level tooling keeps working without a mesh.
    """

    backend = "sharded"

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._routing_epoch = -1
        self._routing = None

    def _routing_for_epoch(self):
        """Per-epoch memo of (owner, local_slot); re-derived only after an
        online refresh bumps ``cache.epoch``."""
        ep = self.cache.epoch
        if self._routing_epoch != ep:
            owner, local = self.cache.shard_routing()
            if len(owner):
                # materialize the shard stack *here*, on the prefetch
                # worker — serialized with refresh hooks — once per epoch
                self.cache.sharded_device_arrays()
            self._routing = (owner, local)
            self._routing_epoch = ep
        return self._routing

    def fill_spec(self, spec, step=None):
        spec = super().fill_spec(spec, step=step)
        owner, local = self._routing_for_epoch()
        if len(owner) == 0:  # empty feature cache: every id is a host fill
            spec.owner = np.full(len(spec.ids), -1, dtype=np.int32)
            spec.local_slot = np.zeros(len(spec.ids), dtype=np.int32)
            return spec
        safe = np.maximum(spec.cache_pos, 0)  # pads/misses route as -1
        spec.owner = np.where(spec.hit, owner[safe], -1).astype(np.int32)
        spec.local_slot = np.where(spec.hit, local[safe], -1).astype(np.int32)
        return spec


def pack_sharded_specs(spec_groups: Sequence[Sequence[BatchSpec]],
                       feat_dim: int,
                       bucket: int = DEFAULT_BUCKET) -> Dict[str, np.ndarray]:
    """Stack ``ShardedBatchBuilder`` specs — grouped per clique, one spec
    per clique device — into the arrays the hierarchical train step shards
    over the 2-D ``(pod, clique)`` mesh (leading axes = clique index,
    clique-local device).  A single-clique run is simply ``K_c == 1``.

    Unique-id counts differ per device, so ids pad to the bucket-rounded
    mesh-wide max (bounding jit retraces to one per bucket) — the specs
    arrive already bucket-rounded per device, and this pass re-rounds to
    the global max.  Padded tail entries route as misses with zero fill
    rows and are never referenced by any level position.  Returns::

        owner      (K_c, K_g, n_pad) int32   routing: owning clique-local
                                             device, -1 = miss/pad
        local      (K_c, K_g, n_pad) int32   row within the owner's shard
        miss_rows  (K_c, K_g, n_pad, D) f32  host-staged rows at miss slots
        labels     (K_c, K_g, B) int32
        pos_{l}    (K_c, K_g, prod(level_l shape)) int32  positions into ids
        valid_{l}  (K_c, K_g, *level_l shape) bool        lvl >= 0
        cache_epochs (K_c,) int64  per-clique refresh generation (uniform
                                   *within* each clique, asserted; cliques
                                   refresh independently so rows may differ)
    """
    groups = [list(gr) for gr in spec_groups]
    if not groups or any(not gr for gr in groups):
        raise ValueError("pack_sharded_specs: need one non-empty spec "
                         "group per clique")
    k_gs = {len(gr) for gr in groups}
    if len(k_gs) != 1:
        raise ValueError(f"pack_sharded_specs: ragged spec groups "
                         f"{sorted(len(gr) for gr in groups)}; the "
                         "(pod, clique) mesh needs one uniform K_g")
    k_c, k_g = len(groups), k_gs.pop()
    epochs = np.zeros(k_c, dtype=np.int64)
    for ci, gr in enumerate(groups):
        eps = {s.cache_epoch for s in gr}
        if len(eps) != 1:
            raise ValueError(f"pack_sharded_specs: clique {ci} specs span "
                             f"cache epochs {sorted(eps)}; one synchronized "
                             "step must gather from one refresh generation "
                             "per clique")
        epochs[ci] = gr[0].cache_epoch
    flat = [s for gr in groups for s in gr]
    n_pad = max(max(len(s.ids) for s in flat), 1)
    n_pad = -(-n_pad // bucket) * bucket
    owner = np.full((k_c, k_g, n_pad), -1, dtype=np.int32)
    local = np.zeros((k_c, k_g, n_pad), dtype=np.int32)
    miss_rows = np.zeros((k_c, k_g, n_pad, feat_dim), dtype=np.float32)
    for ci, gr in enumerate(groups):
        for gi, s in enumerate(gr):
            n = len(s.owner)
            owner[ci, gi, :n] = s.owner
            local[ci, gi, :n] = np.maximum(s.local_slot, 0)
            mloc = np.flatnonzero(s.miss_inv >= 0) if s.miss_inv is not None \
                else np.zeros(0, np.int64)
            if len(mloc):
                miss_rows[ci, gi, mloc] = s.miss_feats[:s.n_miss, :feat_dim]
    packed = {"owner": owner, "local": local, "miss_rows": miss_rows,
              "labels": np.stack([s.labels for s in flat]).reshape(
                  (k_c, k_g) + flat[0].labels.shape)}
    n_levels = len(flat[0].levels)
    for li in range(n_levels):
        lvl_shape = flat[0].levels[li].shape
        packed[f"pos_{li}"] = np.stack(
            [s.level_pos[li].reshape(-1).astype(np.int32) for s in flat]
        ).reshape((k_c, k_g, -1))
        packed[f"valid_{li}"] = np.stack(
            [s.levels[li] >= 0 for s in flat]).reshape(
                (k_c, k_g) + lvl_shape)
    packed["cache_epochs"] = epochs
    return packed


def make_batch_builder(backend: str, g: CSRGraph,
                       cache: Optional[CliqueCache],
                       fanouts: Sequence[int],
                       counter: Optional[TrafficCounter] = None,
                       dev: int = 0, **kw) -> BatchBuilder:
    if backend == "host":
        return HostBatchBuilder(g, cache, fanouts, counter, dev, **kw)
    if backend == "device":
        return DeviceBatchBuilder(g, cache, fanouts, counter, dev, **kw)
    if backend == "sharded":
        return ShardedBatchBuilder(g, cache, fanouts, counter, dev, **kw)
    raise ValueError(f"unknown batch backend {backend!r} (expected one of "
                     f"{BACKENDS})")
