"""Hotness-aware unified cache (paper §4.2): topology + features in device
memory, sliced across the devices of one clique.

Structures (per clique):
* feature cache — 2-D array of hot-vertex feature rows, slot-major by owning
  device; ``feat_pos[v]`` maps vertex -> global slot (-1 = miss),
  ``feat_owner[slot]`` -> device (for the GPU-GPU traffic matrix).
* topology cache — CSR subset of hot adjacency lists (``topo_pos[v]`` -> row).

The device arrays are jnp (HBM-resident on TPU; gathers go through the Pallas
kernel in repro.kernels).  ``TrafficCounter`` accounts every miss in PCIe
transactions with the same CLS granularity as the cost model, and every
intra-clique remote hit as ICI/NVLink traffic — this is what the Fig. 2/8/10
benchmarks read out.
"""
from __future__ import annotations

import dataclasses
import threading
from functools import partial
from typing import List, Optional, Sequence

import numpy as np

from repro.core.hotness import CLS, S_FLOAT32, S_UINT32, S_UINT64
from repro.graph.csr import CSRGraph


@dataclasses.dataclass
class TrafficCounter:
    n_devices: int
    # traffic[dst, src]: src == n_devices means CPU (PCIe); else peer device
    bytes_matrix: np.ndarray = None
    # topology-exchange traffic, same [dst, src] layout: sampled neighbor
    # ids served by the owner shard (diagonal = own shard, off-diagonal =
    # the routed neighbor exchange's intra-clique hops).  Kept separate
    # from bytes_matrix so feature-gather accounting stays bit-identical
    # between the replicated and sharded topology layouts.
    topo_bytes_matrix: np.ndarray = None
    pcie_transactions: int = 0
    feature_requests: int = 0
    feature_hits: int = 0
    topo_requests: int = 0
    topo_hits: int = 0
    # sampling's host-CSR fallback: spec builds that had to touch the host
    # CSR at all (one *deferred, batched* resolve per build — zero on a
    # warm epoch whose frontier fits the cached topology), and the neighbor
    # draws those resolves produced (miss rows x fanout; counterfactual
    # for the host backend, exact for device/sharded after the
    # stale-parent fix routes cached children through the owner shard)
    host_sample_syncs: int = 0
    host_sampled_edges: int = 0
    # guards the scalar tallies when several prefetch workers account
    # concurrently (integer adds commute, so totals stay bit-identical
    # regardless of build interleaving; the lock only prevents lost updates)
    lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def __post_init__(self):
        if self.bytes_matrix is None:
            self.bytes_matrix = np.zeros(
                (self.n_devices, self.n_devices + 1), dtype=np.int64)
        if self.topo_bytes_matrix is None:
            self.topo_bytes_matrix = np.zeros(
                (self.n_devices, self.n_devices + 1), dtype=np.int64)

    @classmethod
    def for_devices(cls, devices) -> "TrafficCounter":
        """Counter sized so every physical device id has its own column —
        device ids are used directly as matrix indices (no modulo aliasing)."""
        devices = list(devices)
        return cls(n_devices=(max(devices) + 1) if devices else 1)

    @classmethod
    def for_plan(cls, plan) -> "TrafficCounter":
        return cls.for_devices([d for c in plan.partition.cliques for d in c])

    def merge(self, other: "TrafficCounter"):
        """Fold ``other``'s tallies into this counter.  Takes BOTH locks
        (id-ordered, so two concurrent merges of the same pair cannot
        deadlock): ``other`` may still be fed by prefetch workers, and an
        unlocked read of its ten tallies mid-update would tear — some
        fields pre-, some post-accounting — losing updates from the
        merged view.  Regression-tested with a racing worker in
        ``tests/test_cache_and_planner.py``."""
        if other is self:
            raise ValueError("cannot merge a TrafficCounter into itself")
        first, second = ((self, other) if id(self) < id(other)
                         else (other, self))
        with first.lock, second.lock:
            self.bytes_matrix += other.bytes_matrix
            self.topo_bytes_matrix += other.topo_bytes_matrix
            self.pcie_transactions += other.pcie_transactions
            self.feature_requests += other.feature_requests
            self.feature_hits += other.feature_hits
            self.topo_requests += other.topo_requests
            self.topo_hits += other.topo_hits
            self.host_sample_syncs += other.host_sample_syncs
            self.host_sampled_edges += other.host_sampled_edges

    @property
    def feature_hit_rate(self) -> float:
        return self.feature_hits / max(self.feature_requests, 1)

    @property
    def topo_hit_rate(self) -> float:
        return self.topo_hits / max(self.topo_requests, 1)

    @staticmethod
    def _cross_clique(matrix: np.ndarray,
                      cliques: Sequence[Sequence[int]]) -> int:
        total = 0
        for ci, devs in enumerate(cliques):
            others = [d for cj, c in enumerate(cliques) if cj != ci
                      for d in c]
            if others:
                total += int(matrix[np.ix_(list(devs), others)].sum())
        return total

    def cross_clique_bytes(self, cliques: Sequence[Sequence[int]]) -> int:
        """Device-to-device bytes between devices of *different* cliques.
        The hierarchical executor's invariant is that this is exactly 0 —
        feature rows only travel intra-clique (peer exchange) or over
        PCIe (host fill); tests and the hierarchy benchmark gate on it."""
        return self._cross_clique(self.bytes_matrix, cliques)

    def cross_clique_topo_bytes(self, cliques: Sequence[Sequence[int]]) -> int:
        """Topology-exchange bytes between devices of different cliques.
        The sharded topology cache's invariant mirrors the feature one:
        every frontier row is served by an owner shard *within* the
        requester's clique (or by the host over PCIe), so this is exactly
        0 — the topology benchmark and the sharded suite gate on it."""
        return self._cross_clique(self.topo_bytes_matrix, cliques)

    def per_clique_split(self, cliques: Sequence[Sequence[int]]) -> list:
        """Feature-gather traffic aggregated per clique: local-hit bytes
        (each device's own partition, the matrix diagonal), peer bytes
        (intra-clique exchange, off-diagonal within the clique block) and
        host-fill bytes (the PCIe column)."""
        out = []
        for ci, devs in enumerate(cliques):
            devs = list(devs)
            sub = self.bytes_matrix[np.ix_(devs, devs)]
            out.append({"clique": ci,
                        "local_bytes": int(np.trace(sub)),
                        "peer_bytes": int(sub.sum() - np.trace(sub)),
                        "host_fill_bytes": int(
                            self.bytes_matrix[devs, -1].sum())})
        return out

    def publish_metrics(self, reg) -> None:
        """Mirror the live tallies into a telemetry ``MetricsRegistry``
        (repro.obs) — pulled at snapshot boundaries, so accounting hot
        paths pay nothing.  One consistent capture under the lock, then
        monotonic ``set_total`` per counter: the registry's window deltas
        telescope to these exact totals.  Byte matrices publish both as
        per-tier aggregates (local diagonal / intra-clique peer /
        PCIe column) and as per-``(dst, src)`` pair counters for every
        pair that has ever moved a byte."""
        with self.lock:
            bm = self.bytes_matrix.copy()
            tm = self.topo_bytes_matrix.copy()
            scalars = {
                "traffic.feature_requests": self.feature_requests,
                "traffic.feature_hits": self.feature_hits,
                "traffic.topo_requests": self.topo_requests,
                "traffic.topo_hits": self.topo_hits,
                "traffic.pcie_transactions": self.pcie_transactions,
                "traffic.host_sample_syncs": self.host_sample_syncs,
                "traffic.host_sampled_edges": self.host_sampled_edges,
            }
        for name, v in scalars.items():
            reg.counter(name).set_total(int(v))
        for name, m in (("traffic.feat_bytes", bm),
                        ("traffic.topo_bytes", tm)):
            dev = m[:, :-1]
            reg.counter(name, tier="local").set_total(int(np.trace(dev)))
            reg.counter(name, tier="peer").set_total(
                int(dev.sum() - np.trace(dev)))
            reg.counter(name, tier="pcie").set_total(int(m[:, -1].sum()))
            for dst, src in zip(*np.nonzero(m)):
                src_lbl = "host" if src == self.n_devices else int(src)
                reg.counter(f"{name}_pair", dst=int(dst),
                            src=src_lbl).set_total(int(m[dst, src]))


class CliqueCache:
    """One clique's unified cache."""

    TOPOLOGY_MODES = ("sharded", "replicated")

    def __init__(self, g: CSRGraph, devices: Sequence[int],
                 feat_ids_per_dev: Sequence[np.ndarray],
                 topo_ids_per_dev: Sequence[np.ndarray],
                 materialize: bool = True,
                 topology_mode: str = "sharded"):
        if topology_mode not in self.TOPOLOGY_MODES:
            raise ValueError(f"unknown topology_mode {topology_mode!r} "
                             f"(expected one of {self.TOPOLOGY_MODES})")
        self.g = g
        self.devices = list(devices)
        # "sharded" (default): each device holds only the CSR rows the plan
        # assigned to it; the union of shards is the cached topology, and
        # sampling routes each frontier row to its owner shard (K_g x the
        # topology per device budget).  "replicated": every device holds
        # the whole union — the equal-contents legacy layout kept as the
        # parity oracle and the equal-memory benchmark baseline.
        self.topology_mode = topology_mode
        # ---- feature cache ----
        self.feat_pos = np.full(g.n, -1, dtype=np.int64)
        owners = []
        all_ids = []
        for gi, ids in enumerate(feat_ids_per_dev):
            all_ids.append(ids)
            owners.append(np.full(len(ids), gi, dtype=np.int32))
        ids = np.concatenate(all_ids) if all_ids else np.zeros(0, np.int64)
        self.feat_ids = ids.astype(np.int64)
        self.feat_owner = (np.concatenate(owners) if owners
                           else np.zeros(0, np.int32))
        self.feat_pos[self.feat_ids] = np.arange(len(self.feat_ids))
        self._materialized = materialize
        if materialize:
            self.feat_cache = (g.get_features(self.feat_ids)
                               if len(self.feat_ids)
                               else np.zeros((0, g.feat_dim), np.float32))
        else:
            self.feat_cache = None
        # ---- topology cache (CSR subset) ----
        self._build_topology(topo_ids_per_dev)
        # device residency is double-buffered across refresh epochs: the
        # previous epoch's arrays stay alive until the epoch after next so
        # in-flight batch specs keep gathering from the buffer they indexed
        self.epoch = 0
        self._device_arrays = None
        self._prev_device_arrays = None
        self._sharded_arrays = None
        self._prev_sharded_arrays = None
        self._shard_routing = None
        self._shard_sharding = None  # set by place_shards
        self._prev_epoch = -1
        # guards the lazy materializations below: with the prefetch worker
        # *pool*, several devices of one clique can race the first spec
        # build.  Mutating refreshes never need it — the refresh hook is
        # serialized with every build by the Prefetcher's step barrier.
        self._mat_lock = threading.RLock()

    @staticmethod
    def _subset_csr(g: CSRGraph, tids: np.ndarray):
        """CSR subset for ``tids``: (indptr, indices) with row ``r`` holding
        ``tids[r]``'s full adjacency in host order (the bit-parity anchor:
        any sampler drawing ``r % deg`` offsets against it reproduces
        ``host_sample_level`` exactly)."""
        deg = (g.indptr[tids + 1] - g.indptr[tids]) if len(tids) \
            else np.zeros(0, np.int64)
        indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
        if len(tids):
            # vectorized adjacency copy: slot k of the subset CSR maps to
            # g.indices[g.indptr[tids[row]] + (k - indptr[row])]
            starts = g.indptr[tids]
            total = int(indptr[-1])
            src = (np.arange(total, dtype=np.int64)
                   - np.repeat(indptr[:-1], deg)
                   + np.repeat(starts, deg))
            indices = g.indices[src].astype(np.int32)
        else:
            indices = np.zeros(0, np.int32)
        return indptr, indices

    def _build_topology(self, topo_ids_per_dev: Sequence[np.ndarray]) -> None:
        """(Re)build the topology cache from per-device id lists.

        Always builds the *union* CSR subset (``topo_pos`` / ``cache_indptr``
        / ``cache_indices``) — the host mirror every fallback resolve and
        accounting pass reads, and the replicated layout's device residency.
        In sharded mode additionally builds the per-device shard form: the
        vertex->owner routing tables (``topo_owner`` / ``topo_local``) and
        the padded per-shard CSR stacks (``topo_shard_indptr`` (k_g, R+1),
        ``topo_shard_indices`` (k_g, E)) the routed neighbor exchange
        gathers from.  Each shard stores its vertices' adjacency in host
        order, so shard sampling is bit-identical to the union CSR."""
        g = self.g
        per_dev = [np.asarray(t).astype(np.int64) for t in topo_ids_per_dev]
        tids = (np.concatenate(per_dev) if per_dev
                else np.zeros(0, np.int64))
        self.topo_ids = tids
        self.topo_ids_per_dev = per_dev
        self.topo_pos = np.full(g.n, -1, dtype=np.int64)
        self.topo_pos[tids] = np.arange(len(tids))
        deg = (g.indptr[tids + 1] - g.indptr[tids]) if len(tids) \
            else np.zeros(0, np.int64)
        self.cache_indptr = np.concatenate([[0], np.cumsum(deg)]).astype(np.int64)
        self.cache_indices = (self._subset_csr(g, tids)[1]
                              if self._materialized else None)
        self.topo_owner = None
        self.topo_local = None
        self.topo_shard_indptr = None
        self.topo_shard_indices = None
        if self.topology_mode != "sharded":
            return
        # vertex -> (owner shard, row within it); later lists win on
        # duplicate ids, matching the union's topo_pos assignment order
        self.topo_owner = np.full(g.n, -1, dtype=np.int32)
        self.topo_local = np.zeros(g.n, dtype=np.int64)
        for gi, ids in enumerate(per_dev):
            self.topo_owner[ids] = gi
            self.topo_local[ids] = np.arange(len(ids))
        if not self._materialized:
            return
        k_g = max(len(self.devices), 1)
        shard_csrs = [self._subset_csr(g, ids) for ids in per_dev]
        shard_csrs += [self._subset_csr(g, np.zeros(0, np.int64))
                       for _ in range(k_g - len(shard_csrs))]
        R = max(len(p) - 1 for p, _ in shard_csrs)
        E = max(max(len(ix) for _, ix in shard_csrs), 1)
        self.topo_shard_indptr = np.zeros((k_g, R + 1), dtype=np.int64)
        self.topo_shard_indices = np.zeros((k_g, E), dtype=np.int32)
        for gi, (p, ix) in enumerate(shard_csrs):
            self.topo_shard_indptr[gi, :len(p)] = p
            self.topo_shard_indptr[gi, len(p):] = p[-1]  # pad rows: deg 0
            self.topo_shard_indices[gi, :len(ix)] = ix

    # ---- device residency ----
    @staticmethod
    def _lane_padded(D: int) -> int:
        """Feature columns padded to the 128-lane boundary — shared by the
        flat table and the shard stack so the Pallas row DMAs (which move
        whole lane tiles) never re-pad per batch."""
        return -(-max(D, 1) // 128) * 128

    def _epoch_view(self, current, prev, epoch: Optional[int], what: str):
        """Double-buffered epoch pinning, shared by the flat and sharded
        views: ``epoch`` selects the current or the single retained
        previous buffer; anything older raises."""
        if epoch is None or epoch == self.epoch:
            return current
        if epoch == self._prev_epoch and prev is not None:
            return prev
        raise RuntimeError(
            f"cache epoch {epoch} is no longer resident{what} (current "
            f"{self.epoch}, retained {self._prev_epoch}); refresh_interval "
            "must be larger than the prefetch depth")

    def device_arrays(self, epoch: Optional[int] = None):
        """jnp copies (lazy): the HBM-resident cache halves.

        ``feat_cache`` columns are padded once to the 128-lane boundary
        (only when feat_dim exceeds one lane tile) so the per-batch Pallas
        gather never re-pads the whole table; gather consumers slice back
        to ``g.feat_dim``.

        ``epoch`` pins a refresh generation: batch specs built before an
        online cache refresh finalize against the buffer they indexed (the
        double buffer retains exactly one previous epoch — refresh
        intervals must exceed the prefetch depth, which the manager
        enforces)."""
        if self._device_arrays is None:
            with self._mat_lock:
                if self._device_arrays is None:
                    import jax.numpy as jnp

                    fc = self.feat_cache
                    D = fc.shape[1]
                    Dp = self._lane_padded(D)
                    if Dp != D:
                        fc = np.pad(fc, ((0, 0), (0, Dp - D)))
                    # feat_cache / feat_pos MUST be copies: on the CPU
                    # backend jnp.asarray zero-copy aliases aligned numpy
                    # buffers, and apply_feature_delta mutates those host
                    # mirrors in place — an aliased "retained" epoch would
                    # be silently rewritten.  The topology arrays are
                    # replaced wholesale (never mutated), so aliasing them
                    # is safe.
                    arrays = {
                        "feat_cache": jnp.array(fc),
                        "feat_pos": jnp.array(self.feat_pos),
                        "cache_indptr": jnp.asarray(self.cache_indptr),
                        "cache_indices": jnp.asarray(self.cache_indices),
                        "topo_pos": jnp.asarray(self.topo_pos),
                    }
                    arrays.update(self._topo_shard_jnp())
                    # publish whole: readers test for None without the lock
                    self._device_arrays = arrays
        return self._epoch_view(self._device_arrays,
                                self._prev_device_arrays, epoch, "")

    def _topo_shard_jnp(self) -> dict:
        """jnp views of the sharded topology residency (empty dict in
        replicated mode): the vertex->owner routing tables and the padded
        per-shard CSR stacks.  Plain ``asarray`` aliasing is safe — like
        the union CSR these arrays are replaced wholesale by
        ``replace_topology``, never mutated in place."""
        if self.topo_owner is None or self.topo_shard_indptr is None:
            return {}
        import jax.numpy as jnp

        return {"topo_owner": jnp.asarray(self.topo_owner),
                "topo_local": jnp.asarray(self.topo_local),
                "topo_shard_indptr": jnp.asarray(self.topo_shard_indptr),
                "topo_shard_indices": jnp.asarray(self.topo_shard_indices)}

    # ---- per-device shard views (clique-parallel executor) ----
    def shard_routing(self):
        """Ownership routing tables for the sharded executor: two int32
        arrays over global feature-cache slots, ``owner[s]`` (clique-local
        index of the device whose HBM shard holds slot ``s``) and
        ``local_slot[s]`` (the row of that slot within the owner's shard).
        Together with ``split_hits`` this is how a batch's cached ids are
        routed: requester == owner -> local-hit gather, requester != owner
        -> intra-clique peer exchange, pos < 0 -> host fill.

        Slots freed by an online refresh keep their last routing entry;
        they are unreachable (``feat_pos`` no longer maps any vertex to
        them), so the stale entry is never consulted.

        Memoized (the tables are invariant between refreshes and read per
        spec build on the prefetch hot path); ``apply_feature_delta``
        invalidates."""
        if self._shard_routing is None:
            with self._mat_lock:
                if self._shard_routing is None:
                    owner = self.feat_owner.astype(np.int32)
                    local = np.zeros(len(owner), dtype=np.int32)
                    for gi in range(len(self.devices)):
                        sel = np.flatnonzero(owner == gi)
                        local[sel] = np.arange(len(sel), dtype=np.int32)
                    self._shard_routing = (owner, local)
        return self._shard_routing

    def shard_row_count(self) -> int:
        """Rows of the largest per-device shard (all shards pad to this)."""
        if len(self.feat_owner) == 0:
            return 0
        return int(np.bincount(self.feat_owner,
                               minlength=len(self.devices)).max())

    def place_shards(self, devices: Sequence) -> None:
        """Pin where ``sharded_device_arrays`` puts the shard stack: one
        jax device per clique device, in clique-local order (the clique's
        row of the hierarchical mesh).  The stack is then sharded along its
        leading axis, so each device holds its own partition and nothing
        more.  Call before the first spec build of a run; unplaced caches
        keep the stack on the default device."""
        from jax.sharding import Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.launch.mesh import CLIQUE_AXIS

        if len(devices) != len(self.devices):
            raise ValueError(f"place_shards: {len(devices)} jax devices for "
                             f"a {len(self.devices)}-device clique")
        sharding = NamedSharding(Mesh(np.asarray(list(devices)),
                                      (CLIQUE_AXIS,)), P(CLIQUE_AXIS))
        if sharding != self._shard_sharding:
            self._shard_sharding = sharding
            self._sharded_arrays = None  # rebuilt on the new devices

    def sharded_device_arrays(self, epoch: Optional[int] = None):
        """The cache's *partitioned* device residency: the feature table
        restacked as one shard per clique device, ``{"feat_shards": (k_g,
        R, D_padded)}`` — row ``local_slot[s]`` of shard ``owner[s]`` is
        global slot ``s``.  Placed by ``place_shards``, shard ``g`` lives on
        clique device ``g`` only, which is what ``routed_gather`` serves
        local hits from (peer hits ride the intra-clique exchange).

        Same lazy build + double-buffered epoch pinning as
        ``device_arrays``: specs built before an online refresh finalize
        against the shard stack they indexed."""
        if self._sharded_arrays is None:
            with self._mat_lock:
                if self._sharded_arrays is None:
                    import jax

                    if self.feat_cache is None:
                        raise RuntimeError(
                            "sharded_device_arrays needs a materialized "
                            "cache (build the plan with "
                            "materialize_caches=True)")
                    k_g = len(self.devices)
                    owner, local = self.shard_routing()
                    fc = self.feat_cache
                    D = fc.shape[1]
                    shards = np.zeros((k_g, self.shard_row_count(),
                                       self._lane_padded(D)), np.float32)
                    if len(owner):
                        shards[owner, local, :D] = fc
                    # one H2D copy per device, of that device's shard only
                    self._sharded_arrays = {"feat_shards": jax.device_put(
                        shards, self._shard_sharding)}
        return self._epoch_view(self._sharded_arrays,
                                self._prev_sharded_arrays, epoch,
                                " in sharded form")

    # ---- online refresh (cache manager API) ----
    def begin_epoch(self) -> int:
        """Rotate the device double buffer: the current arrays become the
        retained previous epoch; subsequent mutations build the new one.
        Returns the new epoch id.

        If the device arrays were never materialized (host-backend
        training) there is nothing to retain and nothing that can pin the
        outgoing epoch: host reads go through the numpy mirrors and are
        serialized with refreshes on the prefetch worker, while any device
        spec build would have materialized the arrays already.  The
        rotation then only bumps the epoch id."""
        self._prev_device_arrays = self._device_arrays
        self._prev_sharded_arrays = self._sharded_arrays
        had_any = (self._device_arrays is not None
                   or self._sharded_arrays is not None)
        self._prev_epoch = self.epoch if had_any else -1
        self.epoch += 1
        return self.epoch

    def apply_feature_delta(self, evict_ids: np.ndarray,
                            admit_ids: np.ndarray,
                            admit_owner: np.ndarray,
                            admit_rows: Optional[np.ndarray] = None,
                            scatter: str = "auto") -> dict:
        """Evict ``evict_ids`` from the feature cache and write the admitted
        rows into the freed slots (slot reuse — no reallocation, no change
        to cache capacity).

        admit_owner: per admitted id, the owning device's *clique-local*
        index (CSLP local preference).  admit_rows defaults to a host fetch
        of the admitted ids.  If fewer slots are freed than ids admitted,
        the admission list is truncated (capacity is fixed); surplus freed
        slots become empty (-1 in ``feat_ids``).

        Device side: a Pallas scatter writes the admitted rows into a *new*
        table buffer (``scatter='pallas'|'xla'|'auto'``), leaving the
        previous epoch's buffer untouched for in-flight batches.  Call
        ``begin_epoch`` first.

        Returns {"evicted": n, "admitted": n, "bytes_h2d": host->device
        admission traffic}.
        """
        evict_ids = np.asarray(evict_ids, dtype=np.int64)
        admit_ids = np.asarray(admit_ids, dtype=np.int64)
        slots = self.feat_pos[evict_ids]
        if (slots < 0).any():
            raise ValueError("apply_feature_delta: evict_ids contain "
                             "vertices that are not cached")
        self.feat_pos[evict_ids] = -1
        self.feat_ids[slots] = -1
        # reuse every empty slot (just-freed + leftovers of past refreshes)
        free = np.flatnonzero(self.feat_ids < 0)
        n_admit = min(len(admit_ids), len(free))
        admit_ids = admit_ids[:n_admit]
        admit_owner = np.asarray(admit_owner, dtype=np.int32)[:n_admit]
        use = free[:n_admit]
        # host-side slot maps
        self.feat_pos[admit_ids] = use
        self.feat_ids[use] = admit_ids
        self.feat_owner[use] = admit_owner
        if admit_rows is None:
            admit_rows = (self.g.get_features(admit_ids) if n_admit
                          else np.zeros((0, self.g.feat_dim), np.float32))
        admit_rows = np.asarray(admit_rows, dtype=np.float32)[:n_admit]
        if self.feat_cache is not None and n_admit:
            self.feat_cache[use] = admit_rows
        # device side: double-buffered scatter into the freed slots
        if self._device_arrays is not None:
            import jax.numpy as jnp

            from repro.kernels import kernel_impl, ops, ref

            old = self._device_arrays
            table = old["feat_cache"]
            Dp = table.shape[1]
            rows = admit_rows
            if rows.shape[0] and Dp != rows.shape[1]:
                rows = np.pad(rows, ((0, 0), (0, Dp - rows.shape[1])))
            jidx = jnp.asarray(use, jnp.int32)
            jrows = jnp.asarray(rows)
            new_table = (ref.scatter_rows(table, jidx, jrows)
                         if kernel_impl(scatter) == "xla"
                         else ops.scatter_rows(table, jidx, jrows))
            new = dict(old)
            new["feat_cache"] = new_table
            new["feat_pos"] = jnp.array(self.feat_pos)  # copy: mirror mutates
            self._device_arrays = new
        # partitioned view: routing changed, so drop the memo and — if the
        # sharded stack was materialized — rebuild it *eagerly here*, on
        # the refresh (prefetch worker) thread.  A lazy rebuild would run
        # on the consumer thread at the next finalize and could snapshot
        # the host mirrors mid-way through the *next* refresh's in-place
        # mutation; rebuilding before this call returns keeps consumers on
        # epoch-pinned buffers only, matching the flat device_arrays path.
        # The retained previous epoch was stashed by begin_epoch.
        self._shard_routing = None
        if self._sharded_arrays is not None:
            self._sharded_arrays = None
            self.sharded_device_arrays()
        return {"evicted": int(len(evict_ids)), "admitted": int(n_admit),
                "bytes_h2d": int(n_admit) * self.g.feat_dim * S_FLOAT32}

    def replace_topology(self, topo_ids_per_dev: Sequence[np.ndarray]) -> None:
        """Swap the topology half of the cache for a new planned id set.

        Topology is only read at spec-build time (on the prefetch worker,
        serialized with refreshes), never at finalize time, so a full
        rebuild — unlike the feature table — needs no epoch retention; the
        rebuilt arrays simply join the current epoch's dict."""
        self._build_topology(topo_ids_per_dev)
        if self._device_arrays is not None:
            import jax.numpy as jnp

            new = dict(self._device_arrays)
            new["cache_indptr"] = jnp.asarray(self.cache_indptr)
            new["cache_indices"] = jnp.asarray(self.cache_indices)
            new["topo_pos"] = jnp.asarray(self.topo_pos)
            # drop any stale shard entries before re-adding (a refresh can
            # legally flip the per-shard stack shapes)
            for k in ("topo_owner", "topo_local", "topo_shard_indptr",
                      "topo_shard_indices"):
                new.pop(k, None)
            new.update(self._topo_shard_jnp())
            self._device_arrays = new

    def feat_ids_by_device(self) -> List[np.ndarray]:
        """Current per-device cached feature ids (clique-local order) —
        the cache manager's view of residency for delta planning.  Empty
        slots (evicted, not yet re-admitted) are skipped."""
        live = self.feat_ids >= 0
        return [self.feat_ids[live & (self.feat_owner == gi)]
                for gi in range(len(self.devices))]

    def device_sample_cached(self, seeds, fanout: int, key=None, *,
                             rand=None):
        """Fixed-fanout neighbor sampling *on device* from the HBM-resident
        topology cache (the TPU analogue of Legion's GPU sampling).

        Seeds whose adjacency is cached sample from the cache CSR; misses
        (uncached or negative/padded seeds) return -1 rows for the host
        pipeline to fill (and account as PCIe).  Randomness comes either
        from a jax PRNG ``key`` or from a precomputed host array ``rand``
        of shape (B, fanout) — the latter lets the device path replay the
        exact draws of the host sampler (bit-identical subgraphs, which the
        host/device parity tests rely on).

        In sharded topology mode each row routes through its owner shard's
        padded CSR (the single-process form of the routed neighbor
        exchange — under the clique mesh the same lookup is the
        ``kernels.gather.routed_neighbor_sample`` collective); every shard
        stores its vertices' adjacency in host order, so the outputs are
        bit-identical to the replicated layout and to the host sampler.
        Returns (neighbors (B, fanout) int32, hit_mask (B,) bool).
        """
        import jax
        import jax.numpy as jnp

        # materialize before any early return: the first call happens at
        # spec-build time on the prefetch worker (serialized with refresh
        # hooks), and later refreshes rely on that — a lazy consumer-thread
        # materialization could snapshot the host mirrors mid-mutation
        da = self.device_arrays()
        seeds = jnp.asarray(seeds, jnp.int32)
        if len(self.cache_indices) == 0:
            # empty topology cache: every row is a host fill (gathering
            # from the zero-length adjacency array would be an XLA error)
            return (jnp.full(seeds.shape + (fanout,), -1, jnp.int32),
                    jnp.zeros(seeds.shape, bool))
        if rand is not None:
            r = jnp.asarray(rand)
        else:
            r = jax.random.randint(key, (seeds.shape[0], fanout), 0, 1 << 30)
        sharded = self.topology_mode == "sharded"
        tables = ((da["topo_owner"], da["topo_local"],
                   da["topo_shard_indptr"], da["topo_shard_indices"])
                  if sharded else
                  (da["topo_pos"], da["cache_indptr"], da["cache_indices"]))
        return _sample_hop()(tables, seeds, r, sharded=sharded)

    def device_sample_chain(self, seeds, fanouts: Sequence[int],
                            rands: Sequence[np.ndarray]):
        """Enqueue every hop's device half back-to-back — *no host sync*.

        Hop ``k`` samples directly from hop ``k-1``'s device output, so the
        whole multi-hop chain dispatches before any result is read back
        (one sync per batch instead of one per hop).  A frontier row whose
        parent was a topology miss carries ``-1`` on device, so the child
        row simply comes back as a miss too; the caller's single host
        resolve pass (``graph.sampling.cache_sample_batch``) re-samples
        exactly those rows from the host CSR with the same ``rands`` draws,
        which keeps the composed levels bit-identical to the host sampler.

        ``rands[k]`` must be the hop-``k`` draw of shape
        ``(len(flattened frontier_k), fanouts[k])``.  Returns two lists of
        *unmaterialized* jax arrays: per-hop neighbors (flat, fanout) and
        per-hop device-hit masks.
        """
        import jax.numpy as jnp

        outs, hits = [], []
        frontier = jnp.asarray(np.asarray(seeds), jnp.int32)
        for f, r in zip(fanouts, rands):
            out, hit = self.device_sample_cached(frontier, f, rand=r)
            outs.append(out)
            hits.append(hit)
            frontier = out.reshape(-1)
        return outs, hits

    @property
    def feat_bytes(self) -> int:
        return len(self.feat_ids) * self.g.feat_dim * S_FLOAT32

    @property
    def topo_bytes(self) -> int:
        """Bytes of the cached topology *union* (adjacency + id map)."""
        return int(self.cache_indptr[-1]) * S_UINT32 + len(self.topo_ids) * S_UINT64

    def topo_bytes_by_device(self) -> List[int]:
        """Per-device topology residency: each device's own shard under
        ``"sharded"`` (the union is spread across the clique), the whole
        union on every device under ``"replicated"``.  This is the
        honest per-device HBM cost the equal-memory benchmark equates."""
        if self.topology_mode != "sharded":
            return [self.topo_bytes for _ in self.devices]
        out = []
        for ids in self.topo_ids_per_dev:
            deg = (self.g.indptr[ids + 1] - self.g.indptr[ids]) if len(ids) \
                else np.zeros(0, np.int64)
            out.append(int(deg.sum()) * S_UINT32 + len(ids) * S_UINT64)
        return out

    # ---- accounting + extraction ----
    def split_hits(self, ids: np.ndarray):
        """Hit/miss split of a unique-vertex request against the feature
        cache: returns (pos, hit) where ``pos[i]`` is the cache slot for
        ``ids[i]`` (-1 on miss) and ``hit = pos >= 0``.  This is the only
        sanctioned way for batch backends to read cache placement — they
        must not poke at ``feat_pos`` directly."""
        ids = np.asarray(ids, dtype=np.int64)
        pos = self.feat_pos[ids]
        return pos, pos >= 0

    def account_feature_gather(self, pos: np.ndarray, hit: np.ndarray,
                               requester_dev: int,
                               counter: TrafficCounter) -> None:
        """Traffic accounting for one feature gather, shared by the host and
        device batch backends (identical counts by construction).  Hits are
        charged to their owning device's column (physical device ids index
        the matrix directly), misses to the CPU/PCIe column."""
        n_miss = int((~hit).sum())
        row_bytes = self.g.feat_dim * S_FLOAT32
        tx_per_row = int(np.ceil(row_bytes / CLS))
        if hit.any() and max(self.devices) >= counter.n_devices:
            raise ValueError(
                f"TrafficCounter(n_devices={counter.n_devices}) cannot "
                f"index clique devices {self.devices}; size it from the "
                "plan (TrafficCounter.for_plan / for_devices)")
        with counter.lock:
            counter.feature_requests += len(pos)
            counter.feature_hits += int(hit.sum())
            counter.pcie_transactions += tx_per_row * n_miss
            counter.bytes_matrix[requester_dev, -1] += row_bytes * n_miss
            if hit.any():
                owners = self.feat_owner[pos[hit]]
                cnt = np.bincount(owners, minlength=len(self.devices))
                np.add.at(counter.bytes_matrix[requester_dev],
                          np.asarray(self.devices), row_bytes * cnt)

    def extract_features(self, ids: np.ndarray, requester_dev: int,
                         counter: Optional[TrafficCounter] = None,
                         store=None, step: Optional[int] = None) -> np.ndarray:
        """Gather rows for `ids` (unique sampled vertices of one batch),
        accounting hits (local/peer) and misses (CPU over PCIe).

        ``store`` routes the HBM misses through a tiered
        :class:`~repro.core.feature_store.FeatureStore` (host-RAM cache
        over an SSD-resident table) instead of the direct ``g.get_features``
        host fill; ``step`` keys the store's lookahead/prefetch state.
        Rows are bitwise identical either way — the store is an
        accounting + placement layer, never a value transform."""
        ids = np.asarray(ids, dtype=np.int64)
        pos, hit = self.split_hits(ids)
        out = np.empty((len(ids), self.g.feat_dim), dtype=np.float32)
        if hit.any():
            out[hit] = self.feat_cache[pos[hit]]
        if (~hit).any():
            miss_ids = ids[~hit]
            out[~hit] = (store.gather(miss_ids, step=step, dev=requester_dev)
                         if store is not None
                         else self.g.get_features(miss_ids))
        if store is not None:
            store.record_hbm(len(ids), int(hit.sum()))
        if counter is not None:
            self.account_feature_gather(pos, hit, requester_dev, counter)
        return out

    def sample_accounting(self, srcs: np.ndarray, fanout: int,
                          counter: TrafficCounter, requester_dev: int):
        """Account one sampling level: adjacency reads of `srcs` hit the topo
        cache or cost PCIe transactions (Eq. 3/4 granularity).

        The legacy counters (requests/hits/pcie/bytes_matrix) are mode-
        independent by construction: the sharded and replicated layouts
        cache the *same* vertex set, so the hit split is identical.  The
        topology-specific exchange traffic lands in ``topo_bytes_matrix``:
        each hit delivers its ``fanout`` sampled neighbor ids from the
        owner shard (a peer column under sharded mode, the requester's own
        diagonal under replicated), and each miss adds ``fanout`` edges to
        ``host_sampled_edges`` — the host-side sampling work the sharded
        cache exists to eliminate."""
        srcs = np.asarray(srcs, dtype=np.int64)
        srcs = srcs[srcs >= 0]
        pos = self.topo_pos[srcs]
        hit = pos >= 0
        miss = srcs[~hit]
        tx = n_bytes = 0
        if len(miss):
            deg = self.g.indptr[miss + 1] - self.g.indptr[miss]
            tx = int((np.ceil(deg * S_UINT32 / CLS).astype(np.int64) + 1).sum())
            n_bytes = int((deg * S_UINT32).sum())
        hb = fanout * S_UINT32
        with counter.lock:
            counter.topo_requests += len(srcs)
            counter.topo_hits += int(hit.sum())
            counter.pcie_transactions += tx
            counter.bytes_matrix[requester_dev, -1] += n_bytes
            counter.host_sampled_edges += fanout * len(miss)
            counter.topo_bytes_matrix[requester_dev, -1] += n_bytes
            if hit.any():
                if self.topology_mode == "sharded":
                    owners = self.topo_owner[srcs[hit]]
                    cnt = np.bincount(owners, minlength=len(self.devices))
                    np.add.at(counter.topo_bytes_matrix[requester_dev],
                              np.asarray(self.devices), hb * cnt)
                else:
                    counter.topo_bytes_matrix[
                        requester_dev, requester_dev] += hb * int(hit.sum())

    def publish_metrics(self, reg, clique: int = 0) -> None:
        """Residency gauges for the telemetry registry (repro.obs):
        cached feature/topology rows and the refresh epoch, labeled per
        clique.  Pulled at snapshot boundaries only."""
        reg.gauge("cache.feat_rows", clique=clique).set(len(self.feat_ids))
        reg.gauge("cache.topo_rows", clique=clique).set(len(self.topo_ids))
        reg.gauge("cache.epoch", clique=clique).set(self.epoch)


_sample_hop_jit = None  # built on first use (keeps jax import lazy)


def _sample_hop():
    """One hop of ``CliqueCache.device_sample_cached`` as one jitted
    program (compiled once per frontier shape, not once per eager op):
    ``(tables, seeds, rand, *, sharded) -> (neighbors, hit)``, where
    ``tables`` is (owner, local, shard indptr, shard indices) for the
    sharded layout and (topo_pos, indptr, indices) for the replicated
    one."""
    global _sample_hop_jit
    if _sample_hop_jit is None:
        import jax
        import jax.numpy as jnp

        @partial(jax.jit, static_argnames=("sharded",))
        def sample_hop(tables, seeds, r, *, sharded: bool):
            valid = seeds >= 0
            safe_seed = jnp.where(valid, seeds, 0)
            if sharded:
                owner, local, indptr, indices = tables
                own = owner[safe_seed]
                hit = (own >= 0) & valid
                o = jnp.maximum(own, 0)
                loc = local[safe_seed]
                start = indptr[o, loc]
                deg = indptr[o, loc + 1] - start
                offs = r % jnp.maximum(deg, 1)[:, None]
                idx = jnp.minimum(start[:, None] + offs, indices.shape[1] - 1)
                out = indices[o[:, None], idx].astype(jnp.int32)
            else:
                pos_map, indptr, indices = tables
                pos = pos_map[safe_seed]
                hit = (pos >= 0) & valid
                safe = jnp.maximum(pos, 0)
                start = indptr[safe]
                deg = indptr[safe + 1] - start
                offs = r % jnp.maximum(deg, 1)[:, None]
                idx = jnp.minimum(start[:, None] + offs, indices.shape[0] - 1)
                out = indices[idx].astype(jnp.int32)
            ok = hit & (deg > 0)
            return jnp.where(ok[:, None], out, -1), hit

        _sample_hop_jit = sample_hop
    return _sample_hop_jit


def stack_hierarchical_shards(caches: Sequence[CliqueCache],
                              epochs: Sequence[int], mesh):
    """Stack every clique's partitioned feature residency into the one
    tensor the hierarchical executor shards over the ``("pod", "clique")``
    ``mesh``: shape ``(K_c, K_g, R_max, D_padded)`` with sharding
    ``P(POD_AXIS, CLIQUE_AXIS)`` — row ``ci`` is clique ``ci``'s
    ``sharded_device_arrays(epochs[ci])["feat_shards"]``.

    Each clique's stack must already sit on its mesh row
    (``CliqueCache.place_shards``), so the stack is assembled from the
    per-device shards where they lie: no feature row moves between
    devices, and a step that consumes the result reshards nothing.

    Each clique plans its own cache from its own partition hotness, so
    per-clique row counts differ; shorter shards zero-pad (on their own
    device) to the tallest clique's ``R``.  The pad rows are unreachable —
    every routing entry (``owner``/``local_slot``) indexes within its own
    clique's real rows.  ``epochs`` pins each clique's refresh generation
    independently (online refreshes fire per clique, so one synchronized
    step may legitimately combine different epochs across cliques — never
    within one).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import CLIQUE_AXIS, POD_AXIS

    if len(caches) != len(epochs):
        raise ValueError(f"{len(caches)} caches but {len(epochs)} epochs")
    k_gs = {len(c.devices) for c in caches}
    if len(k_gs) != 1:
        raise ValueError(f"ragged clique sizes {sorted(k_gs)}: the "
                         "hierarchical shard stack needs one uniform K_g")
    stacks = [c.sharded_device_arrays(int(e))["feat_shards"]
              for c, e in zip(caches, epochs)]
    R = max(s.shape[1] for s in stacks)
    pieces = []
    for ci, s in enumerate(stacks):
        by_dev = {sh.device: sh.data for sh in s.addressable_shards}
        for gi, dev in enumerate(mesh.devices[ci]):
            piece = by_dev.get(dev)
            if piece is None or piece.shape[0] != 1:
                raise ValueError(
                    f"clique {ci}'s shard stack is not placed on its mesh "
                    "row; call CliqueCache.place_shards with the row's "
                    "devices before building specs")
            if piece.shape[1] != R:
                piece = jnp.pad(piece, ((0, 0), (0, R - piece.shape[1]),
                                        (0, 0)))
            pieces.append(piece[None])
    shape = (len(stacks), len(mesh.devices[0]), R, stacks[0].shape[2])
    return jax.make_array_from_single_device_arrays(
        shape, NamedSharding(mesh, P(POD_AXIS, CLIQUE_AXIS)), pieces)


def plan_cache_contents(g: CSRGraph, k_g: int, cslp_res, cost_plan: dict,
                        mem_per_device: float, topology_mode: str = "sharded"):
    """Fill per-device queues until the planned per-device budgets (§4.2 S3).
    Returns (feat_ids_per_dev, topo_ids_per_dev) — the *target* residency
    sets, shared by initial cache construction and online delta refreshes.

    ``topology_mode`` controls how the per-device topology byte budget
    ``bt`` is spent.  Under ``"sharded"`` each device fills its own CSLP
    queue ``G_T[gi]`` to ``bt`` (the per-device lists are disjoint, so the
    clique's *union* caches ~k_g x bt of topology — the capacity win the
    routed neighbor exchange pays for with intra-clique hops).  Under
    ``"replicated"`` every device must hold the same union, so the union
    itself is capped at ``bt``: the globally hottest vertices (``Q_T``
    order) up to ``bt`` bytes, split back into per-device lists by CSLP
    ownership purely for bookkeeping.  This is the equal-memory baseline
    the topology_scaling benchmark compares against."""
    alpha = cost_plan["m_T"] / max(cost_plan["m_T"] + cost_plan["m_F"], 1)
    if topology_mode not in CliqueCache.TOPOLOGY_MODES:
        raise ValueError(f"unknown topology_mode {topology_mode!r}; "
                         f"expected one of {CliqueCache.TOPOLOGY_MODES}")
    bt = mem_per_device * alpha
    bf = mem_per_device * (1 - alpha)
    keep = None
    if topology_mode == "replicated":
        q = np.asarray(cslp_res.Q_T)
        b = np.cumsum(g.topology_bytes(q)) if len(q) else np.zeros(0)
        keep = np.zeros(g.n, dtype=bool)
        keep[q[: int(np.searchsorted(b, bt, side="right"))]] = True
    feat_ids, topo_ids = [], []
    for gi in range(k_g):
        # topology: fill G_T[gi] until bt bytes (sharded), or take this
        # device's slice of the bt-byte union (replicated)
        q = np.asarray(cslp_res.G_T[gi])
        if keep is not None:
            topo_ids.append(q[keep[q]] if len(q) else q)
        else:
            b = np.cumsum(g.topology_bytes(q)) if len(q) else np.zeros(0)
            topo_ids.append(q[: int(np.searchsorted(b, bt, side="right"))])
        # features: fixed row size
        q = cslp_res.G_F[gi]
        nrows = int(bf // g.feature_bytes_per_vertex())
        feat_ids.append(q[:nrows])
    return feat_ids, topo_ids


def build_clique_cache(g: CSRGraph, devices, cslp_res, cost_plan: dict,
                       mem_per_device: float, materialize: bool = True,
                       topology_mode: str = "sharded") -> CliqueCache:
    feat_ids, topo_ids = plan_cache_contents(g, len(devices), cslp_res,
                                             cost_plan, mem_per_device,
                                             topology_mode=topology_mode)
    return CliqueCache(g, devices, feat_ids, topo_ids, materialize=materialize,
                       topology_mode=topology_mode)
