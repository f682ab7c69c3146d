"""End-to-end Legion GNN training.

Per step (paper Figure 7's pipeline, host side in the Prefetcher thread):
  batch generator (local shuffle of the device tablet)
  -> neighbor sampler (host CSR; topology-cache hits accounted as HBM reads,
     misses as PCIe transactions)
  -> feature extractor (unified-cache gather: device rows via the Pallas
     gather path, misses host->device)
  -> graph constructor (padded level tensors + masks)
while the device runs train_step on the previous batch (JAX async dispatch +
prefetch queue depth), gradients synchronized across devices (optionally
int8-error-feedback compressed).

The multi-device run is simulated faithfully on one process: each simulated
device consumes its own tablet stream and the synchronized step averages
gradients — mathematically identical to synchronous DP all-reduce.

The pipeline is **relaunchable**: everything derived from the (devices,
plan, backend) triple — builders, lookahead windows, the Prefetcher, the
sharded mesh step — is built by one ``launch(start_step)`` closure, so the
elastic recovery path (``resilience=``, see docs/resilience.md) can tear
the pipeline down on a simulated device loss, replan onto the survivors
with ``replan_on_topology_change``, and launch a fresh pipeline at the
current step; telemetry sources re-register by name with folded base
totals so the registry counters stay monotonic across the swap.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import LegionPlan, replan_on_topology_change
from repro.core.unified_cache import TrafficCounter
from repro.graph.csr import CSRGraph
from repro.models.gnn import (GNNConfig, defs as gnn_defs,
                              forward as gnn_forward, loss_fn as gnn_loss)
from repro.models.params import init_from_defs
from repro.obs import maybe_span
from repro.train.batch import (DeviceBatchBuilder, HostBatchBuilder,
                               make_batch_builder, pack_sharded_specs)
from repro.train.checkpoint import (AsyncCheckpointer,
                                    latest_resumable_checkpoint,
                                    restore_checkpoint)
from repro.train.optimizer import adamw, apply_updates
from repro.train.pipeline import LookaheadWindow, Prefetcher, StragglerMonitor
from repro.train.resilience import (ResilienceConfig, ResilienceStats,
                                    RngJournal, topology_from_partition)

# pipeline/refresh summary keys folded into the monotonic base totals when
# a remesh replaces the Prefetcher / OnlineCacheManager mid-run
_PIPE_FOLD_KEYS = ("batches_built", "gets", "host_build_s_total",
                   "host_pack_s_total", "queue_dry_s_total",
                   "worker_deaths", "worker_restarts")
_REFRESH_FOLD_KEYS = ("checks", "refreshes", "admitted", "evicted",
                      "topo_rebuilds", "refresh_bytes_h2d")
# a fresh pipeline's first build compiles the device sampler (tens of
# seconds per hop shape on a TPU at the paper's batch), so the first fetch
# waits longer than a steady-state one; a dead worker still surfaces at once
_FIRST_GET_TIMEOUT_S = 900.0


def _fold(base: dict, summary: dict, keys: Sequence[str]) -> None:
    for k in keys:
        v = summary.get(k)
        if isinstance(v, (int, float)):
            base[k] = base.get(k, 0) + v


def make_gnn_batch(g: CSRGraph, cache, cfg: GNNConfig, seeds: np.ndarray,
                   rng: np.random.Generator, counter: Optional[TrafficCounter],
                   dev: int) -> dict:
    """Sample + extract one padded mini-batch, with traffic accounting.

    Back-compat shim over ``HostBatchBuilder`` (returns numpy, not jnp)."""
    builder = HostBatchBuilder(g, cache, cfg.fanouts, counter, dev)
    return builder.assemble(builder.build_spec(seeds, rng))


def _make_sharded_step(cfg: GNNConfig, opt, mesh, axes, n_total: int,
                       feat_dim: int, impl: str):
    """Build the jitted hierarchical (clique-parallel × data-parallel)
    train step over the 2-D ``(pod, clique)`` mesh.

    One ``shard_map`` over both axes does the whole device phase.  All
    cache traffic is intra-clique: the routed gather (local hits from the
    device's own partition, peer hits via the peer exchange) reduces over
    the ``clique`` axis only, so no feature row ever crosses a clique
    boundary — each pod row serves batches from its own clique's unified
    cache, exactly the paper's hierarchical design.  Gradients combine
    with one ``psum`` over *both* axes (intra-clique NVLink/ICI + the
    inter-clique data-parallel reduction): per-shard losses are summed
    (not averaged) and normalized by the mesh-wide batch size after the
    psum, so the math matches the single-device backends' mean over the
    concatenated batch exactly.  A single clique is the degenerate
    ``K_c=1`` mesh — same code path.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.gather import routed_gather

    D = feat_dim
    pod_axis, clique_axis = axes
    P2 = P(pod_axis, clique_axis)

    def body(params, shards, packed):
        shard = shards[0, 0]                   # (R, Dp): my cache partition
        if shard.shape[0] == 0:                # empty cache: all host fill
            feats = packed["miss_rows"][0, 0]
        else:
            feats = routed_gather(shard, packed["owner"][0, 0],
                                  packed["local"][0, 0], clique_axis,
                                  impl=impl)
            feats = feats[:, :D] + packed["miss_rows"][0, 0]
        batch = {"labels": packed["labels"][0, 0]}
        li = 0
        while f"pos_{li}" in packed:
            valid = packed[f"valid_{li}"][0, 0]
            f = feats[packed[f"pos_{li}"][0, 0]].reshape(valid.shape + (D,))
            batch[f"feats_{li}"] = f * valid[..., None].astype(f.dtype)
            if li > 0:
                batch[f"mask_{li}"] = valid
            li += 1

        def local_sum_loss(p):
            logits = gnn_forward(cfg, p, batch).astype(jnp.float32)
            labels = batch["labels"]
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
            acc = (logits.argmax(-1) == labels).astype(jnp.float32).sum()
            return (lse - ll).sum(), acc

        (loss_sum, acc_sum), grads = jax.value_and_grad(
            local_sum_loss, has_aux=True)(params)
        loss = jax.lax.psum(loss_sum, axes) / n_total
        acc = jax.lax.psum(acc_sum, axes) / n_total
        grads = jax.tree.map(lambda x: x / n_total,
                             jax.lax.psum(grads, axes))
        return grads, loss, acc

    # replication checking off: the out-specs mix psum-reduced (replicated)
    # values with per-shard inputs, which the static checker rejects
    smapped = jax.shard_map(body, mesh=mesh, in_specs=(P(), P2, P2),
                            out_specs=(P(), P(), P()), check_vma=False)

    @jax.jit
    def step(params, opt_state, shards, packed):
        grads, loss, acc = smapped(params, shards, packed)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, acc

    return step


@dataclasses.dataclass
class GNNTrainResult:
    losses: List[float]
    accs: List[float]
    epoch_times: List[float]
    counter: TrafficCounter
    straggler: dict
    steps: int
    backend: str = "host"
    pipeline: dict = dataclasses.field(default_factory=dict)
    refresh: dict = dataclasses.field(default_factory=dict)
    # sampling-path traffic digest (from the shared TrafficCounter): how
    # much neighbor sampling ran on device vs fell back to the host CSR
    sampling: dict = dataclasses.field(default_factory=dict)
    # telemetry digest (repro.obs): sink paths + span/snapshot counts when
    # train_gnn ran with telemetry, {} otherwise
    telemetry: dict = dataclasses.field(default_factory=dict)
    # tiered feature store digest (FeatureStore.summary()): per-tier
    # hit/fill/eviction tallies when train_gnn ran with one, {} otherwise
    store: dict = dataclasses.field(default_factory=dict)
    # resilience digest (ResilienceStats.summary() + fault-plan tallies):
    # remesh/restore/injection activity when train_gnn ran with a
    # resilience config or recovered runtime state, {} otherwise
    resilience: dict = dataclasses.field(default_factory=dict)


def train_gnn(g: CSRGraph, plan: Optional[LegionPlan], cfg: GNNConfig, *,
              steps: int = 100, devices: Optional[Sequence[int]] = None,
              seed: int = 0, counter: Optional[TrafficCounter] = None,
              checkpoint_dir: Optional[str] = None, checkpoint_every: int = 50,
              resume: bool = False, prefetch_depth: int = 2,
              prefetch_workers: Optional[int] = None,
              shuffle: str = "local", mesh=None,
              compress_grads: bool = False, backend: str = "host",
              gather: str = "auto", fused: bool = True,
              bucket: int = 256, sampler: str = "chain",
              refresh_interval: Optional[int] = None,
              refresh_config=None, telemetry=None,
              feature_store=None,
              lookahead: Optional[int] = None,
              resilience: Optional[ResilienceConfig] = None) -> GNNTrainResult:
    """Train SAGE/GCN with the Legion pipeline.  ``shuffle='global'`` ignores
    tablets and draws seeds from the full training set (the Fig. 11 baseline).

    ``backend`` selects the batch pipeline (see repro.train.batch):
    ``"host"`` is the classic CPU path; ``"device"`` samples and gathers
    against the HBM-resident unified cache (``gather`` picks the cached-row
    gather impl: auto|pallas|xla) with the host filling only misses, and
    overlaps the device-side gather with the previous train step.  The
    device phase is retrace-free: specs pad to ``bucket``-rounded shapes
    and finalize is one fused jitted dispatch (``fused=False`` restores
    the legacy gather→overlay→take chain; ``sampler="stepwise"`` the
    per-hop-sync sampler — both kept for parity tests and the
    ``pipeline_stall`` before/after benchmark).  ``prefetch_workers``
    sizes the Prefetcher's build pool (default: one thread per device,
    capped at cpu_count-1 — serial on small hosts); per-device spec
    builds of one step run concurrently, the refresh hook stays
    serialized with all of them.
    ``"sharded"`` is the hierarchical clique-parallel executor over the
    2-D ``(pod, clique)`` mesh: ``devices`` must cover whole NVLink/ICI
    cliques (any number of complete, equal-sized cliques; the default —
    every plan device — runs the full hierarchy, one clique is the
    degenerate ``K_c=1`` mesh).  Each mesh position holds its own clique's
    cache partition (``CliqueCache.sharded_device_arrays``, stacked per
    clique by ``stack_hierarchical_shards``), batch gathers are routed by
    the ownership map under ``shard_map`` (local-hit gather on the owning
    device, peer exchange strictly *intra*-clique — feature rows never
    cross cliques), and gradients combine with one ``psum`` over both
    axes (cliques train data-parallel, the paper's §4.1 hierarchy).
    It needs ``len(jax.devices()) >= len(devices)`` — simulate on CPU
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

    ``refresh_interval`` (steps) enables the online cache manager: live
    per-vertex traffic is accumulated, drift against the planned hotness is
    checked every interval on the prefetch worker, and a drifted clique's
    unified cache is delta-refreshed in place (see repro.core.cache_manager).
    ``refresh_config`` (a RefreshConfig) overrides the remaining knobs.
    ``refresh_interval=None`` (default) disables the manager entirely —
    batches and traffic counts are bit-identical to a run without it.

    ``telemetry`` (a ``repro.obs.Telemetry`` or ``TelemetryConfig``)
    instruments the run: spans around spec builds (prefetch workers),
    pack, H2D staging, fused finalize, each device step and the refresh
    hook; windowed metric snapshots every ``config.window`` steps pulled
    from the TrafficCounter/Prefetcher/OnlineCacheManager/CliqueCaches;
    a JSONL stream plus a Perfetto-loadable Chrome trace.  The telemetry
    object is closed (final snapshot, sinks flushed) when this returns.
    ``telemetry=None`` (default) is the hard zero-overhead path: no
    telemetry code runs and results are bit-identical to pre-telemetry
    builds.

    ``feature_store`` (a ``repro.core.feature_store.FeatureStore``, or a
    ``TieredStoreConfig`` to build one over ``g``) routes every HBM-miss
    feature fill through the tiered store's host-RAM/SSD tiers instead of
    a direct host-array read — the layout that trains graphs whose feature
    table exceeds host RAM (``g.feature_file`` set, ``g.features`` absent).
    ``lookahead`` sets how many batches each device samples ahead of its
    feature fill (default: the store config's ``lookahead``): the future
    batches' store-request sets feed the store's next-use eviction index
    and their SSD reads prefetch on the store's I/O pool.  Sampling stays
    in strict step order (the whole per-step RNG draw moves earlier in
    wall time, never reorders), so batches — and losses — are bitwise
    identical to the storeless run.  ``lookahead=0`` disables sampling
    ahead but keeps store routing.

    With ``mesh`` (a jax Mesh with a "data" axis) the step runs as explicit
    shard_map data parallelism; ``compress_grads=True`` additionally swaps
    the gradient all-reduce for the int8 error-feedback compressed version
    (4x less DP wire — the DCN-saving configuration for the pod axis).

    ``resilience`` (a ``repro.train.resilience.ResilienceConfig``) turns
    on the recovery hooks: bounded prefetch-worker respawns, retried
    checkpoint writes, and — on a (simulated) device loss — an in-place
    remesh onto the survivors (``replan_on_topology_change`` + a fresh
    pipeline launch; the sharded backend downgrades to per-device
    execution with host-side gradient exchange, which is mathematically
    the same synchronous DP).  Its optional ``fault_plan`` injects
    deterministic faults for tests and the chaos bench.  Checkpoints
    written with ``checkpoint_dir`` additionally carry *runtime* state —
    sampler RNG boundary states, online-manager hotness, store residency
    — and ``resume=True`` restores all of it, so a preempted job
    continues the exact batch sequence with its learned hot set instead
    of re-warming (see docs/resilience.md).
    """
    if devices is None:
        devices = sorted(plan.partition.tablets) if plan is not None else [0]
    # the device/sharded backends need a unified cache; planless runs
    # degrade to the host pipeline (nothing device-resident to gather
    # from) and the result reports the backend that actually ran
    backend = backend if plan is not None else "host"
    exec_clique_ids, exec_cliques = None, None
    if backend == "sharded":
        if mesh is not None or compress_grads:
            raise ValueError(
                "backend='sharded' builds its own hierarchical (pod, "
                "clique) mesh and combines gradients with one psum over "
                "both axes; it does not compose with mesh=/compress_grads= "
                "(use backend='device' for the DP-mesh path)")
        # devices must cover whole NVLink/ICI cliques (each clique's cache
        # is partitioned across all of its devices); any number of complete
        # cliques trains hierarchically, one clique is the K_c=1 case
        exec_clique_ids, exec_cliques = \
            plan.partition.execution_cliques(devices)
        sizes = sorted({len(c) for c in exec_cliques})
        if len(sizes) != 1:
            raise ValueError(
                f"backend='sharded' needs uniform clique sizes for the "
                f"(pod, clique) mesh; cliques {exec_clique_ids} have sizes "
                f"{[len(c) for c in exec_cliques]} — run ragged cliques as "
                "separate jobs or replan with replan_on_topology_change")
        # clique-major order == shard stacking order == mesh position
        devices = [d for c in exec_cliques for d in c]
    n_dev = len(devices)
    counter = counter if counter is not None else TrafficCounter.for_devices(devices)

    resil = resilience
    fplan = resil.fault_plan if resil is not None else None
    rstats = ResilienceStats()
    if fplan is not None and any(
            s.site == "device_loss" for s in fplan._specs):
        if plan is None or mesh is not None:
            raise ValueError(
                "device_loss recovery needs a LegionPlan to replan from "
                "and does not compose with an explicit mesh= (the remesh "
                "rebuilds the executor itself)")

    tele = telemetry
    if tele is not None and not hasattr(tele, "span"):
        # a TelemetryConfig (or anything config-shaped): build the
        # Telemetry here so callers can pass plain knobs
        from repro.obs import Telemetry

        tele = Telemetry(tele)

    key = jax.random.PRNGKey(seed)
    params = init_from_defs(gnn_defs(cfg), key)
    opt = adamw(cfg.lr)
    opt_state = opt.init(params)
    step0 = 0

    ckpt = None
    runtime0 = None
    if checkpoint_dir:
        ckpt = AsyncCheckpointer(
            checkpoint_dir,
            retries=(resil.checkpoint_retries if resil is not None else 1),
            fault_plan=fplan)
        if resume:
            # newest checkpoint that actually validates against the model
            # tree — torn/partial files from a crash are skipped, not
            # picked (see latest_resumable_checkpoint)
            path = latest_resumable_checkpoint(checkpoint_dir,
                                               like=(params, opt_state))
            if path:
                step0, (params, opt_state), runtime0 = restore_checkpoint(
                    path, (params, opt_state), with_runtime=True)
                rstats.resumed_from_step = step0

    ef_state = None
    if mesh is not None and compress_grads:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.train.compression import (init_error_feedback,
                                             make_compressed_grad_fn)

        ef_state = init_error_feedback(params)
        grad_fn = make_compressed_grad_fn(
            lambda p, b: gnn_loss(cfg, p, b)[0], mesh, dp_axis="data")
        batch_sharding = NamedSharding(mesh, P("data"))

        @jax.jit
        def train_step(params, opt_state, ef, batch):
            batch = jax.lax.with_sharding_constraint(
                batch, jax.tree.map(lambda _: batch_sharding, batch))
            loss, grads, ef = grad_fn(params, batch, ef)
            updates, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, updates)
            return params, opt_state, ef, loss

    @jax.jit
    def train_step_plain(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: gnn_loss(cfg, p, batch), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, loss, metrics["acc"]

    rngs = {d: np.random.default_rng(seed + 17 * d) for d in devices}
    # RNG journal: boundary states at each step, so checkpoints capture
    # "state with steps < k drawn" even while the lookahead window has the
    # live generator several steps ahead (see resilience.RngJournal)
    journal = {d: RngJournal() for d in devices} if ckpt is not None else None
    if runtime0 is not None:
        for d, st_rng in runtime0.get("rng", {}).items():
            if d in rngs:
                rngs[d].bit_generator.state = st_rng
        rstats.runtime_restored = "rng" in runtime0
    all_train = (plan.partition.train_vertices if plan is not None
                 else np.arange(g.n))

    rc = None
    manager = None
    if plan is not None and (refresh_interval is not None
                             or refresh_config is not None):
        from repro.core.cache_manager import OnlineCacheManager, RefreshConfig

        rc = refresh_config or RefreshConfig()
        if refresh_interval is not None:
            rc = dataclasses.replace(rc, interval=refresh_interval)
        if rc.interval is not None and rc.interval <= prefetch_depth:
            raise ValueError(
                f"refresh_interval ({rc.interval}) must exceed "
                f"prefetch_depth ({prefetch_depth}): the cache double "
                "buffer retains one epoch, so queued specs older than one "
                "refresh would gather from a released buffer")
        manager = OnlineCacheManager(g, plan, rc, counter=counter)
        if runtime0 is not None and runtime0.get("manager") is not None:
            # recover the learned hot set: restore the blended hotness and
            # delta-replan each clique's residency from it in one pass
            rstats.cache_rebuilds += manager.load_state_dict(
                runtime0["manager"], reapply=True)

    store = feature_store
    if store is not None and not hasattr(store, "gather"):
        # a TieredStoreConfig (or anything config-shaped): build the
        # FeatureStore over the graph here so callers can pass plain knobs
        from repro.core.feature_store import FeatureStore

        store = FeatureStore(g, store, counter=counter)
    if store is not None and fplan is not None:
        # thread the chaos harness under the store: ssd_read/ssd_stall
        # faults fire inside _timed_read's retry loop
        store.source = fplan.wrap_source(store.source)
    if store is not None and runtime0 is not None \
            and runtime0.get("store") is not None:
        store.load_state_dict(runtime0["store"])
    if lookahead is not None and store is None:
        raise ValueError("lookahead= needs a feature_store to feed "
                         "(announce/prefetch hints go to the store)")
    window = (lookahead if lookahead is not None
              else (store.config.lookahead if store is not None else 0))

    def sampling_summary():
        """Sampling-path digest off the shared counter: the sharded
        topology cache's whole point is driving ``host_sample_syncs`` and
        ``host_sampled_edges`` to zero on warm epochs."""
        return {"host_sample_syncs": counter.host_sample_syncs,
                "host_sampled_edges": counter.host_sampled_edges,
                "topo_hit_rate": counter.topo_hit_rate}

    # ---- the relaunchable pipeline ------------------------------------
    # everything derived from (devices, plan, backend) lives in this
    # mutable cell so the device-loss recovery path can rebuild it;
    # *_base carry closed components' totals (monotonic across a swap)
    st = {"devices": list(devices), "plan": plan, "backend": backend,
          "manager": manager, "exec_cliques": exec_cliques,
          "per_dev": max(cfg.batch_size // max(n_dev, 1), 16),
          "prefetcher": None, "finalize": None, "sharded_step": None}
    pipeline_base: dict = {}
    # one tally per lookahead part closure: builds whose chain the
    # previous build had dispatched (kept across a remesh, so the total
    # stays monotonic)
    sampled_ahead: List[List[int]] = []
    refresh_base: dict = {}
    refresh_events: List[dict] = []
    streams = {}

    def launch(start_step: int) -> None:
        """(Re)build the batch pipeline to produce steps
        ``start_step..steps-1`` from the current (devices, plan, backend)
        state: tablet streams, builders (+observers), the sharded mesh
        step when applicable, per-device spec closures (lookahead windows
        when a store is attached) and the Prefetcher itself."""
        devs, plan_l = st["devices"], st["plan"]
        backend_l, manager_l = st["backend"], st["manager"]
        per_dev = st["per_dev"]
        for d in devs:
            streams[d] = (plan_l.partition.tablets[d]
                          if (plan_l is not None and shuffle == "local")
                          else all_train)

        builders = {}
        for d in devs:
            cache = plan_l.cache_for_device(d) if plan_l is not None else None
            kw = ({"gather": gather, "fused": fused, "bucket": bucket,
                   "sampler": sampler}
                  if backend_l in ("device", "sharded") else {})
            if manager_l is not None:
                kw["observer"] = manager_l.observer_for(d)
            builders[d] = make_batch_builder(backend_l, g, cache, cfg.fanouts,
                                             counter, d, **kw)
            builders[d].telemetry = tele
            builders[d].store = store

        sharded_step = None
        if backend_l == "sharded":
            from repro.core.unified_cache import stack_hierarchical_shards
            from repro.launch.mesh import (CLIQUE_AXIS, POD_AXIS,
                                           make_hierarchical_mesh)

            exec_cl = st["exec_cliques"]
            clique_caches = [plan_l.caches[ci] for ci in exec_clique_ids]
            hier_mesh = make_hierarchical_mesh(exec_cl)
            # each clique's shard stack lives on its own mesh row, placed
            # once per cache epoch; steps then move no cache rows
            for ci, cache in enumerate(clique_caches):
                cache.place_shards(list(hier_mesh.devices[ci]))
            sharded_step = _make_sharded_step(
                cfg, opt, hier_mesh, (POD_AXIS, CLIQUE_AXIS),
                n_total=per_dev * len(devs), feat_dim=g.feat_dim,
                impl=builders[devs[0]].gather)
            shard_stack_memo = {}

            def hierarchical_shards(epochs):
                """The (K_c, K_g, R, Dp) mesh tensor for one per-clique
                epoch vector, memoized: cliques refresh independently, so
                the stack rebuilds only when some clique's epoch moves.
                Two entries are retained — the same double-buffer horizon
                as the caches — so queued steps straddling a refresh keep
                their stack alive.  A rebuild assembles the per-device
                shards where they already lie (each clique's stack is
                HBM-resident on its mesh row and epoch-memoized per cache;
                only the refreshed clique's shards crossed PCIe), paid once
                per refresh *event*, never per step; an in-place row update
                cannot do better here because R_max may change when a
                refresh re-homes slot owners."""
                if epochs not in shard_stack_memo:
                    while len(shard_stack_memo) >= 2:
                        shard_stack_memo.pop(next(iter(shard_stack_memo)))
                    shard_stack_memo[epochs] = stack_hierarchical_shards(
                        clique_caches, epochs, hier_mesh)
                return shard_stack_memo[epochs]
        st["sharded_step"] = sharded_step

        def make_spec_fn(d: int):
            """Host phase of one device's part of a *synchronized* step.
            One closure per device so the Prefetcher pool can build them
            concurrently: each owns its device's RNG stream, builder and
            observer (single-owner — the step barrier keeps one device's
            builds serial across steps), and shared TrafficCounter tallies
            commute under the counter's lock, so totals stay bit-identical
            to the serial build order."""
            rng, tablet, builder = rngs[d], streams[d], builders[d]
            jr = journal[d] if journal is not None else None

            if store is not None:
                # sample-ahead mode: the window pre-samples up to
                # ``window`` future steps (strict step order — same RNG
                # sequence as the plain path), announces their
                # store-request sets and issues their SSD prefetches,
                # then fills the front spec
                def sample_one(step: int, rng=rng, tablet=tablet,
                               builder=builder, jr=jr):
                    seeds = tablet[rng.integers(0, len(tablet),
                                                size=per_dev)]
                    spec = builder.sample_spec(seeds, rng)
                    if jr is not None:
                        # boundary state: steps <= this one fully drawn
                        jr.record(step + 1, rng)
                    return spec

                win = LookaheadWindow(builder, store, sample_one,
                                      window=window, limit=steps, dev=d,
                                      start=start_step)
                build = win.build
            elif (manager_l is None and isinstance(builder, DeviceBatchBuilder)
                  and builder.sampler == "chain"):
                # one-batch sampling lookahead: batch k+1's seeds and hop
                # draws are drawn, and its device chain enqueued, inside
                # build k right after k's resolve, so chain(k+1) queues
                # ahead of finalize(k) and runs while the host dedups and
                # fills batch k.  The RNG is drawn in the same order as
                # build_spec's, resolve draws nothing, and accounting stays
                # at resolve and fill: batches and counts are unchanged,
                # and a chain never resolved counts nothing.  Off under a
                # refresh hook, which may swap the cached topology that a
                # chain dispatched before it would resolve against.
                pending = {}  # step -> chain dispatched by the last build
                ahead = [0]
                sampled_ahead.append(ahead)

                def dispatch(step: int, rng=rng, tablet=tablet,
                             builder=builder):
                    seeds = tablet[rng.integers(0, len(tablet),
                                                size=per_dev)]
                    return builder.sample_dispatch(seeds, rng, for_step=step)

                def build(step: int, rng=rng, builder=builder, jr=jr,
                          pending=pending, ahead=ahead):
                    chain = pending.pop(step, None)
                    pending.clear()
                    if chain is None:
                        chain = dispatch(step)
                    else:
                        ahead[0] += 1

                    def dispatch_next():
                        if jr is not None:
                            # boundary state: steps <= this one drawn
                            jr.record(step + 1, rng)
                        if step + 1 < steps:
                            pending[step + 1] = dispatch(step + 1)

                    spec = builder.resolve_spec(chain, dispatch_next)
                    return builder.fill_spec(spec, step=step)
            else:
                def build(step: int, rng=rng, tablet=tablet,
                          builder=builder, jr=jr):
                    seeds = tablet[rng.integers(0, len(tablet),
                                                size=per_dev)]
                    spec = builder.build_spec(seeds, rng, step=step)
                    if jr is not None:
                        jr.record(step + 1, rng)
                    return spec

            if tele is None:
                return build

            def spec_fn(step: int):
                # runs on a prefetch worker thread: the span is what makes
                # the build pool's concurrency visible in the trace
                with tele.span("spec_build", step=step, dev=d):
                    return build(step)
            return spec_fn

        def finalize_batch(item):
            """Device phase: finalize every part and concatenate (==DP).
            Runs on the consumer thread; with the device backend the cache
            gather is dispatched asynchronously and overlaps the in-flight
            train step.  The sharded backend dequeues an already-packed
            hierarchical batch (the Prefetcher's pack_fn ran on the
            worker); here it only resolves the epoch-pinned shard stack
            the packed slots index into."""
            if backend_l == "sharded":
                packed = dict(item)
                epochs = tuple(int(e) for e in packed.pop("cache_epochs"))
                return hierarchical_shards(epochs), packed
            parts = [builders[d].finalize(s) for d, s in zip(devs, item)]
            if len(parts) == 1:
                return parts[0]
            return {k: jnp.concatenate([p[k] for p in parts])
                    for k in parts[0]}

        def pack_fn(spec_groups):
            """Sharded second host phase: per-clique spec groups -> the
            2-D mesh-layout pack, then hand each spec's staging buffer
            back to its builder's pool."""
            packed = pack_sharded_specs(spec_groups, g.feat_dim,
                                        bucket=bucket)
            for d, s in zip(devs, (s for gr in spec_groups for s in gr)):
                builders[d].release_spec(s)
            return packed

        if journal is not None:
            for d in devs:
                # the state that samples ``start_step`` onward: a
                # checkpoint taken before any build can still resume here
                journal.setdefault(d, RngJournal()).record(start_step,
                                                           rngs[d])
        st["finalize"] = finalize_batch
        st["prefetcher"] = Prefetcher(
            part_fns=[make_spec_fn(d) for d in devs],
            part_group_sizes=([len(c) for c in st["exec_cliques"]]
                              if backend_l == "sharded" else None),
            workers=prefetch_workers, depth=prefetch_depth,
            limit=max(steps - start_step, 0),
            pre_batch_hook=(manager_l.on_step
                            if manager_l is not None else None),
            pack_fn=(pack_fn if backend_l == "sharded" else None),
            extra_summary=sampling_summary, telemetry=tele,
            start_step=start_step,
            max_restarts=(resil.worker_restarts if resil is not None else 0),
            fault_plan=fplan)

    def remesh(dead: List[int], at_step: int) -> None:
        """Device-loss recovery: tear the pipeline down, replan onto the
        survivors (dead devices' tablets and hotness merge into their
        clique peers — ``replan_on_topology_change``), and launch a fresh
        pipeline at the current step.  The sharded mesh cannot shrink in
        place, so that backend downgrades to per-device execution with
        host-side gradient exchange (concatenated batch == synchronous
        DP, mathematically unchanged).  Survivor RNG streams re-seed
        deterministically from (seed, step, device), so a chaos run with
        a fixed fault plan is reproducible end to end."""
        t0 = time.perf_counter()
        old = st["prefetcher"]
        old.close()  # a pending organic worker failure still surfaces
        _fold(pipeline_base, old.summary(), _PIPE_FOLD_KEYS)
        survivors = [d for d in st["devices"] if d not in set(dead)]
        if not survivors:
            raise RuntimeError(
                f"device(s) {sorted(dead)} lost at step {at_step} and no "
                "survivors remain — nothing to remesh onto")
        topo = topology_from_partition(st["plan"].partition)
        new_plan = replan_on_topology_change(g, st["plan"], topo,
                                             alive=survivors)
        st["plan"] = new_plan
        st["devices"] = [d for c in new_plan.partition.cliques for d in c]
        st["per_dev"] = max(cfg.batch_size // max(len(survivors), 1), 16)
        if st["backend"] == "sharded":
            st["backend"] = "device"
        for d in st["devices"]:
            rngs[d] = np.random.default_rng([seed, at_step, d])
        if st["manager"] is not None:
            _fold(refresh_base, st["manager"].summary(), _REFRESH_FOLD_KEYS)
            refresh_events.extend(st["manager"].stats.events)
            from repro.core.cache_manager import OnlineCacheManager

            # a fresh manager over the survivor plan: replan already
            # merged the dead devices' hotness into the new plan stats
            st["manager"] = OnlineCacheManager(g, new_plan, rc,
                                               counter=counter)
        launch(at_step)
        dt = time.perf_counter() - t0
        rstats.remesh_events += 1
        rstats.devices_lost += len(dead)
        rstats.remesh_s += dt
        rstats.events.append({"step": at_step, "lost": sorted(map(int, dead)),
                              "survivors": len(survivors),
                              "backend": st["backend"], "remesh_s": dt})
        if tele is not None:
            tele.event("remesh", step=at_step,
                       lost=sorted(map(int, dead)),
                       survivors=len(survivors))

    launch(step0)

    if tele is not None:
        # metric sources pulled at every windowed snapshot: components
        # mirror their own tallies, nothing extra runs on hot paths.
        # Sources that a remesh replaces are registered as closures over
        # the pipeline cell (add_source replaces by name) with folded
        # base totals, so counters stay monotonic across the swap.
        tele.add_source("traffic", counter.publish_metrics)

        def publish_prefetch(reg):
            st["prefetcher"].publish_metrics(reg, base=pipeline_base)
            reg.counter("prefetch.sample_ahead").set_total(
                sum(n for n, in sampled_ahead))
        tele.add_source("prefetch", publish_prefetch)
        if store is not None:
            tele.add_source("store", store.publish_metrics)
        if st["manager"] is not None:

            def publish_refresh(reg):
                if st["manager"] is not None:
                    st["manager"].publish_metrics(reg, base=refresh_base)
            tele.add_source("refresh", publish_refresh)
        if plan is not None:

            def publish_caches(reg):
                for ci, cache in enumerate(st["plan"].caches):
                    cache.publish_metrics(reg, clique=ci)
            tele.add_source("caches", publish_caches)
        if ckpt is not None:
            tele.add_source("checkpoint", ckpt.publish_metrics)
        if resil is not None or rstats.resumed_from_step is not None:
            tele.add_source("resilience", rstats.publish_metrics)
        if fplan is not None:
            tele.add_source("faults", fplan.publish_metrics)
        h_step = tele.registry.histogram("step.time_s")
        h_flag = tele.registry.histogram("straggler.step_time_s")
    monitor = StragglerMonitor()
    if tele is not None:
        tele.add_source("straggler", monitor.publish_metrics)
    losses, accs, epoch_times = [], [], []
    steps_per_epoch = max(len(all_train) // max(cfg.batch_size, 1), 1)
    t_epoch = time.perf_counter()
    reached = step0
    try:
        # priming fetch is pipeline warm-up (first host build, cold
        # workers), so it gets its own span; train_loop is the
        # steady-state stepping loop that device_step spans tile.
        with maybe_span(tele, "pipeline_prime"):
            next_batch = (st["finalize"](st["prefetcher"].get(
                _FIRST_GET_TIMEOUT_S)) if steps > step0 else None)
        with maybe_span(tele, "train_loop"):
            for step in range(step0, steps):
                if fplan is not None:
                    dead = fplan.device_losses(step)
                    if dead:
                        if resil is None or resil.on_device_loss == "raise":
                            raise RuntimeError(
                                f"device(s) {sorted(dead)} lost at step "
                                f"{step} (on_device_loss='raise')")
                        # the in-flight batch was built by the lost
                        # topology: discard it, remesh, rebuild step
                        remesh(dead, step)
                        next_batch = st["finalize"](st["prefetcher"].get(
                            _FIRST_GET_TIMEOUT_S))
                t0 = time.perf_counter()
                # the device-step span covers dispatch, the overlapped
                # prefetch of step i+1, and the block on step i's loss —
                # i.e. the whole per-step wall slice the trace attributes
                with maybe_span(tele, "device_step", step=step):
                    batch = next_batch
                    if ef_state is not None:
                        params, opt_state, ef_state, loss = train_step(
                            params, opt_state, ef_state, batch)
                        acc = jnp.zeros(())
                    elif st["backend"] == "sharded":
                        shards, packed = batch
                        params, opt_state, loss, acc = st["sharded_step"](
                            params, opt_state, shards, packed)
                    else:
                        params, opt_state, loss, acc = train_step_plain(
                            params, opt_state, batch)
                    # build batch i+1 while the device chews on step i:
                    # the host phase comes off the prefetch queue, and
                    # finalize's device gather rides the same async
                    # dispatch stream as the step.
                    next_batch = (st["finalize"](st["prefetcher"].get())
                                  if step + 1 < steps else None)
                    loss.block_until_ready()
                dt = time.perf_counter() - t0
                flagged = monitor.record(dt)
                losses.append(float(loss))
                accs.append(float(acc))
                reached = step + 1
                if tele is not None:
                    h_step.observe(dt)
                    if flagged:
                        h_flag.observe(dt)
                    if (step + 1) % tele.config.window == 0:
                        tele.snapshot(step + 1)
                if ckpt and (step + 1) % checkpoint_every == 0:
                    ckpt.save(step + 1, (params, opt_state),
                              runtime=_runtime_state(st, journal, store,
                                                     step + 1))
                if (step + 1) % steps_per_epoch == 0:
                    epoch_times.append(time.perf_counter() - t_epoch)
                    t_epoch = time.perf_counter()
    finally:
        # close() may re-raise a worker exception (see Prefetcher.close);
        # the final telemetry snapshot (exact totals need every worker
        # build accounted) and the final checkpoint must happen either way
        try:
            st["prefetcher"].close()
        finally:
            try:
                if store is not None:
                    # drain the store's I/O pool (before the final
                    # telemetry snapshot so its read/stall totals are
                    # complete); the store itself stays usable
                    store.close()
                if tele is not None:
                    tele.close(final_step=steps)
            finally:
                if ckpt:
                    # the step actually completed — an aborted run must
                    # not publish a checkpoint labeled with a step it
                    # never reached
                    ckpt.save(reached, (params, opt_state),
                              runtime=_runtime_state(st, journal, store,
                                                     reached))
                    ckpt.close()

    pipe = st["prefetcher"].summary()
    for k, v in pipeline_base.items():
        if k in pipe:
            pipe[k] = pipe[k] + v
    pipe["sample_ahead"] = sum(n for n, in sampled_ahead)
    refresh = {}
    if st["manager"] is not None:
        refresh = st["manager"].summary()
        for k, v in refresh_base.items():
            refresh[k] = refresh.get(k, 0) + v
        refresh["events"] = refresh_events + refresh.get("events", [])
    resilience_digest = {}
    if resil is not None or rstats.resumed_from_step is not None \
            or rstats.remesh_events:
        resilience_digest = rstats.summary()
        if fplan is not None:
            resilience_digest["faults"] = fplan.summary()
        if ckpt is not None:
            resilience_digest["checkpoint"] = ckpt.summary()
    return GNNTrainResult(losses=losses, accs=accs, epoch_times=epoch_times,
                          counter=counter, straggler=monitor.summary(),
                          steps=steps - step0, backend=st["backend"],
                          pipeline=pipe,
                          refresh=refresh,
                          sampling=sampling_summary(),
                          telemetry=({} if tele is None else {
                              "jsonl_path": tele.config.jsonl_path,
                              "trace_path": tele.config.trace_path,
                              "spans": tele.span_count,
                              "open_spans": tele.open_spans,
                              "window": tele.config.window}),
                          store=(store.summary() if store is not None
                                 else {}),
                          resilience=resilience_digest)


def _runtime_state(st: dict, journal, store, next_step: int) -> dict:
    """The runtime payload for a checkpoint at boundary ``next_step``:
    per-device sampler RNG states *at that boundary* (from the journal —
    the live generators are already ahead by the lookahead window), the
    online manager's learned hotness, and the store's host-tier
    residency.  ``restore_checkpoint(..., with_runtime=True)`` +
    ``train_gnn(resume=True)`` put all of it back."""
    rt: dict = {"version": 1,
                "devices": [int(d) for d in st["devices"]]}
    if journal is not None:
        states = {}
        for d in st["devices"]:
            s = journal[d].state_for(next_step)
            if s is None:
                states = None
                break
            states[int(d)] = s
        if states is not None:
            rt["rng"] = states
    if st["manager"] is not None:
        rt["manager"] = st["manager"].state_dict()
    if store is not None:
        rt["store"] = store.state_dict()
    return rt
