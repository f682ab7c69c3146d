"""Host/device batch-pipeline parity: the two backends must be
interchangeable — identical subgraph shapes, identical hit/miss accounting,
identical batches, matching loss trajectories."""
import threading

import numpy as np
import pytest

from repro.core.cliques import topology_matrix
from repro.core.planner import build_plan
from repro.core.unified_cache import TrafficCounter
from repro.graph.csr import powerlaw_graph
from repro.graph.sampling import (cache_sample_batch, host_sample_batch,
                                  unique_vertices)
from repro.models.gnn import GNNConfig
from repro.train.batch import (DeviceBatchBuilder, HostBatchBuilder,
                               make_batch_builder)
from repro.train.loop import train_gnn

FANOUTS = (5, 3)


@pytest.fixture(scope="module")
def setup():
    g = powerlaw_graph(6000, 10, seed=4, feat_dim=32)
    plan = build_plan(g, topology_matrix("nv2"), mem_per_device=1_000_000,
                      batch_size=256, seed=0)
    return g, plan


def _builders(g, plan, dev=0, gather="xla"):
    cache = plan.cache_for_device(dev)
    ch = TrafficCounter.for_plan(plan)
    cd = TrafficCounter.for_plan(plan)
    return (HostBatchBuilder(g, cache, FANOUTS, ch, dev),
            DeviceBatchBuilder(g, cache, FANOUTS, cd, dev, gather=gather),
            ch, cd)


def test_sampler_parity(setup):
    """Cache-aware device sampling replays the host sampler bit for bit."""
    g, plan = setup
    cache = plan.cache_for_device(0)
    seeds = plan.partition.tablets[0][:128]
    lv_h = host_sample_batch(g, seeds, FANOUTS, np.random.default_rng(11))
    lv_d, hits = cache_sample_batch(g, cache, seeds, FANOUTS,
                                    np.random.default_rng(11))
    assert [l.shape for l in lv_h] == [l.shape for l in lv_d]
    for a, b in zip(lv_h, lv_d):
        np.testing.assert_array_equal(a, b)
    # the masks really split: some device-sampled levels, some host fallback
    assert all(h.dtype == bool for h in hits)


@pytest.mark.parametrize("gather", ["xla", "pallas"])
def test_batch_parity(setup, gather):
    """Same seeds => identical batch tensors and identical accounting,
    cached rows routed through the requested gather implementation."""
    g, plan = setup
    bh, bd, ch, cd = _builders(g, plan, gather=gather)
    seeds = plan.partition.tablets[0][:64]
    batch_h = bh.build(seeds, np.random.default_rng(3))
    batch_d = bd.build(seeds, np.random.default_rng(3))
    assert set(batch_h) == set(batch_d)
    for k in batch_h:
        np.testing.assert_allclose(np.asarray(batch_h[k], np.float32),
                                   np.asarray(batch_d[k], np.float32),
                                   rtol=0, atol=0, err_msg=k)
    for f in ("feature_requests", "feature_hits", "topo_requests",
              "topo_hits", "pcie_transactions"):
        assert getattr(ch, f) == getattr(cd, f), f
    np.testing.assert_array_equal(ch.bytes_matrix, cd.bytes_matrix)
    assert ch.feature_hits > 0 and ch.feature_hits < ch.feature_requests


def test_device_spec_is_hit_miss_split(setup):
    """The device spec ships only miss rows host-side — the cache-resident
    majority never crosses the host boundary — in the bucket-rounded
    layout: ids/cache_pos/hit/miss_inv pad to the bucket quantum with
    inert tails, and miss rows live in the staging buffer's head."""
    g, plan = setup
    _, bd, _, _ = _builders(g, plan)
    seeds = plan.partition.tablets[0][:64]
    spec = bd.build_spec(seeds, np.random.default_rng(5))
    n = spec.n_ids
    # bucket-rounded stable shapes, inert padding
    assert len(spec.ids) == len(spec.cache_pos) == len(spec.hit) \
        == len(spec.miss_inv)
    assert len(spec.ids) % bd.bucket == 0
    assert spec.miss_feats.shape[0] % bd.bucket == 0
    assert (spec.ids[n:] == -1).all() and not spec.hit[n:].any()
    assert (spec.miss_inv[n:] == -1).all()
    # only the true misses ship feature rows (staged at the head)
    assert spec.n_miss == int((~spec.hit[:n]).sum())
    assert spec.n_miss < n  # the cache actually absorbs traffic
    miss_ids = spec.ids[:n][~spec.hit[:n]]
    np.testing.assert_array_equal(spec.miss_feats[:spec.n_miss, :g.feat_dim],
                                  g.get_features(miss_ids))
    # split_hits is consistent with what extract_features would do
    pos, hit = plan.cache_for_device(0).split_hits(spec.ids[:n])
    np.testing.assert_array_equal(hit, spec.hit[:n])
    np.testing.assert_array_equal(pos, spec.cache_pos[:n])


def test_train_gnn_backend_parity(setup):
    """backend='device' trains to the same losses as backend='host'."""
    g, plan = setup
    cfg = GNNConfig(feat_dim=32, hidden=32, batch_size=64, fanouts=FANOUTS,
                    lr=3e-3)
    rh = train_gnn(g, plan, cfg, steps=8, seed=0, backend="host")
    rd = train_gnn(g, plan, cfg, steps=8, seed=0, backend="device")
    assert rd.backend == "device"
    np.testing.assert_allclose(rh.losses, rd.losses, atol=1e-5)
    assert rh.counter.feature_hits == rd.counter.feature_hits
    assert rh.counter.topo_hits == rd.counter.topo_hits
    assert rh.counter.pcie_transactions == rd.counter.pcie_transactions
    assert rd.pipeline["batches_built"] >= rd.steps


def test_fused_matches_legacy_finalize(setup):
    """fused one-dispatch finalize == the legacy gather→overlay→take chain
    (and the stepwise sampler == the chained one), bit for bit."""
    g, plan = setup
    cache = plan.cache_for_device(0)
    seeds = plan.partition.tablets[0][:64]
    bf = DeviceBatchBuilder(g, cache, FANOUTS, None, 0, gather="xla")
    bl = DeviceBatchBuilder(g, cache, FANOUTS, None, 0, gather="xla",
                            fused=False, sampler="stepwise")
    for trial in range(3):
        rng_f, rng_l = (np.random.default_rng(20 + trial) for _ in range(2))
        a = bf.build(seeds, rng_f)
        b = bl.build(seeds, rng_l)
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k], np.float32),
                                          np.asarray(b[k], np.float32),
                                          err_msg=k)


def test_device_finalize_retraces_once_per_bucket(setup):
    """The tentpole pin: across a 50-step device-backend run the fused
    finalize compiles at most once per (id-bucket, miss-bucket) shape pair
    — not once per batch — and the host backend's finalize path triggers
    no XLA compile at all."""
    import jax

    from repro.train import batch as batch_mod

    g, plan = setup
    cache = plan.cache_for_device(0)
    tablet = plan.partition.tablets[0]
    compiles = {"on": False, "n": 0}

    def _listener(event, _dur, **kw):
        if compiles["on"] and event.startswith("/jax/core/compile"):
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(_listener)

    builder = DeviceBatchBuilder(g, cache, FANOUTS, None, 0, gather="xla")
    fused = batch_mod._get_fused_finalize()
    fused.clear_cache()
    rng = np.random.default_rng(77)
    shapes = set()
    for _ in range(50):
        seeds = tablet[rng.integers(0, len(tablet), 64)]
        spec = builder.build_spec(seeds, rng)
        shapes.add((len(spec.ids), spec.miss_feats.shape[0]))
        jax.block_until_ready(builder.finalize(spec))
    # ≤ one compile per shape bucket (50 batches collapse to a handful of
    # bucket pairs), where the pre-fused path retraced almost every batch
    assert fused._cache_size() <= len(shapes)
    assert len(shapes) <= 6, f"bucketing failed to collapse shapes: {shapes}"

    # host backend: 50 build+finalize cycles, zero compiles
    host = HostBatchBuilder(g, cache, FANOUTS, None, 0)
    jax.block_until_ready(host.build(tablet[:64], np.random.default_rng(1)))
    compiles["on"] = True
    try:
        for _ in range(50):
            seeds = tablet[rng.integers(0, len(tablet), 64)]
            jax.block_until_ready(host.build(seeds, rng))
    finally:
        compiles["on"] = False
    assert compiles["n"] == 0, "host finalize path must stay compile-free"


def test_staging_pool_reuse_and_padding_is_inert(setup):
    """The miss staging buffer is reused across batches (no fresh host
    array per batch) and releasing+reacquiring never corrupts an
    already-finalized batch."""
    import jax

    g, plan = setup
    cache = plan.cache_for_device(0)
    builder = DeviceBatchBuilder(g, cache, FANOUTS, None, 0, gather="xla")
    seeds = plan.partition.tablets[0][:64]
    spec1 = builder.build_spec(seeds, np.random.default_rng(9))
    buf = spec1.miss_feats
    batch1 = builder.finalize(spec1)           # releases the buffer
    snap = {k: np.asarray(v).copy() for k, v in batch1.items()}
    spec2 = builder.build_spec(seeds, np.random.default_rng(10))
    assert spec2.miss_feats is buf, "staging buffer was not pooled"
    jax.block_until_ready(builder.finalize(spec2))
    for k, v in batch1.items():               # batch1 unharmed by the reuse
        np.testing.assert_array_equal(np.asarray(v), snap[k], err_msg=k)


def test_make_batch_builder_validation(setup):
    g, plan = setup
    with pytest.raises(ValueError):
        make_batch_builder("gpu", g, None, FANOUTS)
    with pytest.raises(ValueError):
        make_batch_builder("device", g, None, FANOUTS)
    b = make_batch_builder("host", g, None, FANOUTS)
    batch = b.build(np.arange(32), np.random.default_rng(0))
    assert batch["feats_0"].shape == (32, g.feat_dim)


@pytest.mark.parametrize("backend", ["host", "device"])
def test_builders_on_two_threads_match_serial_builds(setup, backend):
    """Each builder owns its dedup slot map: two devices' builders sampling
    concurrently on two threads give the specs a serial build gives.
    (With one map shared between them, the host case mismatches.)"""
    g, plan = setup
    steps, batch = 30, 4096

    def specs(dev, out, barrier=None):
        bh, bd, _, _ = _builders(g, plan, dev=dev)
        builder = bh if backend == "host" else bd
        rng = np.random.default_rng(100 + dev)
        tablet = plan.partition.tablets[dev]
        if barrier is not None:
            barrier.wait()
        for _ in range(steps):
            seeds = tablet[rng.integers(0, len(tablet), size=batch)]
            out.append(builder.sample_spec(seeds, rng))

    serial = {d: [] for d in (0, 1)}
    for d in (0, 1):
        specs(d, serial[d])
    threaded = {d: [] for d in (0, 1)}
    barrier = threading.Barrier(2)
    workers = [threading.Thread(target=specs, args=(d, threaded[d], barrier))
               for d in (0, 1)]
    for t in workers:
        t.start()
    for t in workers:
        t.join(timeout=120)
        assert not t.is_alive()
    for d in (0, 1):
        assert len(threaded[d]) == steps
        for a, b in zip(serial[d], threaded[d]):
            np.testing.assert_array_equal(a.ids, unique_vertices(a.levels))
            np.testing.assert_array_equal(a.ids, b.ids)
            for pa, pb in zip(a.level_pos, b.level_pos):
                assert pa.dtype == pb.dtype
                np.testing.assert_array_equal(pa, pb)
