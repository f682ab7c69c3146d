"""Clique-parallel executor checks — the body of tests/test_sharded.py.

Importable so the checks can run two ways:

* in-process, when the interpreter already sees >= 4 jax devices (the CI
  ``multidevice`` job launches pytest with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``);
* as a spawned subprocess that sets the flag itself (single-device local
  runs), keeping the main pytest process on 1 device.

Run directly: ``python tests/_sharded_checks.py <path-to-src>``.
"""
import numpy as np

N_DEV = 4


def check_routed_gather():
    """shard_map routed gather == dense oracle, xla and pallas impls."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ref
    from repro.kernels.gather import routed_gather
    from repro.launch.mesh import make_clique_mesh

    rng = np.random.default_rng(0)
    k, R, D, n = N_DEV, 12, 32, 50
    shards = rng.normal(size=(k, R, D)).astype(np.float32)
    owner = rng.integers(-1, k, size=(k, n)).astype(np.int32)  # -1 = miss
    local = rng.integers(0, R, size=(k, n)).astype(np.int32)
    want = np.asarray(ref.routed_gather_dense(
        jnp.asarray(shards), jnp.asarray(owner), jnp.asarray(local)))

    mesh = make_clique_mesh(k)
    for impl in ("xla", "pallas"):
        fn = jax.shard_map(
            lambda s, o, l: routed_gather(s[0], o[0], l[0], "clique",
                                          impl=impl)[None],
            mesh=mesh, in_specs=(P("clique"), P("clique"), P("clique")),
            out_specs=P("clique"), check_vma=False)
        got = np.asarray(jax.jit(fn)(shards, owner, local))
        np.testing.assert_array_equal(got, want, err_msg=f"impl={impl}")
    print("routed gather OK")


def check_routed_neighbor_exchange():
    """shard_map routed neighbor exchange == dense oracle == host sampler
    (replayed draws) — the mesh-collective form of the sharded topology
    cache's sample path."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph
    from repro.graph.sampling import host_sample_level
    from repro.kernels import ref
    from repro.kernels.gather import routed_neighbor_sample
    from repro.launch.mesh import make_clique_mesh

    rng = np.random.default_rng(1)
    g = powerlaw_graph(3000, 8, seed=9, feat_dim=16)
    plan = build_plan(g, topology_matrix("nv8", N_DEV),
                      mem_per_device=300_000, batch_size=256, seed=0)
    cache = plan.caches[0]
    assert cache.topology_mode == "sharded"
    k, n, f = N_DEV, 64, 5
    seeds = rng.integers(0, g.n, size=(k, n)).astype(np.int64)
    rand = rng.integers(0, 1 << 31, size=(k, n, f)).astype(np.int32)
    owner = cache.topo_owner[seeds].astype(np.int32)
    local = cache.topo_local[seeds].astype(np.int32)
    indptr = jnp.asarray(cache.topo_shard_indptr)
    indices = jnp.asarray(cache.topo_shard_indices)

    want = np.asarray(ref.routed_neighbor_sample_dense(
        indptr, indices, jnp.asarray(owner), jnp.asarray(local),
        jnp.asarray(rand)))
    # owned rows must replay the host sampler's draws bit-exactly; unowned
    # rows are the -1 sentinel for the deferred host fill
    for gi in range(k):
        host = host_sample_level(g, seeds[gi], f, None, rand=rand[gi])
        hit = owner[gi] >= 0
        np.testing.assert_array_equal(want[gi][hit], host[hit])
        assert (want[gi][~hit] == -1).all()

    mesh = make_clique_mesh(k)
    fn = jax.shard_map(
        lambda p, i, o, l, r: routed_neighbor_sample(
            p[0], i[0], o[0], l[0], r[0], "clique")[None],
        mesh=mesh, in_specs=(P("clique"), P("clique"), P("clique"),
                             P("clique"), P("clique")),
        out_specs=P("clique"), check_vma=False)
    got = np.asarray(jax.jit(fn)(indptr, indices, owner, local, rand))
    np.testing.assert_array_equal(got, want)
    print("routed neighbor exchange OK")


def _train(g, plan, cfg, backend, steps, devices=None):
    from repro.core.unified_cache import TrafficCounter
    from repro.train.loop import train_gnn

    counter = TrafficCounter.for_plan(plan)
    res = train_gnn(g, plan, cfg, steps=steps, seed=0, counter=counter,
                    backend=backend, gather="xla", devices=devices)
    return res, counter


def check_backend_parity():
    """host == device bit-for-bit; sharded matches both up to the float
    associativity of the per-clique psum (single-ulp per step), with
    bit-identical hit/miss/traffic accounting across all three."""
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig

    g = powerlaw_graph(3000, 8, seed=9, feat_dim=16)
    plan = build_plan(g, topology_matrix("nv8", N_DEV),
                      mem_per_device=300_000, batch_size=256, seed=0)
    assert plan.partition.cliques == [[0, 1, 2, 3]]
    cfg = GNNConfig(feat_dim=16, hidden=32, batch_size=64, fanouts=(4, 2),
                    lr=3e-3)
    steps = 12
    r_h, c_h = _train(g, plan, cfg, "host", steps)
    r_d, c_d = _train(g, plan, cfg, "device", steps)
    r_s, c_s = _train(g, plan, cfg, "sharded", steps)
    assert r_s.backend == "sharded"

    np.testing.assert_array_equal(r_h.losses, r_d.losses)
    np.testing.assert_allclose(r_d.losses, r_s.losses, rtol=0, atol=1e-4)
    np.testing.assert_allclose(r_d.accs, r_s.accs, rtol=0, atol=1e-6)
    for a, b in ((c_h, c_d), (c_d, c_s)):
        assert (a.feature_requests, a.feature_hits, a.topo_requests,
                a.topo_hits, a.pcie_transactions, a.host_sampled_edges) == \
               (b.feature_requests, b.feature_hits, b.topo_requests,
                b.topo_hits, b.pcie_transactions, b.host_sampled_edges)
        np.testing.assert_array_equal(a.bytes_matrix, b.bytes_matrix)
        np.testing.assert_array_equal(a.topo_bytes_matrix,
                                      b.topo_bytes_matrix)
    # host builds sync on every batch by construction; the chained device
    # sampler syncs at most that often (and identically across the device
    # and sharded backends, which share the sampler path)
    assert c_h.host_sample_syncs == steps * N_DEV
    assert c_d.host_sample_syncs == c_s.host_sample_syncs
    assert c_d.host_sample_syncs <= c_h.host_sample_syncs
    # the clique really routes: some hit bytes come from peer devices, for
    # features and for the sharded topology's neighbor exchange alike
    peer = c_s.bytes_matrix[:, :-1].sum() - np.trace(c_s.bytes_matrix[:, :-1])
    assert peer > 0, "no intra-clique peer traffic routed"
    topo_peer = (c_s.topo_bytes_matrix[:, :-1].sum()
                 - np.trace(c_s.topo_bytes_matrix[:, :-1]))
    assert topo_peer > 0, "no routed neighbor-exchange traffic"
    # ...but never across cliques (single clique here: vacuously zero —
    # check_clique_validation covers the 2x2 hierarchy)
    assert c_s.cross_clique_topo_bytes(plan.partition.cliques) == 0
    print("backend parity OK")


def check_sharded_epoch_pinning():
    """The partitioned shard stack honors the same double-buffered epoch
    contract as the flat device arrays: specs built before a refresh
    finalize against the stack they indexed; two refreshes back raises."""
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph

    g = powerlaw_graph(2000, 8, seed=3, feat_dim=16)
    plan = build_plan(g, topology_matrix("nv8", N_DEV),
                      mem_per_device=200_000, batch_size=256, seed=0)
    cache = plan.caches[0]
    e0 = cache.epoch
    old = np.asarray(cache.sharded_device_arrays()["feat_shards"])
    cache.begin_epoch()
    evict = cache.feat_ids[:2].copy()
    cache.apply_feature_delta(evict, np.asarray([], np.int64),
                              np.asarray([], np.int32))
    retained = np.asarray(cache.sharded_device_arrays(e0)["feat_shards"])
    np.testing.assert_array_equal(retained, old)
    new = cache.sharded_device_arrays()["feat_shards"]
    assert new.shape[0] == N_DEV
    cache.begin_epoch()
    try:
        cache.sharded_device_arrays(e0)
    except RuntimeError:
        pass
    else:
        raise AssertionError("stale sharded epoch did not raise")
    print("sharded epoch pinning OK")


def check_clique_validation():
    """Device sets that partially cover a clique are rejected; whole
    cliques — one, or several at once (the hierarchical mesh) — train."""
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig
    from repro.train.loop import train_gnn

    g = powerlaw_graph(2000, 8, seed=3, feat_dim=16)
    cfg = GNNConfig(feat_dim=16, hidden=32, batch_size=64, fanouts=(4, 2))
    plan = build_plan(g, topology_matrix("nv2", 4), mem_per_device=200_000,
                      batch_size=256, seed=0)  # two 2-cliques
    for bad in ([0], [0, 1, 2]):
        try:
            train_gnn(g, plan, cfg, steps=1, backend="sharded", devices=bad)
        except ValueError:
            pass
        else:
            raise AssertionError(f"devices={bad} should have been rejected")
    # a full single clique is the degenerate K_c=1 hierarchy
    res = train_gnn(g, plan, cfg, steps=2, backend="sharded", devices=[1, 0],
                    gather="xla")
    assert len(res.losses) == 2 and np.isfinite(res.losses).all()
    # both cliques at once: the 2x2 hierarchical mesh — and the sharded
    # topology exchange must stay strictly intra-clique on it
    from repro.core.unified_cache import TrafficCounter

    counter = TrafficCounter.for_plan(plan)
    res2 = train_gnn(g, plan, cfg, steps=2, backend="sharded",
                     devices=[2, 0, 3, 1], gather="xla", counter=counter)
    assert len(res2.losses) == 2 and np.isfinite(res2.losses).all()
    assert counter.cross_clique_topo_bytes(plan.partition.cliques) == 0
    assert counter.topo_bytes_matrix.sum() > 0
    print("clique validation OK")


def main():
    import jax

    assert jax.device_count() >= N_DEV, (
        f"need {N_DEV} devices, have {jax.device_count()}; set XLA_FLAGS="
        f"--xla_force_host_platform_device_count={N_DEV} before jax import")
    check_routed_gather()
    check_routed_neighbor_exchange()
    check_backend_parity()
    check_sharded_epoch_pinning()
    check_clique_validation()
    print("ALL SHARDED OK")


if __name__ == "__main__":
    import os
    import sys

    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={N_DEV}")
    if len(sys.argv) > 1:
        sys.path.insert(0, sys.argv[1])
    main()
