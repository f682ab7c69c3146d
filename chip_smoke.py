"""On-chip smoke test: Legion's training path on a TPU, end to end.

    python chip_smoke.py [--seed 0] [--steps 5]    # one chip
    python chip_smoke.py --chips 4                 # the 1x4 ICI clique only

Default phase (one chip).  Checks the three Pallas kernels of the feature
path bit-exactly against their jnp references at real sizes (a 1M-row
cache, about 190k gathered rows), then trains the paper's GraphSAGE config
(hidden 256, fan-outs 25x10, batch 8000) on the 200k-vertex ``PR``
instance through ``train_gnn(backend="device", gather="pallas")``, with a
cache budget that holds about half the feature rows so both hit and miss
rows reach the fused kernel.  It prints the losses, the feature hit rate,
compile time and count, peak device memory and whether the compiled fused
finalize holds the kernel, then trains the same steps with
``gather="xla"`` (the jnp reference) and requires bitwise-equal losses:
the kernel only copies rows.

``--chips 4`` runs this and nothing else: the same graph planned over one
4-chip ICI clique, ``backend="sharded"`` on the 1x4 mesh against
``backend="device"`` on the same plan and seeds.  Traffic counts must
match exactly and losses within rtol 1e-3; the feature-shard stack must
span the 4 chips.

Everything is generated from ``--seed``.  The script refuses to run
without a TPU.  Its last stdout line is one JSON object naming the device.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

CACHE_BYTES = 75e6  # per clique: ~101k of the 200k feature rows
KERNEL_ROWS, KERNEL_IDX = 1 << 20, 190_000  # kernel check: cache rows, ids


def _compile_meter():
    """Running totals of XLA backend compiles (count, seconds)."""
    import jax

    tot = {"n": 0, "s": 0.0}

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tot["n"] += 1
            tot["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    return tot


def _setup(seed: int, chips: int):
    from repro.configs.legion_gnn import GRAPHSAGE
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import synthetic_instance

    t0 = time.perf_counter()
    g = synthetic_instance("PR", seed=seed)
    cfg = dataclasses.replace(GRAPHSAGE, feat_dim=g.feat_dim,
                              n_classes=g.n_classes)
    plan = build_plan(g, topology_matrix("tpu-pod", chips),
                      mem_per_device=CACHE_BYTES / chips,
                      fanouts=cfg.fanouts, batch_size=cfg.batch_size,
                      seed=seed)
    print(f"graph PR: {g.n} vertices, {g.nnz} edges, D={g.feat_dim}; plan "
          f"over {chips} chip(s): {len(plan.caches[0].feat_ids)} cached "
          f"feature rows, {len(plan.caches[0].topo_ids)} cached adjacency "
          f"rows ({time.perf_counter() - t0:.1f} s)")
    return g, cfg, plan


def _train(g, plan, cfg, seed, steps, **kw):
    from repro.train.loop import train_gnn

    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=steps, seed=seed, **kw)
    assert len(res.losses) == steps and np.isfinite(res.losses).all(), \
        res.losses
    print(f"  {kw}: losses {[float(x) for x in res.losses]} "
          f"feature hit rate {res.counter.feature_hit_rate:.6f} "
          f"({time.perf_counter() - t0:.1f} s)")
    return res


def _peak_bytes(device):
    """``peak_bytes_in_use`` as the backend reports it (None if it does
    not)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def _traffic(c) -> tuple:
    return (c.feature_requests, c.feature_hits, c.topo_requests,
            c.topo_hits, c.pcie_transactions, c.host_sampled_edges,
            c.bytes_matrix.tobytes(), c.topo_bytes_matrix.tobytes())


def check_kernels(seed: int) -> None:
    """Each kernel of the feature path == its jnp reference, bit for bit,
    on the chip at the sizes the paper's setting produces."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops, ref

    n_rows, n_idx, width = KERNEL_ROWS, KERNEL_IDX, 128
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    table = jax.random.normal(k[0], (n_rows, width), jnp.float32)
    idx = jax.random.randint(k[1], (n_idx,), -1, n_rows, jnp.int32)
    miss = jax.random.normal(k[2], (n_idx, width), jnp.float32)
    inv = jnp.where(jax.random.bernoulli(k[3], 0.5, (n_idx,)),
                    jax.random.randint(k[4], (n_idx,), 0, n_idx), -1)
    slots = jax.random.permutation(k[5], n_rows)[:n_idx].astype(jnp.int32)
    cases = {
        "gather_rows": (ops.gather_rows(table, idx),
                        ref.gather_rows(table, idx)),
        "fused_gather_overlay": (
            ops.fused_gather_overlay(table, idx, miss, inv),
            ref.fused_gather_overlay(table, idx, miss, inv)),
        "scatter_rows": (ops.scatter_rows(table, slots, miss),
                         ref.scatter_rows(table, slots, miss)),
    }
    for name, (got, want) in cases.items():
        same = bool(jnp.array_equal(got, want))
        print(f"kernel {name} {tuple(got.shape)}: bitwise equal to ref: "
              f"{same}")
        assert same, name


def one_chip(seed: int, steps: int) -> None:
    import jax

    from repro.train.batch import DeviceBatchBuilder, _get_fused_finalize

    check_kernels(seed)
    g, cfg, plan = _setup(seed, 1)
    meter = _compile_meter()
    fused = _get_fused_finalize()
    res_p = _train(g, plan, cfg, seed, steps, backend="device",
                   gather="pallas")
    hit = res_p.counter.feature_hit_rate
    assert 0.0 < hit < 1.0, hit
    print(f"  compiles: {meter['n']} ({meter['s']:.1f} s), fused finalize "
          f"compiled {fused._cache_size()} time(s)")
    print(f"  peak_bytes_in_use {_peak_bytes(jax.devices()[0])}")

    # the step's first batch again, to show the kernel is in the program
    cache = plan.caches[0]
    builder = DeviceBatchBuilder(g, cache, cfg.fanouts, gather="pallas")
    rng = np.random.default_rng(seed)  # device 0's stream in train_gnn
    tablet = plan.partition.tablets[0]
    spec = builder.build_spec(
        tablet[rng.integers(0, len(tablet), size=cfg.batch_size)], rng)
    print(f"  first batch: {spec.n_ids} unique ids ({len(spec.ids)} "
          f"padded), {spec.n_miss} misses ({len(spec.miss_feats)} staged)")
    text = fused.lower(*builder.finalize_args(spec), impl="pallas",
                       D=g.feat_dim).compile().as_text()
    assert "tpu_custom_call" in text
    print("  fused finalize compiled text holds tpu_custom_call: True")

    res_x = _train(g, plan, cfg, seed, steps, backend="device", gather="xla")
    assert res_x.losses == res_p.losses, (res_p.losses, res_x.losses)
    assert _traffic(res_x.counter) == _traffic(res_p.counter)
    print("  pallas losses bitwise equal to the xla reference: True")


def four_chips(seed: int, steps: int) -> None:
    import jax

    g, cfg, plan = _setup(seed, 4)
    assert plan.partition.cliques == [[0, 1, 2, 3]], plan.partition.cliques
    meter = _compile_meter()
    res_s = _train(g, plan, cfg, seed, steps, backend="sharded",
                   gather="pallas")
    assert res_s.backend == "sharded"
    print(f"  compiles: {meter['n']} ({meter['s']:.1f} s)")
    stack = plan.caches[0].sharded_device_arrays()["feat_shards"]
    devs = stack.sharding.device_set
    print(f"  feature-shard stack {tuple(stack.shape)} on {len(devs)} "
          f"devices, per-device shards "
          f"{sorted({tuple(s.data.shape) for s in stack.addressable_shards})}")
    assert len(devs) == 4, devs
    peaks = [_peak_bytes(d) for d in jax.devices()]
    print(f"  peak_bytes_in_use per chip after sharded: {peaks}")

    res_d = _train(g, plan, cfg, seed, steps, backend="device",
                   gather="pallas")
    assert _traffic(res_s.counter) == _traffic(res_d.counter)
    print(f"  traffic counts equal: feature requests "
          f"{res_s.counter.feature_requests} hits "
          f"{res_s.counter.feature_hits} topo requests "
          f"{res_s.counter.topo_requests} pcie tx "
          f"{res_s.counter.pcie_transactions}")
    a, b = np.asarray(res_s.losses), np.asarray(res_d.losses)
    print(f"  max |sharded - device| loss difference "
          f"{float(np.abs(a - b).max())}")
    np.testing.assert_allclose(a, b, rtol=1e-3)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; JAX found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"--chips {args.chips}: JAX sees {len(jax.devices())}")

    from repro.utils import enable_compile_cache

    print(f"device {dev.device_kind} x{len(jax.devices())}; compile cache "
          f"{enable_compile_cache()}")
    if args.chips == 1:
        one_chip(args.seed, args.steps)
    else:
        four_chips(args.seed, args.steps)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
