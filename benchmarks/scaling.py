"""Clique-parallel + hierarchical scaling benchmarks on simulated devices.

``run_scaling`` (the ``clique_scaling`` bench): 1 -> N devices of ONE
clique.  For each clique size a fresh worker process is spawned with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the flag must be
set before jax import, hence the subprocess), builds a single-clique plan,
trains with ``backend="sharded"`` — the shard_map executor with
cache-partition-aware gather routing — and reports

* throughput (steps/s and seed vertices/s), and
* the feature-gather traffic split per device: local-hit bytes (own cache
  partition), cross-device peer bytes (intra-clique exchange), and
  host-fill bytes (true misses over PCIe),

as ``name,value,derived`` CSV rows in the run.py format.

``run_hierarchy`` (the ``hierarchy_scaling`` bench): the 2-D sweep — the
SAME fixed graph trained on a 1x4, 2x2, and 2x4 (K_c x K_g) hierarchy.
Each worker additionally runs the single-device oracle (the host backend
over the same plan and seeds) and HARD-GATES parity: the sharded loss
trajectory must match within atol=1e-4, traffic accounting must be
bit-identical, and cross-clique feature-gather bytes must be exactly
zero (the hierarchy invariant: peer traffic never leaves a clique).
Results also land in ``BENCH_hierarchy.json`` (steps/s + per-clique
local/peer/host-fill bytes per configuration).

Run standalone with ``python benchmarks/scaling.py [--smoke]
[--devices 1,2,4] [--hierarchy]``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _worker(n_dev: int, smoke: bool) -> None:
    """Runs in the subprocess: train sharded on an n_dev clique, print
    one JSON result line prefixed with RESULT:."""
    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.core.unified_cache import TrafficCounter
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig
    from repro.train.loop import train_gnn

    if smoke:
        n, deg, feat, steps, batch = 4000, 8, 32, 10, 128
    else:
        n, deg, feat, steps, batch = 40_000, 16, 64, 30, 512
    g = powerlaw_graph(n, deg, seed=0, feat_dim=feat)
    plan = build_plan(g, topology_matrix("nv8", n_dev),
                      mem_per_device=0.1 * g.n * g.feat_dim * 4,
                      batch_size=batch, seed=0, fanouts=(5, 3))
    cfg = GNNConfig(feat_dim=feat, hidden=64, batch_size=batch,
                    fanouts=(5, 3), lr=1e-3)
    counter = TrafficCounter.for_plan(plan)
    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=steps, seed=0, counter=counter,
                    backend="sharded", gather="auto")
    wall = time.perf_counter() - t0
    bm = counter.bytes_matrix
    per_dev = []
    for d in range(n_dev):
        local = int(bm[d, d])
        peer = int(bm[d, :-1].sum() - bm[d, d])
        host = int(bm[d, -1])
        per_dev.append({"device": d, "local_bytes": local,
                        "peer_bytes": peer, "host_fill_bytes": host})
    out = {"n_dev": n_dev, "steps": steps, "wall_s": wall,
           "steps_per_s": steps / wall,
           "seeds_per_s": steps * batch / wall,
           "feature_hit_rate": counter.feature_hit_rate,
           "loss_first": float(res.losses[0]),
           "loss_last": float(res.losses[-1]),
           "per_dev": per_dev}
    assert np.isfinite(res.losses).all()
    print("RESULT:" + json.dumps(out))


# (K_c, K_g) -> the Table-1 topology kind + device count realizing it
HIERARCHY_KINDS = {(1, 4): ("nv8", 4), (2, 2): ("nv2", 4),
                   (2, 4): ("nv4", 8)}


def _hierarchy_worker(k_c: int, k_g: int, smoke: bool) -> None:
    """Runs in the subprocess (forced device count set by the parent):
    train the fixed graph on a k_c x k_g hierarchy, gate parity against
    the single-device oracle, print one RESULT: JSON line."""
    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.core.unified_cache import TrafficCounter
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig
    from repro.train.loop import train_gnn

    kind, n_gpus = HIERARCHY_KINDS[(k_c, k_g)]
    # one FIXED graph across every configuration — the sweep isolates the
    # mesh shape, not the instance
    if smoke:
        n, deg, feat, steps, batch = 4000, 8, 32, 10, 128
    else:
        n, deg, feat, steps, batch = 40_000, 16, 64, 30, 512
    g = powerlaw_graph(n, deg, seed=0, feat_dim=feat)
    plan = build_plan(g, topology_matrix(kind, n_gpus),
                      mem_per_device=0.1 * g.n * g.feat_dim * 4,
                      batch_size=batch, seed=0, fanouts=(5, 3))
    cliques = plan.partition.cliques
    assert [len(c) for c in cliques] == [k_g] * k_c, cliques
    cfg = GNNConfig(feat_dim=feat, hidden=64, batch_size=batch,
                    fanouts=(5, 3), lr=1e-3)
    # single-device oracle: host pipeline, identical plan/seeds/streams
    c_o = TrafficCounter.for_plan(plan)
    res_o = train_gnn(g, plan, cfg, steps=steps, seed=0, counter=c_o,
                      backend="host")
    c_s = TrafficCounter.for_plan(plan)
    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=steps, seed=0, counter=c_s,
                    backend="sharded", gather="auto")
    wall = time.perf_counter() - t0

    # ---- hard parity gate ----
    a, b = np.asarray(res_o.losses), np.asarray(res.losses)
    if not np.allclose(a, b, rtol=0, atol=1e-4):
        raise AssertionError(f"hierarchy {k_c}x{k_g}: sharded losses "
                             f"diverged from the single-device oracle "
                             f"(max |d|={np.abs(a - b).max():.3g})")
    if not (c_o.bytes_matrix == c_s.bytes_matrix).all():
        raise AssertionError(f"hierarchy {k_c}x{k_g}: traffic accounting "
                             "differs from the oracle")
    cross = c_s.cross_clique_bytes(cliques)
    if cross:
        raise AssertionError(f"hierarchy {k_c}x{k_g}: {cross} cross-clique "
                             "feature-gather bytes (must be 0)")
    per_clique = c_s.per_clique_split(cliques)
    out = {"k_c": k_c, "k_g": k_g, "steps": steps, "wall_s": wall,
           "steps_per_s": steps / wall,
           "seeds_per_s": steps * batch / wall,
           "feature_hit_rate": c_s.feature_hit_rate,
           "parity": 1, "cross_clique_bytes": cross,
           "loss_first": float(res.losses[0]),
           "loss_last": float(res.losses[-1]),
           "per_clique": per_clique}
    print("RESULT:" + json.dumps(out))


CPU_SIM = "cpu-simulated"  # every row of this bench comes from a CPU mesh


def _cpu_simulated(rows: List[tuple]) -> List[tuple]:
    """Label rows as measured on a simulated (forced host device) mesh."""
    return [(name, value, f"{note} [{CPU_SIM}]")
            for name, value, note in rows]


def _spawn_worker(worker_args: List[str], n_dev: int, smoke: bool,
                  timeout: int = 1800) -> dict:
    """Spawn one benchmark worker subprocess with ``n_dev`` forced host
    devices and return its parsed ``RESULT:`` JSON line.  The XLA flag is
    appended (not overwritten) so user/CI XLA flags survive; ours comes
    last, and the last occurrence of a repeated flag wins.  The child runs
    on the CPU platform explicitly: under ``benchmarks/run.py`` the parent
    already holds the accelerator, and a simulated mesh is a CPU mesh by
    construction."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={n_dev}").strip()
    cmd = [sys.executable, os.path.abspath(__file__)] + worker_args
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(f"worker {worker_args} failed:\n"
                           f"{r.stdout}\n{r.stderr}")
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT:"))
    return json.loads(line[len("RESULT:"):])


def run_hierarchy(configs=((1, 4), (2, 2), (2, 4)), smoke: bool = False,
                  json_dir: str = None) -> List[tuple]:
    """Spawn one worker per (K_c, K_g) hierarchy; returns run.py-style
    rows and writes ``BENCH_hierarchy.json``."""
    rows: List[tuple] = []
    results = []
    for k_c, k_g in configs:
        res = _spawn_worker(["--hworker", f"{k_c}x{k_g}"], k_c * k_g, smoke)
        results.append(res)
        pfx = f"hierarchy_scaling/{k_c}x{k_g}"
        rows.append((f"{pfx}/steps_per_s", res["steps_per_s"],
                     f"wall={res['wall_s']:.2f}s steps={res['steps']}"))
        rows.append((f"{pfx}/seeds_per_s", res["seeds_per_s"],
                     "mesh-wide seed throughput"))
        rows.append((f"{pfx}/parity", res["parity"],
                     "sharded == single-device oracle (hard gate)"))
        rows.append((f"{pfx}/cross_clique_bytes",
                     float(res["cross_clique_bytes"]),
                     "hierarchy invariant: must be 0"))
        rows.append((f"{pfx}/feature_hit_rate", res["feature_hit_rate"],
                     f"loss {res['loss_first']:.3f}->{res['loss_last']:.3f}"))
        for pc in res["per_clique"]:
            ci = pc["clique"]
            rows.append((f"{pfx}/clique{ci}/local_bytes",
                         float(pc["local_bytes"]), "own cache partition"))
            rows.append((f"{pfx}/clique{ci}/peer_bytes",
                         float(pc["peer_bytes"]),
                         "intra-clique cross-device exchange"))
            rows.append((f"{pfx}/clique{ci}/host_fill_bytes",
                         float(pc["host_fill_bytes"]),
                         "true misses (PCIe)"))
    out_dir = (json_dir or os.environ.get("REPRO_BENCH_JSON_DIR")
               or os.path.join(os.path.dirname(__file__), ".."))
    path = os.path.abspath(os.path.join(out_dir, "BENCH_hierarchy.json"))
    with open(path, "w") as f:
        json.dump({"smoke": smoke, "platform": CPU_SIM, "configs": results},
                  f, indent=2, sort_keys=True)
    return _cpu_simulated(rows)


def run_scaling(device_counts=(1, 2, 4), smoke: bool = False) -> List[tuple]:
    """Spawn one worker per clique size; returns run.py-style rows."""
    rows: List[tuple] = []
    for n_dev in device_counts:
        res = _spawn_worker(["--worker", str(n_dev)], n_dev, smoke,
                            timeout=1200)
        pfx = f"clique_scaling/{n_dev}dev"
        rows.append((f"{pfx}/steps_per_s", res["steps_per_s"],
                     f"wall={res['wall_s']:.2f}s steps={res['steps']}"))
        rows.append((f"{pfx}/seeds_per_s", res["seeds_per_s"],
                     "clique-wide seed throughput"))
        rows.append((f"{pfx}/feature_hit_rate", res["feature_hit_rate"],
                     f"loss {res['loss_first']:.3f}->{res['loss_last']:.3f}"))
        for pd in res["per_dev"]:
            d = pd["device"]
            rows.append((f"{pfx}/dev{d}/local_bytes",
                         float(pd["local_bytes"]), "own cache partition"))
            rows.append((f"{pfx}/dev{d}/peer_bytes",
                         float(pd["peer_bytes"]),
                         "intra-clique cross-device exchange"))
            rows.append((f"{pfx}/dev{d}/host_fill_bytes",
                         float(pd["host_fill_bytes"]), "true misses (PCIe)"))
    return _cpu_simulated(rows)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=0,
                    help="internal: run as the n-device worker")
    ap.add_argument("--hworker", default="",
                    help="internal: run as the KcxKg hierarchy worker")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: shrink the instance")
    ap.add_argument("--devices", default="1,2,4",
                    help="comma-separated clique sizes to sweep")
    ap.add_argument("--hierarchy", action="store_true",
                    help="run the KcxKg hierarchy sweep instead of the "
                         "single-clique scaling sweep")
    args = ap.parse_args()
    if args.worker:
        _worker(args.worker, args.smoke)
        return
    if args.hworker:
        k_c, k_g = (int(x) for x in args.hworker.split("x"))
        _hierarchy_worker(k_c, k_g, args.smoke)
        return
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    if args.hierarchy:
        name, rows = "hierarchy_scaling", run_hierarchy(smoke=args.smoke)
    else:
        counts = tuple(int(x) for x in args.devices.split(","))
        name, rows = "clique_scaling", run_scaling(counts, smoke=args.smoke)
    dt_us = (time.perf_counter() - t0) * 1e6
    print(f"{name},{dt_us:.0f},ok rows={len(rows)}")
    for rname, value, note in rows:
        v = f"{value:.6g}" if isinstance(value, float) else value
        print(f"{rname},{v},{note}")


if __name__ == "__main__":
    main()
