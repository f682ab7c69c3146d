"""Sharded topology cache benchmark (the ``topology_scaling`` bench).

One fixed graph, a 4-device nv8 clique, device-backend training — three
arms, each in its own worker subprocess (XLA's forced host device count
must be set before jax import):

* ``replicated``: the equal-memory baseline.  The planner cuts the
  topology *union* at the per-device budget bt, every device mirrors it.
* ``sharded``: the routed layout.  Each device fills its own disjoint
  queue to the same bt, so the union caches ~K_g x more adjacency at
  identical per-device memory; frontier rows are routed to their owner
  shard by the neighbor exchange.
* ``covered``: a what-if arm (budget-exempt) — the sharded cache's
  topology is swapped for full coverage via ``replace_topology`` and the
  epoch must run with ZERO host sampling syncs and zero host-sampled
  edges (the sync-free contract).

A fourth ``hierarchy`` worker trains the 2x2 (K_c x K_g) mesh with the
sharded backend and gates the hierarchy invariant: routed neighbor-
exchange bytes never cross a clique boundary.

HARD gates (AssertionError -> ERROR row in run.py, what CI greps for):

* loss trajectories bitwise identical across replicated/sharded/covered
  (residency layout must not perturb sampling — the host-order draw
  contract);
* equal per-device memory: every sharded shard <= bt and the replicated
  union <= bt, with the same bt in both arms;
* sharded topology hit rate strictly above replicated;
* host-sampled edges: replicated / sharded >= 4x;
* covered arm: host_sample_syncs == 0, host_sampled_edges == 0;
* hierarchy arm: cross_clique_topo_bytes == 0 (and nonzero routed
  traffic overall, so the gate is not vacuous).

Structured results land in ``BENCH_topology.json``.  Run standalone with
``python benchmarks/topology_scaling.py [--smoke]``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

N_DEV = 4

# Broad presample (full train fraction, two epochs) so the hotness
# queues rank the whole reachable frontier — the budget, not the
# presample horizon, is then the binding constraint in BOTH arms.
PLAN_KW = dict(train_fraction=1.0, presample_epochs=2)


def _params(smoke: bool):
    if smoke:
        return dict(n=4000, deg=8, feat=32, steps=10, batch=128)
    return dict(n=40_000, deg=16, feat=64, steps=30, batch=512)


def _setup(smoke: bool, mode: str):
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig

    p = _params(smoke)
    g = powerlaw_graph(p["n"], p["deg"], seed=0, feat_dim=p["feat"])
    mem = 0.15 * g.n * g.feat_dim * 4
    plan = build_plan(g, topology_matrix("nv8", N_DEV), mem_per_device=mem,
                      batch_size=p["batch"], seed=0, fanouts=(5, 3),
                      topology_mode=mode, **PLAN_KW)
    cfg = GNNConfig(feat_dim=p["feat"], hidden=64, batch_size=p["batch"],
                    fanouts=(5, 3), lr=1e-3)
    return g, plan, cfg, mem, p


def _mode_worker(mode: str, smoke: bool) -> None:
    """Train the fixed graph device-backend under one topology layout and
    print one RESULT: JSON line with sampling + residency telemetry."""
    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.unified_cache import TrafficCounter
    from repro.train.loop import train_gnn

    g, plan, cfg, mem, p = _setup(smoke, mode)
    cache = plan.caches[0]
    cp = plan.cost_plans[0]
    bt = mem * cp["m_T"] / max(cp["m_T"] + cp["m_F"], 1)
    counter = TrafficCounter.for_plan(plan)
    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=p["steps"], seed=0, counter=counter,
                    backend="device", gather="auto")
    wall = time.perf_counter() - t0
    assert np.isfinite(res.losses).all()
    tm = counter.topo_bytes_matrix
    peer = int(tm[:, :-1].sum() - np.trace(tm[:, :-1]))
    out = {"mode": mode, "steps": p["steps"], "wall_s": wall,
           "steps_per_s": p["steps"] / wall,
           "topo_hit_rate": counter.topo_hit_rate,
           "host_sample_syncs": int(counter.host_sample_syncs),
           "host_sampled_edges": int(counter.host_sampled_edges),
           "topo_peer_bytes": peer,
           "topo_budget_bytes": float(bt),
           "union_topo_bytes": int(cache.topo_bytes),
           "union_topo_ids": int(len(cache.topo_ids)),
           "topo_bytes_by_device": [int(b) for b in
                                    cache.topo_bytes_by_device()],
           "losses": [float(x) for x in res.losses]}
    print("RESULT:" + json.dumps(out))


def _covered_worker(smoke: bool) -> None:
    """The sync-free what-if: full topology coverage (budget-exempt),
    gated in-process to zero host sampling syncs and edges."""
    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.unified_cache import TrafficCounter
    from repro.train.loop import train_gnn

    g, plan, cfg, _mem, p = _setup(smoke, "sharded")
    cache = plan.caches[0]
    cache.replace_topology(np.array_split(np.arange(g.n, dtype=np.int64),
                                          N_DEV))
    counter = TrafficCounter.for_plan(plan)
    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=p["steps"], seed=0, counter=counter,
                    backend="device", gather="auto")
    wall = time.perf_counter() - t0
    assert np.isfinite(res.losses).all()
    if counter.host_sample_syncs != 0:
        raise AssertionError(
            f"covered epoch issued {counter.host_sample_syncs} host "
            "sampling syncs (must be 0)")
    if counter.host_sampled_edges != 0:
        raise AssertionError(
            f"covered epoch host-sampled {counter.host_sampled_edges} "
            "edges (must be 0)")
    if not counter.topo_hits == counter.topo_requests > 0:
        raise AssertionError("covered epoch saw topology misses")
    out = {"mode": "covered", "steps": p["steps"], "wall_s": wall,
           "steps_per_s": p["steps"] / wall,
           "topo_hit_rate": counter.topo_hit_rate,
           "host_sample_syncs": int(counter.host_sample_syncs),
           "host_sampled_edges": int(counter.host_sampled_edges),
           "losses": [float(x) for x in res.losses]}
    print("RESULT:" + json.dumps(out))


def _hierarchy_worker(smoke: bool) -> None:
    """2x2 hierarchy, sharded backend: the routed neighbor exchange must
    stay strictly intra-clique."""
    sys.path.insert(0, SRC)
    import numpy as np

    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan
    from repro.core.unified_cache import TrafficCounter
    from repro.graph.csr import powerlaw_graph
    from repro.models.gnn import GNNConfig
    from repro.train.loop import train_gnn

    p = _params(smoke)
    g = powerlaw_graph(p["n"], p["deg"], seed=0, feat_dim=p["feat"])
    plan = build_plan(g, topology_matrix("nv2", N_DEV),
                      mem_per_device=0.15 * g.n * g.feat_dim * 4,
                      batch_size=p["batch"], seed=0, fanouts=(5, 3),
                      **PLAN_KW)
    cliques = plan.partition.cliques
    assert [len(c) for c in cliques] == [2, 2], cliques
    cfg = GNNConfig(feat_dim=p["feat"], hidden=64, batch_size=p["batch"],
                    fanouts=(5, 3), lr=1e-3)
    counter = TrafficCounter.for_plan(plan)
    t0 = time.perf_counter()
    res = train_gnn(g, plan, cfg, steps=p["steps"], seed=0, counter=counter,
                    backend="sharded", gather="auto")
    wall = time.perf_counter() - t0
    assert np.isfinite(res.losses).all()
    cross = int(counter.cross_clique_topo_bytes(cliques))
    if cross:
        raise AssertionError(f"{cross} cross-clique neighbor-exchange "
                             "bytes (must be 0)")
    total = int(counter.topo_bytes_matrix.sum())
    if not total:
        raise AssertionError("no topology traffic recorded — the "
                             "cross-clique gate would be vacuous")
    out = {"mode": "hierarchy_2x2", "steps": p["steps"], "wall_s": wall,
           "steps_per_s": p["steps"] / wall,
           "topo_hit_rate": counter.topo_hit_rate,
           "cross_clique_topo_bytes": cross,
           "total_topo_bytes": total}
    print("RESULT:" + json.dumps(out))


CPU_SIM = "cpu-simulated"  # every row of this bench comes from a CPU mesh


def _cpu_simulated(rows: List[tuple]) -> List[tuple]:
    """Label rows as measured on a simulated (forced host device) mesh."""
    return [(name, value, f"{note} [{CPU_SIM}]")
            for name, value, note in rows]


def _spawn_worker(worker_args: List[str], smoke: bool,
                  timeout: int = 1800) -> dict:
    """Spawn one worker subprocess with N_DEV forced host devices and
    return its parsed ``RESULT:`` JSON line.  The XLA flag is appended
    (not overwritten) so user/CI XLA flags survive; the last occurrence
    of a repeated flag wins.  The child runs on the CPU platform
    explicitly: under ``benchmarks/run.py`` the parent already holds the
    accelerator, and a simulated mesh is a CPU mesh by construction."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_DEV}").strip()
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


def run_topology(smoke: bool = False, json_dir: str = None) -> List[tuple]:
    """Spawn the four workers, hard-gate the cross-arm invariants, return
    run.py-style rows, and write ``BENCH_topology.json``."""
    rep = _spawn_worker(["--mode-worker", "replicated"], smoke)
    sh = _spawn_worker(["--mode-worker", "sharded"], smoke)
    cov = _spawn_worker(["--covered-worker"], smoke)
    hier = _spawn_worker(["--hierarchy-worker"], smoke)

    # ---- hard gates ----
    if sh["losses"] != rep["losses"] or cov["losses"] != rep["losses"]:
        raise AssertionError("topology residency layout perturbed the "
                             "loss trajectory (must be bitwise identical)")
    bt = rep["topo_budget_bytes"]
    if sh["topo_budget_bytes"] != bt:
        raise AssertionError("per-device topology budget differs between "
                             "arms — the comparison is not equal-memory")
    if not (max(sh["topo_bytes_by_device"]) <= bt
            and max(rep["topo_bytes_by_device"]) <= bt):
        raise AssertionError(
            f"per-device topology residency exceeds the bt={bt:.0f} "
            f"budget (sharded {sh['topo_bytes_by_device']}, replicated "
            f"{rep['topo_bytes_by_device']})")
    if not sh["topo_hit_rate"] > rep["topo_hit_rate"]:
        raise AssertionError(
            f"sharded topology hit rate {sh['topo_hit_rate']:.3f} does "
            f"not beat replicated {rep['topo_hit_rate']:.3f}")
    ratio = rep["host_sampled_edges"] / max(sh["host_sampled_edges"], 1)
    if ratio < 4.0:
        raise AssertionError(
            f"host-sampled-edge reduction {ratio:.2f}x < 4x "
            f"(replicated {rep['host_sampled_edges']}, sharded "
            f"{sh['host_sampled_edges']})")
    if not sh["topo_peer_bytes"] > 0:
        raise AssertionError("no routed neighbor-exchange peer traffic")
    if rep["topo_peer_bytes"] != 0:
        raise AssertionError("replicated arm recorded peer topology "
                             "traffic (hits must stay requester-local)")

    rows: List[tuple] = []
    for res in (rep, sh):
        pfx = f"topology_scaling/{res['mode']}"
        rows.append((f"{pfx}/topo_hit_rate", res["topo_hit_rate"],
                     f"union {res['union_topo_ids']} ids / "
                     f"{res['union_topo_bytes']}B, bt={bt:.0f}B per dev"))
        rows.append((f"{pfx}/host_sampled_edges",
                     float(res["host_sampled_edges"]),
                     "deferred host fills (fanout x miss rows)"))
        rows.append((f"{pfx}/host_sample_syncs",
                     float(res["host_sample_syncs"]),
                     "batches that touched the host CSR"))
        rows.append((f"{pfx}/topo_peer_bytes",
                     float(res["topo_peer_bytes"]),
                     "routed neighbor-exchange bytes (owner != requester)"))
        rows.append((f"{pfx}/steps_per_s", res["steps_per_s"],
                     f"wall={res['wall_s']:.2f}s steps={res['steps']}"))
    rows.append(("topology_scaling/losses_bitwise_equal", 1.0,
                 "replicated == sharded == covered (hard gate)"))
    rows.append(("topology_scaling/union_bytes_ratio",
                 sh["union_topo_bytes"] / max(rep["union_topo_bytes"], 1),
                 "sharded union / replicated union at equal bt"))
    rows.append(("topology_scaling/host_edge_reduction", ratio,
                 "replicated/sharded host-sampled edges (hard gate >= 4x)"))
    rows.append(("topology_scaling/covered/host_sample_syncs",
                 float(cov["host_sample_syncs"]),
                 "full coverage (budget-exempt what-if): hard gate == 0"))
    rows.append(("topology_scaling/covered/host_sampled_edges",
                 float(cov["host_sampled_edges"]), "hard gate == 0"))
    rows.append(("topology_scaling/hierarchy_2x2/cross_clique_topo_bytes",
                 float(hier["cross_clique_topo_bytes"]),
                 f"hard gate == 0 (total routed "
                 f"{hier['total_topo_bytes']}B)"))

    results = {"replicated": rep, "sharded": sh, "covered": cov,
               "hierarchy_2x2": hier,
               "host_edge_reduction": ratio,
               "topo_budget_bytes": bt}
    out_dir = (json_dir or os.environ.get("REPRO_BENCH_JSON_DIR")
               or os.path.join(os.path.dirname(__file__), ".."))
    path = os.path.abspath(os.path.join(out_dir, "BENCH_topology.json"))
    with open(path, "w") as f:
        json.dump({"smoke": smoke, "platform": CPU_SIM, "arms": results},
                  f, indent=2, sort_keys=True)
    return _cpu_simulated(rows)


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mode-worker", default="",
                    help="internal: run as the replicated/sharded worker")
    ap.add_argument("--covered-worker", action="store_true",
                    help="internal: run as the full-coverage worker")
    ap.add_argument("--hierarchy-worker", action="store_true",
                    help="internal: run as the 2x2 hierarchy worker")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: shrink the instance")
    args = ap.parse_args()
    if args.mode_worker:
        _mode_worker(args.mode_worker, args.smoke)
        return
    if args.covered_worker:
        _covered_worker(args.smoke)
        return
    if args.hierarchy_worker:
        _hierarchy_worker(args.smoke)
        return
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    rows = run_topology(smoke=args.smoke)
    dt_us = (time.perf_counter() - t0) * 1e6
    print(f"topology_scaling,{dt_us:.0f},ok rows={len(rows)}")
    for rname, value, note in rows:
        v = f"{value:.6g}" if isinstance(value, float) else value
        print(f"{rname},{v},{note}")


if __name__ == "__main__":
    main()
