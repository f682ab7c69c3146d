"""End-to-end driver: train GraphSAGE with the full Legion stack
(hierarchical partitioning, unified cache, pipelined sampling server,
checkpointing).

Quick run:        PYTHONPATH=src python examples/train_graphsage.py
~100M-param run:  PYTHONPATH=src python examples/train_graphsage.py --full
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.cliques import topology_matrix
from repro.core.planner import build_plan
from repro.graph.csr import powerlaw_graph
from repro.models.gnn import GNNConfig
from repro.train.loop import train_gnn
from repro.utils import enable_compile_cache

ap = argparse.ArgumentParser()
ap.add_argument("--full", action="store_true",
                help="~100M-param model, a few hundred steps")
ap.add_argument("--steps", type=int, default=0)
ap.add_argument("--ckpt", default="/tmp/legion_sage_ckpt")
ap.add_argument("--backend", choices=["host", "device", "sharded"],
                default="host",
                help="batch pipeline: host numpy path; device-resident "
                     "cache sampling + Pallas feature gather; or the "
                     "clique-parallel shard_map executor (needs one jax "
                     "device per clique device — on CPU export XLA_FLAGS="
                     "--xla_force_host_platform_device_count=N first)")
ap.add_argument("--refresh-interval", type=int, default=None,
                help="enable the online cache manager: drift check + "
                     "adaptive cache refresh every N steps")
args = ap.parse_args()
enable_compile_cache()

if args.full:
    n, hidden, steps, batch = 200_000, 6912, args.steps or 300, 512
else:
    n, hidden, steps, batch = 30_000, 256, args.steps or 60, 256

g = powerlaw_graph(n, 20, seed=0, feat_dim=128)
plan = build_plan(g, topology_matrix("nv4"), mem_per_device=32e6, seed=0)
cfg = GNNConfig(feat_dim=128, hidden=hidden, batch_size=batch,
                fanouts=(10, 5), lr=1e-3)
n_params = 128 * hidden * 2 + hidden * hidden * 2 + hidden * 32
print(f"training SAGE hidden={hidden} (~{n_params/1e6:.1f}M params) "
      f"for {steps} steps")
# the sharded executor runs the full (pod, clique) hierarchy when the
# interpreter sees enough devices, else the first clique (the degenerate
# K_c=1 mesh); the other backends simulate all devices on one
devices = None
if args.backend == "sharded":
    import jax

    all_devs = [d for c in plan.partition.cliques for d in c]
    devices = (all_devs if jax.device_count() >= len(all_devs)
               else plan.partition.cliques[0])
    k_g = len(plan.partition.cliques[0])
    print(f"sharded mesh: {len(devices) // k_g}x{k_g} (pod, clique)")
res = train_gnn(g, plan, cfg, steps=steps, checkpoint_dir=args.ckpt,
                checkpoint_every=50, backend=args.backend, devices=devices,
                refresh_interval=args.refresh_interval)
print(f"loss {res.losses[0]:.3f} -> {res.losses[-1]:.3f}   "
      f"final acc {res.accs[-1]:.3f}")
print(f"backend {res.backend}  host build "
      f"{res.pipeline['host_build_s_mean'] * 1e3:.1f} ms/batch")
print(f"feature hit {res.counter.feature_hit_rate:.1%}  "
      f"topo hit {res.counter.topo_hit_rate:.1%}  "
      f"PCIe tx {res.counter.pcie_transactions}")
print("straggler:", res.straggler)
if res.refresh:
    print(f"cache refresh: {res.refresh['checks']} checks, "
          f"{res.refresh['refreshes']} refreshes, "
          f"{res.refresh['admitted']} rows admitted "
          f"({res.refresh['refresh_bytes_h2d']} H2D bytes)")
