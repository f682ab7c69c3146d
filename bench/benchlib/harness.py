"""One benchmark run of one training cell, end to end.

Set-up builds the dataset from the configuration's ``graph_seed`` and the
Legion plan, then warms every program the window will run:

1. the *first steps*: ``train_gnn`` from ``--seed`` for the traffic's
   ``first_steps`` (step 1, then a resumed call for the rest), each call
   checkpointing at its end.  This compiles the sampler, the fused
   finalize and the train step, and these steps are the ones the
   reference checks;
2. the *census*: the reference sampler replays the seed's draws for every
   step the window will take and gives each step's padded shapes, so the
   fused finalize is compiled for each of them before the window opens;
3. the *window*: ``train_gnn(resume=True)`` continues the same training
   state from the last checkpoint.  Its first step traces the step function
   again and is left out; the window runs from that step's end to the
   first step end ``--seconds`` later.

After the window the device peak is read, the program's state is freed,
and the reference runs the first steps again from the seed and is compared
with what the program produced.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from benchlib import graphgen, refgnn

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DATA_DIR = os.path.join(BENCH_DIR, ".data")
RUN_DIR = os.path.join(BENCH_DIR, ".run")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def host_peak_gb() -> float:
    """This process's peak resident host memory so far."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A cell's files, found by the names in BENCHMARK.json."""

    def __init__(self, name: str, bench: Optional[dict] = None,
                 config: Optional[dict] = None):
        """``bench`` and ``config`` replace BENCHMARK.json and the
        configuration file (tests run a cell at a smaller size)."""
        bench = bench if bench is not None else load_json(
            os.path.join(ROOT, "BENCHMARK.json"))
        self.bench = bench
        wl = {w["name"]: w for w in bench["workloads"]}[name]
        self.name, self.chips = name, int(wl["chips"])
        self.config = config if config is not None else load_json(
            os.path.join(BENCH_DIR, "configs", f"{wl['config']}.json"))
        self.traffic = load_json(
            os.path.join(BENCH_DIR, "traffic", f"{wl['traffic']}.json"))
        self.limits = load_json(
            os.path.join(BENCH_DIR, "workloads", f"{name}.json"))["limits"]

    def metrics(self, kind: str) -> List[dict]:
        """This cell's ``end_to_end`` or ``per_layer`` entries."""
        return [m for m in self.bench[kind]
                if self.name in m.get("workloads", [self.name])]


def window_steps(step_ends_ns: List[int], seconds: float) -> int:
    """How many steps the window holds: it opens at the first step's end
    and closes at the first later step end at least ``seconds`` after that
    (or at the last step, if none is)."""
    t0 = step_ends_ns[0]
    for k, t in enumerate(step_ends_ns[1:], start=1):
        if t - t0 >= seconds * 1e9:
            return k
    return len(step_ends_ns) - 1


def round_bucket(n: int, bucket: int) -> int:
    """The program's padded batch layout: the smallest positive multiple
    of ``bucket`` that holds ``n`` rows."""
    return max(-(-n // bucket), 1) * bucket


class Run:
    """Everything one run measured; per-layer readers take what they
    need from it."""

    def __init__(self):
        self.setup_s = 0.0
        self.window_ns = (0, 0)
        self.window_steps: List[int] = []
        self.step_s: List[float] = []
        self.spans = []
        self.counter = None
        self.census: List[dict] = []
        self.trace = None
        self.config: dict = {}
        self.device_kind = ""
        self.compiles: dict = {}
        self.memory_peak_bytes = 0


def make_dataset(cfg: dict):
    from repro.graph.csr import CSRGraph

    t0 = time.perf_counter()
    indptr, indices, built = graphgen.load_or_build(
        DATA_DIR, cfg["n_vertices"], cfg["avg_degree"], cfg["alpha"],
        cfg["graph_seed"])
    t1 = time.perf_counter()
    X = graphgen.uniform_features(cfg["n_vertices"], cfg["feat_dim"],
                                  cfg["graph_seed"])
    t2 = time.perf_counter()
    tv = graphgen.train_split(cfg["n_vertices"], cfg["train_fraction"],
                              cfg["graph_seed"])
    g = CSRGraph(indptr=indptr, indices=indices, n=cfg["n_vertices"],
                 feat_dim=cfg["feat_dim"], n_classes=cfg["n_classes"],
                 features=X, seed=cfg["graph_seed"])
    log(f"host peak {host_peak_gb():.1f} GB; dataset: {g.n} vertices, {g.nnz} edges (mean degree "
        f"{g.nnz / g.n:.3f}), D={g.feat_dim}, {len(tv)} training vertices; "
        f"graph {'generated' if built else 'loaded'} in {t1 - t0:.1f} s, "
        f"features {X.nbytes / 1e9:.2f} GB in {t2 - t1:.1f} s")
    return g, tv, built


def plan_for(g, tv, cfg: dict):
    from repro.core.cliques import topology_matrix
    from repro.core.planner import build_plan

    t0 = time.perf_counter()
    plan = build_plan(g, topology_matrix("tpu-pod", 1),
                      mem_per_device=cfg["cache_bytes_per_chip"],
                      train_vertices=tv, fanouts=tuple(cfg["fanouts"]),
                      batch_size=cfg["batch_size"], seed=cfg["plan_seed"])
    c = plan.caches[0]
    log(f"host peak {host_peak_gb():.1f} GB; plan: alpha {plan.cost_plans[0]['alpha']:.2f}, "
        f"{len(c.feat_ids)} cached feature rows "
        f"({len(c.feat_ids) / g.n:.3f} of all), {len(c.topo_ids)} cached "
        f"adjacency rows, {time.perf_counter() - t0:.1f} s "
        f"{ {k: round(v, 1) for k, v in plan.timings.items()} }")
    return plan


def gnn_config(cfg: dict):
    """The program's model configuration; the configuration's optional
    ``model_args`` pass as keywords, so one it does not know fails."""
    from repro.models.gnn import GNNConfig

    return GNNConfig(name=cfg["name"], model=cfg["model"],
                     feat_dim=cfg["feat_dim"], hidden=cfg["hidden"],
                     n_classes=cfg["n_classes"],
                     fanouts=tuple(cfg["fanouts"]),
                     batch_size=cfg["batch_size"], lr=cfg["optimizer"]["lr"],
                     **cfg.get("model_args", {}))


def census(sampler: refgnn.Sampler, cache, skip: int, steps: int,
           bucket: int) -> List[dict]:
    """Padded shapes of the next ``steps`` batches after ``skip`` steps of
    draws: (unique ids, misses) and their bucket-rounded sizes.  The draws
    are made in step order; the batches are sampled on a thread pool."""
    from concurrent.futures import ThreadPoolExecutor

    for _ in range(skip):
        sampler.draw()
    draws = [sampler.draw() for _ in range(steps)]

    def shape(d):
        lv = sampler.levels(*d)
        flat = np.concatenate([x.reshape(-1) for x in lv])
        ids = np.unique(flat[flat >= 0])
        _, hit = cache.split_hits(ids)
        n_ids, n_miss = len(ids), int((~hit).sum())
        return {"n_ids": n_ids, "n_miss": n_miss,
                "n_pad": round_bucket(n_ids, bucket),
                "m_pad": round_bucket(n_miss, bucket)}

    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as ex:
        return list(ex.map(shape, draws))


def warm_finalize(g, cache, cfg: dict, gather: str, bucket: int,
                  shapes) -> int:
    """Compile the fused finalize for every (ids, misses) padded shape,
    on inert inputs (every row padding)."""
    from repro.train.batch import BatchSpec, DeviceBatchBuilder

    builder = DeviceBatchBuilder(g, cache, tuple(cfg["fanouts"]),
                                 gather=gather, bucket=bucket)
    # misses stage at the device table's (lane-padded) width
    width = cache.device_arrays()["feat_cache"].shape[1]
    B = cfg["batch_size"]
    lv_shapes = [(B,)]
    for f in cfg["fanouts"]:
        lv_shapes.append(lv_shapes[-1] + (f,))
    for n_pad, m_pad in sorted(shapes):
        spec = BatchSpec(
            labels=np.zeros(B, np.int32),
            levels=[np.full(s, -1, np.int64) for s in lv_shapes],
            ids=np.full(n_pad, -1, np.int64),
            level_pos=[np.zeros(s, np.int64) for s in lv_shapes],
            cache_pos=np.full(n_pad, -1, np.int64),
            hit=np.zeros(n_pad, bool),
            miss_feats=np.zeros((m_pad, width), np.float32),
            miss_inv=np.full(n_pad, -1, np.int32),
            cache_epoch=cache.epoch)
        out = builder.finalize(spec)
        out["feats_0"].block_until_ready()
        del out  # one dummy batch on the chip at a time
    return len(shapes)


def read_checkpoint(path: str, paths: List[tuple]) -> Dict[str, dict]:
    """Parameters and AdamW's first moment from one checkpoint of
    ``(params, {"count", "m", "v"})``: leaves in flattening order."""
    n = len(paths)
    with np.load(path) as z:
        leaves = [z[f"leaf_{i}"] for i in range(2 * n + 1 + n)]
    return {"p": dict(zip(paths, leaves[:n])),
            "m": dict(zip(paths, leaves[n + 1:2 * n + 1]))}


def gap_by_leaf(prog: Dict[tuple, np.ndarray], ref: Dict[tuple, np.ndarray],
                ref_grad: Dict[tuple, np.ndarray]) -> tuple:
    """The worst leaf's gap between the program's norm and the
    reference's, over the larger of that leaf's reference norm and the
    median leaf's.  Leaves whose reference gradient norm is under a
    thousandth of the median leaf's are left out (round-off moves them)."""
    gnorm = {k: float(np.linalg.norm(v)) for k, v in ref_grad.items()}
    g_med = statistics.median(gnorm.values())
    keep = [k for k in ref if gnorm[k] >= 1e-3 * g_med]
    rn = {k: float(np.linalg.norm(ref[k])) for k in keep}
    med = statistics.median(rn.values())
    worst, at = 0.0, None
    for k in keep:
        gap = abs(float(np.linalg.norm(prog[k])) - rn[k]) / max(rn[k], med)
        if gap > worst or at is None:
            worst, at = gap, k
    return worst, "/".join(at), len(ref) - len(keep)


def compare(prog: dict, ref: dict, opt: dict) -> Dict[str, float]:
    """The numbers that decide ``correct``: see PERF.md."""
    losses_p, losses_r = prog["losses"], ref["losses"]
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses_p, losses_r))
    grad_prog = {k: v / (1.0 - opt["b1"]) for k, v in prog["m1"].items()}
    grad_gap, grad_at, left_out = gap_by_leaf(grad_prog, ref["g0"], ref["g0"])
    d_prog = {k: prog["p"][k] - ref["p0"][k] for k in ref["p0"]}
    d_ref = {k: ref["p"][k] - ref["p0"][k] for k in ref["p0"]}
    delta_gap, delta_at, _ = gap_by_leaf(d_prog, d_ref, ref["g0"])
    return {"loss_rel_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": delta_gap,
            "feature_requests_diff": float(abs(
                prog["feature_requests"] - ref["feature_requests"])),
            "topo_requests_diff": float(abs(
                prog["topo_requests"] - ref["topo_requests"])),
            "_grad_at": grad_at, "_delta_at": delta_at,
            "_left_out": left_out}


def ref_shapes(cfg: dict, model) -> dict:
    """The reference's parameter leaves for this configuration."""
    return refgnn.param_shapes(model, cfg["feat_dim"], cfg["hidden"],
                               cfg["n_classes"], len(cfg["fanouts"]),
                               cfg.get("model_args"))


def reference_steps(cfg: dict, g, tv, seed: int, steps: int,
                    dtype: str = "float32", batches=None,
                    keep: bool = False) -> dict:
    """The reference's first ``steps`` steps from ``seed``, one batch at a
    time, at the configuration's matmul precision."""
    model = refgnn.load_model(cfg["model"])
    shapes = ref_shapes(cfg, model)
    sampler = refgnn.Sampler(g.indptr, g.indices,
                             refgnn.tablet(tv, cfg["plan_seed"]),
                             cfg["batch_size"], cfg["fanouts"], seed)
    return refgnn.run(model, cfg["optimizer"], shapes, g.features, sampler,
                      cfg["graph_seed"], cfg["n_classes"], seed, steps,
                      cfg["matmul_precision"], dtype=dtype, batches=batches,
                      keep=keep, model_args=cfg.get("model_args"))


def train_kwargs(cfg: dict, traffic: dict, seed: int, gather: str,
                 ckpt_dir: str) -> dict:
    """The program's options for this cell: the device backend with the
    configuration's gather and batch bucket, the traffic's seed order."""
    return dict(seed=seed, backend="device", gather=gather,
                bucket=int(cfg["bucket"]), shuffle=traffic["shuffle"],
                checkpoint_dir=ckpt_dir)


def first_steps(g, plan, cfg: dict, traffic: dict, seed: int, gather: str,
                ckpt_dir: str) -> tuple:
    """``train_gnn`` from ``seed`` for the traffic's ``first_steps``: one
    call for step 1, then one that resumes from its checkpoint for the
    rest.  A call's last checkpoint is always written, while one taken
    inside the loop is dropped when the next arrives before its write
    starts.  Returns (what the comparison reads, the calls' telemetry):
    the per-step losses, AdamW's first moment after step 1, the parameters
    after the last step, and the traffic counts."""
    from repro.train.loop import train_gnn
    from benchlib.telemetry import RecordingTelemetry

    W = int(traffic["first_steps"])
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    tele, losses, feature_requests, topo_requests = None, [], 0, 0
    for steps in sorted({1, W}):
        part = RecordingTelemetry(jax_annotations=False)
        res = train_gnn(g, plan, gnn_config(cfg), steps=steps,
                        resume=steps > 1, checkpoint_every=1 << 30,
                        telemetry=part,
                        **train_kwargs(cfg, traffic, seed, gather, ckpt_dir))
        if tele is None:
            tele = part
        else:
            tele.records.extend(part.records)
        losses += list(res.losses)
        feature_requests += res.counter.feature_requests
        topo_requests += res.counter.topo_requests
    paths = refgnn.leaf_paths(ref_shapes(cfg,
                                         refgnn.load_model(cfg["model"])))
    ck1 = read_checkpoint(os.path.join(ckpt_dir, f"ckpt_{1:08d}.npz"), paths)
    ckw = read_checkpoint(os.path.join(ckpt_dir, f"ckpt_{W:08d}.npz"), paths)
    return ({"losses": losses, "m1": ck1["m"], "p": ckw["p"],
             "feature_requests": feature_requests,
             "topo_requests": topo_requests}, tele)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
             gather: Optional[str] = None, compile_cache: bool = True
             ) -> tuple:
    """One run.  Returns (result dict, checks dict, Run)."""
    import jax

    from repro.train.loop import train_gnn
    from benchlib.telemetry import CompileLog, RecordingTelemetry

    cfg, traffic = cell.config, cell.traffic
    dev = jax.devices()[0]
    if compile_cache:
        from repro.utils import enable_compile_cache

        log(f"compile cache: {enable_compile_cache()}")
        # every program goes to the cache, however quick its compile
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = CompileLog()
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    ckpt_dir = os.path.join(RUN_DIR, "ckpt")
    trace_dir = os.path.join(RUN_DIR, "trace")
    gather = gather or cfg["gather"]
    bucket = int(cfg["bucket"])
    W = int(traffic["first_steps"])
    run = Run()
    run.config, run.device_kind = cfg, dev.device_kind

    log(f"host peak {host_peak_gb():.1f} GB at start")
    g, tv, _ = make_dataset(cfg)
    plan = plan_for(g, tv, cfg)

    # 1. the first steps, from the seed (compiles the whole path)
    tw = time.perf_counter()
    prog, tele_w = first_steps(g, plan, cfg, traffic, seed, gather,
                               ckpt_dir)
    steps_w = tele_w.named("device_step")
    builds = tele_w.named("prefetch_build")
    # one prefetch worker builds one batch at a time, so the window's step
    # is the longer of the device step and the host build
    t_step = max(statistics.median(s.dur_ns for s in steps_w[1:]),
                 statistics.median(s.dur_ns for s in builds)) / 1e9
    log(f"first {W} steps: losses {prog['losses']}, step times "
        f"{[round(s.dur_ns / 1e9, 4) for s in steps_w]} s, builds "
        f"{[round(s.dur_ns / 1e9, 4) for s in builds]} s, "
        f"{time.perf_counter() - tw:.1f} s in all")

    # 2. the census of the window's padded shapes, and their compiles.  The
    # first steps' builds run slower than the window's (their checkpoints
    # and cold buffers compete), so the timed call takes enough steps for
    # a step of 0.6 times that estimate; the window then closes at the
    # first step end ``seconds`` after it opened
    n_win = max(int(traffic["min_window_steps"]),
                math.ceil(seconds / (0.6 * t_step)))
    tc = time.perf_counter()
    sampler = refgnn.Sampler(g.indptr, g.indices,
                             refgnn.tablet(tv, cfg["plan_seed"]),
                             cfg["batch_size"], cfg["fanouts"], seed)
    run.census = census(sampler, plan.caches[0], W, n_win + 1, bucket)
    pairs = {(c["n_pad"], c["m_pad"]) for c in run.census}
    warm_finalize(g, plan.caches[0], cfg, gather, bucket, pairs)
    log(f"census: {n_win + 1} steps, {len(pairs)} padded shapes "
        f"{sorted(pairs)}, {time.perf_counter() - tc:.1f} s")

    # 3. the window
    tele = RecordingTelemetry(jax_annotations=trace)
    if trace:
        jax.profiler.start_trace(trace_dir)
    try:
        timed = train_gnn(g, plan, gnn_config(cfg), steps=W + 1 + n_win,
                          resume=True, checkpoint_every=1 << 30,
                          telemetry=tele,
                          **train_kwargs(cfg, traffic, seed, gather, ckpt_dir))
    finally:
        if trace:
            jax.profiler.stop_trace()
    compiles.stop()
    ds = tele.named("device_step")
    if len(ds) != n_win + 1 or ds[0].step != W:
        raise RuntimeError(f"expected {n_win + 1} device steps from step {W}, "
                           f"got {[s.step for s in ds]}")
    k = window_steps([s.t1_ns for s in ds], seconds)
    run.window_ns = (ds[0].t1_ns, ds[k].t1_ns)
    run.window_steps = [s.step for s in ds[1:k + 1]]
    run.step_s = [s.dur_ns / 1e9 for s in ds[1:k + 1]]
    run.spans = tele.records
    run.counter = timed.counter
    run.setup_s = run.window_ns[0] / 1e9 - t0
    run.compiles = compiles.within(*run.window_ns)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in jax.devices()[:cell.chips])
    run.memory_peak_bytes = peak_bytes
    log(f"host peak {host_peak_gb():.1f} GB; window: {k} of {n_win} steps in "
        f"{(run.window_ns[1] - run.window_ns[0]) / 1e9:.3f} s, compiles "
        f"and traces inside it {run.compiles}, peak bytes {peak_bytes}")
    if trace:
        from benchlib import xplane

        run.trace = xplane.reduce(trace_dir, k)

    # 4. the reference, once the program's state is freed
    losses_t = list(timed.losses)
    del timed, plan
    gc.collect()
    tr = time.perf_counter()
    ref = reference_steps(cfg, g, tv, seed, W)
    checks = compare(prog, ref, cfg["optimizer"])
    log(f"reference: {W} steps in {time.perf_counter() - tr:.1f} s, "
        f"losses {ref['losses']}")
    finite = [x for x in losses_t if math.isfinite(x)]
    result = {"attempted": len(losses_t),
              "failed": len(losses_t) - len(finite),
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": cell.chips,
                         "memory_peak_bytes": int(peak_bytes)}}
    if trace and run.trace is not None:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
    return result, checks, run


def read_metrics(cell: Cell, run: Run, kind: str) -> dict:
    out = {}
    for m in cell.metrics(kind):
        mod = load_module(os.path.join(BENCH_DIR, "metrics",
                                       f"{m['name']}.py"),
                          "bench_metric_" + m["name"].replace(".", "_"))
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def verdict(checks: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over every limited number."""
    shown = {}
    for name, limit in limits.items():
        shown[name] = {"value": checks[name], "limit": limit}
    ok = all(v["value"] <= v["limit"] for v in shown.values())
    return ok, shown


def main(args, t0: float) -> int:
    import jax

    try:
        import repro.train.loop  # noqa: F401  the system under test
    except ImportError as e:
        log(f"bench/run.py runs the program under src/: {e}")
        return 2
    cell = Cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench/run.py needs a TPU; JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips; JAX sees "
            f"{len(devs)}")
        return 2
    result, checks, run = run_cell(cell, args.seed, args.seconds,
                                   bool(args.trace), t0)
    log(f"compiles inside the window: {run.compiles}")
    log(f"compared ({cell.name}, seed {args.seed}): worst gradient leaf "
        f"{checks['_grad_at']}, worst update leaf {checks['_delta_at']}, "
        f"{checks['_left_out']} leaves left out")
    correct, shown = verdict(checks, cell.limits)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"],
           "metrics": read_metrics(cell, run, "per_layer" if args.trace
                                   else "end_to_end"),
           "device": result["device"]}
    if args.trace and run.trace is not None:
        out["breakdown"] = run.trace["breakdown"]
    out["checks"] = shown
    for name, v in shown.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
