"""Readings behind the limits that decide a cell's ``correct``.

    python3 bench/calibrate.py --workload <cell> [--seeds 12]
        [--control-seeds 3] [--fault-seeds 3] [--out FILE]

One process builds the cell's dataset and plan once, then for each seed
runs the program's first steps (the same ``train_gnn`` call and feed a
benchmark run checks) and the reference, and compares them: the *lower*
readings.  On the first ``--control-seeds`` seeds the reference computed in
bfloat16 stands in for the program: the *control*, whose readings must
fail.  On the first ``--fault-seeds`` seeds each fault of
``benchlib.faults`` is planted in the program's path and read the same
way.  Prints one JSON object with every reading, and the largest sound and
smallest control and fault reading of each number.  Needs a TPU;
``bench/tests/test_bench_control.py`` calls ``calibrate`` at a test's size
on the CPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".logs"))

NUMBERS = ("loss_rel_gap", "grad_norm_gap", "update_norm_gap",
           "feature_requests_diff", "topo_requests_diff")


def control_as_program(ctl: dict, ref: dict, opt: dict) -> dict:
    """The control's outputs in the form ``compare`` reads from the
    program: AdamW's first moment after one step is (1 - b1) times the
    clipped gradient."""
    return {"losses": ctl["losses"],
            "m1": {k: (1.0 - opt["b1"]) * v for k, v in ctl["g0"].items()},
            "p": ctl["p"], "feature_requests": ref["feature_requests"],
            "topo_requests": ref["topo_requests"]}


def calibrate(cell, seeds, control_seeds, fault_seeds, gather=None,
              compile_cache=True, fault_names=None) -> dict:
    from benchlib import faults, harness

    cfg, traffic = cell.config, cell.traffic
    if compile_cache:
        import jax

        from repro.utils import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    gather = gather or cfg["gather"]
    ckpt = os.path.join(harness.RUN_DIR, "ckpt")
    g, tv, _ = harness.make_dataset(cfg)
    plan = harness.plan_for(g, tv, cfg)
    W = int(traffic["first_steps"])
    planted = {k: v for k, v in faults.FAULTS.items()
               if fault_names is None or k in fault_names}
    out = {"program": [], "control": [], "faults": {k: [] for k in planted}}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        prog, _ = harness.first_steps(g, plan, cfg, traffic, seed, gather,
                                      ckpt)
        ref = harness.reference_steps(cfg, g, tv, seed, W,
                                      keep=i < control_seeds)
        row = harness.compare(prog, ref, cfg["optimizer"])
        out["program"].append({"seed": seed, **row})
        if i < control_seeds:
            ctl = harness.reference_steps(cfg, g, tv, seed, W,
                                          dtype="bfloat16",
                                          batches=ref["batches"])
            out["control"].append({"seed": seed, **harness.compare(
                control_as_program(ctl, ref, cfg["optimizer"]), ref,
                cfg["optimizer"])})
        if i < fault_seeds:
            for name, plant in planted.items():
                with plant():
                    bad, _ = harness.first_steps(g, plan, cfg, traffic, seed,
                                                 gather, ckpt)
                out["faults"][name].append({"seed": seed, **harness.compare(
                    bad, ref, cfg["optimizer"])})
        del ref
        harness.log(f"seed {seed}: {json.dumps(out['program'][-1])} "
                    f"({time.perf_counter() - t:.1f} s)")
    summary = {}
    for n in NUMBERS:
        summary[n] = {
            "lower": max(r[n] for r in out["program"]),
            "control_min": (min(r[n] for r in out["control"])
                            if out["control"] else None),
            "faults_min": {k: min(r[n] for r in v) if v else None
                           for k, v in out["faults"].items()}}
    out["summary"] = summary
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--faults", default=None,
                    help="comma-separated fault names (default: all)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    # set before JAX is imported, which reads it once: the compile cache
    # sits inside the checkout, as in bench/run.py
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")

    import jax

    from benchlib import harness

    if jax.devices()[0].platform != "tpu":
        harness.log("bench/calibrate.py needs a TPU")
        return 2
    cell = harness.Cell(args.workload)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = calibrate(cell, seeds, args.control_seeds, args.fault_seeds,
                    fault_names=(args.faults.split(",") if args.faults
                                 else None))
    text = json.dumps(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
