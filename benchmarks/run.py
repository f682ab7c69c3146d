"""Benchmark runner: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV: us_per_call is the benchmark's
wall time per measured unit; each figure's metric rows follow as
``name,value,derived``.

``--backend {host,device}`` selects the batch pipeline the training
benchmarks run through (see repro.train.batch); ``--only SUBSTR`` filters
benchmarks by name.  Benchmarks with structured results (``pipeline_stall``)
additionally write ``BENCH_<name>.json`` next to the repo root — or into
``--json-dir`` — so the perf trajectory is recorded run over run; parity
failures inside a benchmark surface as ``ERROR`` rows (what CI gates on),
while timings stay advisory.

``pipeline_stall`` also emits a full telemetry stream into the same
directory (``TELEM_pipeline.jsonl`` + ``TRACE_pipeline.json``, see
``repro.obs``): point ``python -m repro.obs.report`` at the JSONL for the
throughput/stall/hit-rate story, or load the trace in Perfetto.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    from benchmarks import common
    from benchmarks.paper_figures import ALL_BENCHES
    from repro.utils import enable_compile_cache

    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=["host", "device"],
                    default=common.BATCH_BACKEND,
                    help="batch pipeline for the training benchmarks")
    ap.add_argument("--only", default="",
                    help="run only benchmarks whose name contains this")
    ap.add_argument("--bench", default="",
                    help="run exactly one benchmark by name (see ALL_BENCHES)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: shrink benchmark instances")
    ap.add_argument("--json-dir", default="",
                    help="directory for BENCH_*.json result files "
                         "(default: repo root)")
    args = ap.parse_args()
    common.BATCH_BACKEND = args.backend
    common.SMOKE = common.SMOKE or args.smoke
    if args.json_dir:
        common.BENCH_JSON_DIR = args.json_dir
    if args.bench and args.bench not in {n for n, _ in ALL_BENCHES}:
        raise SystemExit(f"unknown benchmark {args.bench!r}; choose from "
                         f"{sorted(n for n, _ in ALL_BENCHES)}")

    print("name,us_per_call,derived")
    for name, fn in ALL_BENCHES:
        if args.bench and name != args.bench:
            continue
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            rows = fn()
            dt_us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{dt_us:.0f},ok rows={len(rows)}")
            for rname, value, note in rows:
                v = f"{value:.6g}" if isinstance(value, float) else value
                print(f"{rname},{v},{note}")
        except Exception as e:  # keep the harness running
            dt_us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{dt_us:.0f},ERROR {type(e).__name__}: {e}")
    # roofline summary (reads dry-run artifacts if present)
    try:
        from benchmarks.roofline import summary_rows

        for rname, value, note in summary_rows():
            v = f"{value:.6g}" if isinstance(value, float) else value
            print(f"{rname},{v},{note}")
    except Exception as e:
        print(f"roofline,0,SKIPPED {e}")


if __name__ == "__main__":
    main()
