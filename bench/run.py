"""The benchmark's one command: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic and metrics are named in
BENCHMARK.json at the root of the checkout and live in files under bench/.
The last line of standard output is one JSON object; the numbers that
decide ``correct`` are the last lines of standard error.  The command
refuses to run without a TPU.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
sys.path.insert(0, BENCH)
# the TPU runtime's logs stay inside the checkout, not at a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", os.path.join(BENCH, ".logs"))
# JAX's persistent compile cache sits at one fixed place inside the checkout,
# whatever the environment names, so only a checkout's first run compiles
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(BENCH, ".jax_cache")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from benchlib import harness

    return harness.main(args, T0)


if __name__ == "__main__":
    sys.exit(main())
