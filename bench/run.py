#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

  python3 bench/run.py --workload cifar10-golddiff.batch --seed 7 \\
      --seconds 20 --trace 0

Runs on the machine it is started on and exits non-zero, printing no
result, when JAX finds no TPU or fewer chips than the cell asks for.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from the profiler trace
and the runtime's spans of the same window.  The last line of standard
output is the result as one JSON object; the numbers that decide
``correct`` are the last lines of standard error and the result's last
key, ``checks``.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
