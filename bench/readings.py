#!/usr/bin/env python3
"""Readings of the compared numbers over many seeds, in one process.

  python3 bench/readings.py --workload cifar10-golddiff.batch \\
      --variant program --seeds 11,12,13 --seconds 4

For each seed: the deployment built from that seed, a short window at
the cell's own load, and the comparison with the reference, as a run
makes it.  ``--variant`` is ``program`` (the lower reading: sound runs),
``control`` (the program's own bfloat16 storage, one precision below
the configuration's float32: the upper reading) or one of the faults
of ``bench/faults.py``.  One JSON line per seed.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import io
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", default="program")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)

    import jax.numpy as jnp
    from bench import harness
    from bench.faults import FAULTS

    harness.enable_compile_cache()
    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        served = harness.build(cell, seed, storage_dtype=(
            jnp.bfloat16 if args.variant == "control" else None))
        if args.variant in FAULTS:
            FAULTS[args.variant](served.rt, setattr)
        res = harness.run(cell, seed, args.seconds, False, served=served,
                          out=io.StringIO(), err=io.StringIO())
        del served
        print(json.dumps({"variant": args.variant, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "checks": res["checks"],
                          "seconds": time.time() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
