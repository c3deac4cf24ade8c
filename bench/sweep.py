#!/usr/bin/env python3
"""Find the knee of a configuration once, on the chip, in one process.

  python3 bench/sweep.py --config cifar10-golddiff --seed 5 --seconds 10 \\
      --fractions 0.5,0.6,0.7,0.8,0.9,1.0

One set-up and one warmup; then the ``batch`` mix for ``--seconds``
measures capacity in requests/s, and the ``steady`` mix runs at each
fraction of that capacity for ``--seconds``.  Each rate prints one JSON
line: offered and completed requests/s, latency p50/p95/max from when
each request was due, requests still open at the close, and the
deadline misses.  The highest rate that completes what it is offered
with no growing backlog is the knee; the steady mix runs at 0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fractions", default="0.5,0.6,0.7,0.8,0.9,1.0")
    args = ap.parse_args(argv)

    import numpy as np
    from bench import harness

    harness.enable_compile_cache()
    cell = harness.load_cell(f"{args.config}.batch")
    harness.device_info(cell.chips)
    served = harness.build(cell, args.seed)
    steady = json.loads((ROOT / "bench" / "traffic" / "steady.json")
                        .read_text())
    steady["deadline_s"] = None          # measure latency, drop nothing

    def report(mix, tag):
        records, _ = harness.serve_window(served.rt, mix, args.seed,
                                          args.seconds)
        due = [r for r in records if r.due < args.seconds]
        done = [r for r in due if r.status == "done"
                and r.finished <= args.seconds]
        lat = [r.latency for r in due if r.latency is not None]
        line = {"mix": tag, "offered_per_s": len(due) / args.seconds,
                "completed_per_s": len(done) / args.seconds,
                "images_per_s": sum(r.n for r in done) / args.seconds,
                "latency_p50_s": float(np.quantile(lat, 0.5)),
                "latency_p95_s": float(np.quantile(lat, 0.95)),
                "latency_max_s": float(max(lat)),
                "open_at_close": sum(r.finished is None
                                     or r.finished > args.seconds
                                     for r in due),
                "not_done": sum(r.status != "done" for r in due)}
        print(json.dumps(line), flush=True)
        return line

    cap = report(cell.traffic, "batch")["completed_per_s"]
    served.rt.run_until_idle()
    for f in (float(x) for x in args.fractions.split(",")):
        report(dict(steady, rate_per_s=f * cap), f"steady x{f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
