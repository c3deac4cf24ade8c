"""Benchmark entrypoint: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark cell) plus
per-table summaries.  ``--full`` runs the paper-scale variants (slow on
CPU); the default fast mode keeps the whole suite minutes-scale.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only table2_quality]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import (engine_speedup, fig3_sensitivity, fig6_hparams,
                        index_speedup, ingest, roofline, screen_speedup,
                        serve_latency, serve_resilience,
                        serve_throughput, sharded_speedup,
                        table1_complexity, table2_quality, table3_scale,
                        table4_edm, table5_orthogonality, table6_bias)
from repro.utils import enable_compile_cache

TABLES = {
    "table1_complexity": table1_complexity,
    "table2_quality": table2_quality,
    "table3_scale": table3_scale,
    "table4_edm": table4_edm,
    "table5_orthogonality": table5_orthogonality,
    "table6_bias": table6_bias,
    "fig3_sensitivity": fig3_sensitivity,
    "fig6_hparams": fig6_hparams,
    "roofline": roofline,
    "engine_speedup": engine_speedup,
    "index_speedup": index_speedup,
    "ingest": ingest,
    "screen_speedup": screen_speedup,
    "serve_latency": serve_latency,
    "serve_resilience": serve_resilience,
    "serve_throughput": serve_throughput,
    "sharded_speedup": sharded_speedup,
}


def _csv_cell(table: str, row: dict) -> str:
    keyish = [str(row.get(k)) for k in ("dataset", "method", "setting",
                                        "schedule", "weighting", "param",
                                        "value", "N", "n_sub", "t", "steps",
                                        "arch", "shape", "kind")
              if row.get(k) is not None]
    name = f"{table}/" + "/".join(keyish) if keyish else table
    us = row.get("time_per_step_s")
    us = f"{us * 1e6:.1f}" if isinstance(us, (int, float)) else ""
    derived = ";".join(f"{k}={v:.5g}" for k, v in row.items()
                       if isinstance(v, (int, float)) and not isinstance(v, bool)
                       and k not in ("time_per_step_s",))
    return f"{name},{us},{derived}"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, choices=list(TABLES) + [None])
    args = ap.parse_args()
    enable_compile_cache()

    failures = []
    for name, mod in TABLES.items():
        if args.only and name != args.only:
            continue
        t0 = time.time()
        try:
            rows, summary = mod.run(fast=not args.full)
            for r in rows:
                print(_csv_cell(name, r), flush=True)
            if hasattr(mod, "write_bench_json"):
                # machine-readable perf record (e.g. BENCH_engine.json)
                mod.write_bench_json(rows)
            print(f"# {name} summary: {summary}  ({time.time()-t0:.1f}s)",
                  flush=True)
        except Exception as e:
            failures.append((name, repr(e)))
            traceback.print_exc()
            print(f"# {name} FAILED: {e}", flush=True)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
