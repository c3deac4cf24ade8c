"""Live roofline: per-stage achieved FLOP/s and byte/s vs measured peaks.

Each GoldDiff step is a fused coarse-screen -> rerank -> aggregate
program, so production never exposes per-stage wall-clock.  This
benchmark reconstructs the roofline honestly: it dispatches each stage
as a standalone compiled program built from the engine's OWN ops
(``engine.coarse`` / ``engine.coarse_indexed``, ``ops.golden_rerank``,
``ops.golden_support_aggregate``, ``ops.golden_aggregate``) on the
engine's own operands, times it warm, and divides by the *analytic*
costs from ``repro.core.plan.step_stage_costs`` — the cost model the
chip benchmark's stage rooflines also use (frozen in ``bench/costs.py``
and divided by the device time of each ``stage.*`` scope), so offline
roofline cells and chip traces speak one cost model.

Machine peaks are measured in-process the same way: a fat fp32 GEMM for
peak FLOP/s, a large streaming add for peak byte/s.  The analytic
traffic model is optimistic (perfect reuse), so every achieved cell
must land at or below its peak — ``scripts/check_bench.py`` gates
exactly that, plus the presence of all core stages (screen, rerank,
aggregate, full_scan, and the fused single-pass ``fused_step`` kind).

Also emits the **tracing-overhead gate**: a warm engine step timed with
the tracer disabled (``obs_base_us``) vs enabled (``obs_traced_us``);
check_bench's budget pair requires traced <= 1.03x base.

Cells merge into ``BENCH_engine.json`` (``roofline/...``, ``obs/...``)
without touching ``engine_speedup``'s cells:

  PYTHONPATH=src python -m benchmarks.roofline
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import merge_bench_json
from repro.core import (GoldDiff, GoldDiffConfig, OptimalDenoiser,
                        make_schedule, streaming)
from repro.core.plan import (full_scan_costs, fused_step_costs,
                             step_stage_costs)
from repro.data import mnist_like
from repro.index import build_index
from repro.kernels import ops
from repro.obs import trace as obs_trace

BENCH_JSON = "BENCH_engine.json"


def _best_time(fn, *args, repeats: int = 5, warmup: int = 2) -> float:
    """Min wall-clock seconds per call — the roofline estimator (the
    least-perturbed run is the one closest to the hardware's capability;
    medians admit scheduler noise into a gated ratio)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def measure_peaks(rng) -> tuple[float, float]:
    """(peak GFLOP/s, peak GB/s) measured in-process.

    GEMM for compute (2k^3 flops, compute-bound at k=1024); a streaming
    ``x + 1`` over 8 MB and 64 MB buffers for bandwidth, keeping the
    most favorable (so cache-resident stage operands cannot beat it).
    """
    k = 1024
    ka, kb = jax.random.split(rng)
    a = jax.random.normal(ka, (k, k), jnp.float32)
    b = jax.random.normal(kb, (k, k), jnp.float32)
    t_gemm = _best_time(jax.jit(lambda x, y: x @ y), a, b)
    peak_gflops = 2.0 * k ** 3 / t_gemm / 1e9
    peak_gbps = 0.0
    for mb in (8, 64):
        v = jnp.zeros((mb * (1 << 20) // 4,), jnp.float32)
        t_copy = _best_time(jax.jit(lambda x: x + 1.0), v)
        peak_gbps = max(peak_gbps, 2.0 * v.size * 4 / t_copy / 1e9)
    return peak_gflops, peak_gbps


def _stage_programs(eng, t: int, x) -> dict:
    """stage -> (fn, args): standalone compiled programs for each stage
    of the engine's step at ``t``, fed the engine's real operands (the
    rerank gets the screen's candidates, the aggregate gets the
    rerank's support + logits — same dataflow as the fused step)."""
    a, sig2 = eng.constants(t)
    q = x / a
    m_t, k_t = eng.sizes(t)
    stages = {}
    if eng.use_index(t):
        mp, npb = eng.padded_m(t), eng.nprobe(t)
        screen = jax.jit(lambda qq: eng.coarse_indexed(qq, mp, npb))
        pos, pd2 = jax.block_until_ready(screen(q))
        cand = eng.index.perm[pos]
        valid = jnp.isfinite(pd2)
        k_eff = min(k_t, mp)
        rerank = jax.jit(lambda qq, cc, vv: ops.golden_rerank(
            qq, eng.X, cc, k_eff, x_norms=eng.x_norms,
            backend=eng.backend, strategy="gather", valid=vv))
        idx, d2 = jax.block_until_ready(rerank(q, cand, valid))
        stages["ivf_screen"] = (screen, (q,))
        stages["rerank"] = (rerank, (q, cand, valid))
    else:
        screen = jax.jit(lambda qq: eng.coarse(qq, m_t))
        cand = jax.block_until_ready(screen(q))
        rerank = jax.jit(lambda qq, cc: ops.golden_rerank(
            qq, eng.X, cc, k_t, x_norms=eng.x_norms,
            backend=eng.backend, strategy=eng.strategy))
        idx, d2 = jax.block_until_ready(rerank(q, cand))
        stages["screen"] = (screen, (q,))
        stages["rerank"] = (rerank, (q, cand))
    lg = jnp.maximum(-d2 / (2.0 * sig2), streaming.NEG_INF)
    agg = jax.jit(lambda ii, ll: ops.golden_support_aggregate(
        eng.X, ii, ll, backend=eng.backend, strategy=eng.strategy_for(t)))
    stages["aggregate"] = (agg, (idx, lg))
    return stages


def _roofline_rows(kind: str, eng, t: int, x, costs: dict,
                   peak_gflops: float, peak_gbps: float,
                   stages: dict) -> list[dict]:
    n = eng.store.n
    rows = []
    for stage, (fn, args) in stages.items():
        c = costs[stage]
        dt = _best_time(fn, *args)
        gflops = c["flops"] / dt / 1e9
        gbps = c["bytes"] / dt / 1e9
        rows.append({
            "kind": kind, "stage": stage, "t": t, "N": n,
            "time_per_step_s": dt,
            "achieved_gflops": gflops, "achieved_gbps": gbps,
            "frac_peak_flops": gflops / peak_gflops,
            "frac_peak_bytes": gbps / peak_gbps,
            "bench": {
                f"roofline/{kind}/N{n}/t{t}/{stage}/achieved_gflops":
                    round(gflops, 4),
                f"roofline/{kind}/N{n}/t{t}/{stage}/achieved_gbps":
                    round(gbps, 4),
            },
        })
    return rows


def run(fast: bool = True):
    n, b = (4096, 32) if fast else (16384, 64)
    store = mnist_like(n, seed=0)
    sch = make_schedule("ddpm_linear", 1000)
    rng = jax.random.PRNGKey(0)
    peak_gflops, peak_gbps = measure_peaks(rng)
    rows = [{"kind": "peak", "stage": "machine", "N": n,
             "achieved_gflops": peak_gflops, "achieved_gbps": peak_gbps,
             "bench": {"roofline/peak/peak_gflops": round(peak_gflops, 4),
                       "roofline/peak/peak_gbps": round(peak_gbps, 4)}}]

    # exact-routing engine: screen / rerank / aggregate at a high- and a
    # low-noise step (the concentration schedule moves the FLOP split)
    gd = GoldDiff(OptimalDenoiser(store, sch), GoldDiffConfig(),
                  backend="xla")
    eng = gd.engine
    for t in (800, 100):
        x = float(sch.b[t]) * jax.random.normal(rng, (b, store.dim))
        costs = step_stage_costs(eng, t, batch=b)
        stages = _stage_programs(eng, t, x)
        rows += _roofline_rows("denoise", eng, t, x, costs,
                               peak_gflops, peak_gbps, stages)

    # indexed-routing engine: the ivf_screen stage (sublinear coarse)
    ix = build_index(store, num_clusters=64)
    gd_ix = GoldDiff(OptimalDenoiser(store, sch), GoldDiffConfig(),
                     backend="xla", index=ix, index_mode="always")
    t = 800
    x = float(sch.b[t]) * jax.random.normal(rng, (b, store.dim))
    costs = step_stage_costs(gd_ix.engine, t, batch=b)
    stages = _stage_programs(gd_ix.engine, t, x)
    rows += _roofline_rows("denoise_ivf", gd_ix.engine, t, x, costs,
                           peak_gflops, peak_gbps, stages)

    # full-scan baseline stage (Eq. 2): the bandwidth-bound wall
    t = 400
    a, sig2 = eng.constants(t)
    x = float(sch.b[t]) * jax.random.normal(rng, (b, store.dim))
    fs = jax.jit(lambda qq: ops.golden_aggregate(
        qq, eng.X, sig2, x_norms=eng.x_norms, backend=eng.backend))
    rows += _roofline_rows("full_scan", eng, t, x,
                           full_scan_costs(eng, batch=b),
                           peak_gflops, peak_gbps,
                           {"full_scan": (fs, (x / a,))})

    # fused single-pass step stage: the whole step is ONE program
    # (kernels/fused_step.py), costed by the read-each-operand-once
    # fused accounting.  Eliminating the staged path's [B, N]-shaped
    # aggregate work roughly halves bytes per step, so this cell should
    # sit closer to the rerank corner of the roof than the staged
    # screen/aggregate cells do.
    for t in (800, 100):
        x = float(sch.b[t]) * jax.random.normal(rng, (b, store.dim))
        fb = jax.jit(lambda xx, _t=t: eng._fused_body(xx, _t))
        rows += _roofline_rows("fused", eng, t, x,
                               fused_step_costs(eng, t, batch=b),
                               peak_gflops, peak_gbps,
                               {"fused_step": (fb, (x,))})

    # tracing-overhead gate: the same warm engine step, tracer off vs on
    # (the default engine fuses its dense-strategy steps, so this pair
    # re-gates the <= 1.03x budget with the fused path ON — the traced
    # span tags then carry the fused_step stage costs)
    t = 800
    x = float(sch.b[t]) * jax.random.normal(rng, (b, store.dim))
    t_base = _best_time(lambda: eng.denoise(x, t), repeats=10, warmup=3)
    tr = obs_trace.Tracer(capacity=1 << 15)
    prev = obs_trace.set_tracer(tr)
    try:
        t_traced = _best_time(lambda: eng.denoise(x, t),
                              repeats=10, warmup=3)
    finally:
        obs_trace.set_tracer(prev)
    rows.append({
        "kind": "obs_overhead", "stage": "denoise", "t": t, "N": n,
        "time_per_step_s": t_traced,
        "overhead_x": t_traced / t_base,
        "bench": {
            f"obs/denoise/N{n}/t{t}/obs_base_us": round(t_base * 1e6, 1),
            f"obs/denoise/N{n}/t{t}/obs_traced_us":
                round(t_traced * 1e6, 1),
        },
    })

    hot = [r for r in rows if r.get("stage") == "rerank"]
    summary = (f"peaks {peak_gflops:.0f} GFLOP/s / {peak_gbps:.1f} GB/s; "
               f"rerank frac-of-peak-flops "
               f"{max(r['frac_peak_flops'] for r in hot):.2f}; "
               f"tracing overhead {t_traced / t_base:.3f}x "
               f"(gate <= 1.03x)")
    return rows, summary


def write_bench_json(rows, path: str = BENCH_JSON) -> None:
    """Merge this table's ``roofline/...`` + ``obs/...`` cells into the
    shared record, preserving ``engine_speedup``'s cells."""
    cells: dict = {}
    for r in rows:
        cells.update(r.get("bench", {}))
    merge_bench_json(path, cells)


def main():
    rows, summary = run(fast=True)
    for r in rows:
        print({k: v for k, v in r.items() if k != "bench"})
    write_bench_json(rows)
    print(f"# wrote {BENCH_JSON}")
    print(f"# {summary}")


if __name__ == "__main__":
    main()
