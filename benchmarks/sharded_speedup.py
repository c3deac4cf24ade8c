"""Sharded GoldDiffEngine vs single host on an emulated 8-device mesh.

A CPU-only parity check: it re-executes itself pinned to the CPU and is
never a chip number (the chip's sharded path is ``chip_smoke.py
--chips 4``, in one process).  Wall-clock on an *emulated* mesh is not a
speedup claim — the eight XLA "devices" share one physical CPU and
every collective is a memcpy — so the timing cells here are recorded
**unpaired** (a trajectory to watch, not a gate).  What IS
gated (``scripts/check_bench.py``, >= 0.95 like every recall cell) is
**parity**: the sharded engine must keep producing the single-host
golden sets and denoised outputs —

* ``recall/sharded_parity/<kind>/...``        golden-set overlap of
  ``select()`` (sharded vs single host), exact and indexed modes;
* ``recall/sharded_parity/<kind>_masked/...`` masked-path output
  agreement, ``1 - min(1, rel_err / 1e-3)``: fp32-reduction-order
  differences (~1e-7) score ~1.0, a broken merge scores 0.

The mesh needs ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
before jax initializes, so ``run()`` re-executes this module as a child
process and parses one JSON line from its stdout:

  PYTHONPATH=src python -m benchmarks.sharded_speedup
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

BENCH_JSON = "BENCH_sharded.json"
MARK = "SHARDED_BENCH_JSON:"
T_BUCKETS = (900, 300, 100, 20)


def _child(fast: bool) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.common import time_call
    from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
    from repro.data import gmm
    from repro.index import build_index

    sch = make_schedule("ddpm_linear", 1000)
    mesh = jax.make_mesh((8,), ("data",))
    rows: list[dict] = []

    def overlap(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return float(np.mean([len(set(a[i]) & set(b[i])) / a.shape[1]
                              for i in range(a.shape[0])]))

    def bench(kind, store, n, batch, **eng_kw):
        ref = GoldDiffEngine(store, sch, GoldDiffConfig(), **eng_kw)
        sh = GoldDiffEngine(store, sch, GoldDiffConfig(), mesh=mesh,
                            **eng_kw)
        rng = jax.random.PRNGKey(0)
        x0 = store.X[:batch]
        for t in T_BUCKETS:
            eps = jax.random.normal(jax.random.fold_in(rng, t), x0.shape)
            x_t = jnp.asarray(sch.add_noise(x0, eps, t))
            t_one = time_call(lambda xx, tt=t: ref.denoise(xx, tt), x_t)
            t_sh = time_call(lambda xx, tt=t: sh.denoise(xx, tt), x_t)
            par = overlap(sh.select(x_t, t), ref.select(x_t, t))
            rows.append({"kind": kind, "method": "single_host", "N": n,
                         "t": t, "time_per_step_s": t_one})
            rows.append({"kind": kind, "method": "sharded8", "N": n, "t": t,
                         "time_per_step_s": t_sh, "recall": par,
                         "indexed": sh.use_index(t)})
        # masked (scan/pjit) path: one program, traced t
        t = T_BUCKETS[1]
        ta = jnp.asarray(t)
        f_ref = jax.jit(lambda xx, tt: ref.denoise_masked(xx, tt))
        f_sh = jax.jit(lambda xx, tt: sh.denoise_masked(xx, tt))
        x_t = jnp.asarray(sch.add_noise(
            x0, jax.random.normal(jax.random.fold_in(rng, 7), x0.shape), t))
        t_one = time_call(f_ref, x_t, ta)
        t_sh = time_call(f_sh, x_t, ta)
        r, s = np.asarray(f_ref(x_t, ta)), np.asarray(f_sh(x_t, ta))
        err = np.abs(s - r).max() / (np.abs(r).max() + 1e-9)
        rows.append({"kind": f"{kind}_masked", "method": "single_host",
                     "N": n, "t": t, "time_per_step_s": t_one})
        rows.append({"kind": f"{kind}_masked", "method": "sharded8", "N": n,
                     "t": t, "time_per_step_s": t_sh, "rel_err": float(err),
                     "recall": max(0.0, 1.0 - min(1.0, float(err) / 1e-3))})

    n_exact = 8192 if fast else 32768
    bench("exact", gmm(n_exact, dim=32, num_modes=64, spread=0.1, seed=0),
          n_exact, batch=16)
    n_ix = 8192 if fast else 32768
    store = gmm(n_ix, dim=32, num_modes=64, spread=0.1, seed=1)
    bench("indexed", store, n_ix, batch=16,
          index=build_index(store, num_clusters=128), index_mode="always")
    return rows


def run(fast: bool = True):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"   # TPU autodetect hangs without a TPU
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "benchmarks.sharded_speedup", "--emit-json"]
    if fast:
        cmd.append("--fast")
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800,
                       env=env)
    line = next((ln for ln in r.stdout.splitlines()
                 if ln.startswith(MARK)), None)
    if line is None:
        raise RuntimeError(f"sharded bench child failed:\n{r.stdout}\n"
                           f"{r.stderr}")
    rows = json.loads(line[len(MARK):])
    pars = [r_["recall"] for r_ in rows if "recall" in r_]
    summary = (f"sharded(8 emulated)-vs-single-host parity: min "
               f"{min(pars):.4f} over {len(pars)} cells (gated >= 0.95); "
               f"timings recorded unpaired (emulated mesh, no speedup "
               f"claim)")
    return rows, summary


def write_bench_json(rows, path: str = BENCH_JSON) -> None:
    """Timing cells (us_per_call, unpaired) + gated parity cells."""
    record = {}
    for r in rows:
        name = f"{r['kind']}/{r['method']}/N{r['N']}/t{r['t']}"
        record[name] = round(r["time_per_step_s"] * 1e6, 1)
        if "recall" in r:
            record[f"recall/sharded_parity/{r['kind']}/N{r['N']}/t{r['t']}"
                   ] = round(r["recall"], 4)
    with open(path, "w") as f:
        json.dump(record, f, indent=2, sort_keys=True)


def main():
    if "--emit-json" in sys.argv:
        rows = _child(fast="--fast" in sys.argv)
        print(MARK + json.dumps(rows))
        return
    rows, summary = run(fast="--full" not in sys.argv)
    for r in rows:
        print(r)
    write_bench_json(rows)
    print(f"# wrote {BENCH_JSON}")
    print(f"# {summary}")


if __name__ == "__main__":
    main()
