#!/usr/bin/env python3
"""Drive the GoldDiff serving path once on the chip and check the output.

One chip (no arguments): the CIFAR-10 deployment at its published shape
— 50,000 images of 32x32x3 generated from ``--seed``, the default
``GoldDiffConfig`` and 10 DDIM steps.  A plan-mode ``ServeEngine``
(``max_batch=8``) is warmed behind a continuous-batching
``ServeRuntime``, which serves a few requests with deadlines.  Checks:

* the device is a TPU;
* the served program calls the TPU kernels its backend selects;
* every ticket is done, none degraded, no breaker open, zero compiles
  after warmup, every image finite;
* at t in {900, 300, 20}, ``select`` and ``denoise`` on the platform's
  backend agree with the ``"xla"`` reference on the same device:
  golden-set overlap >= ``MIN_OVERLAP`` and relative error <=
  ``MAX_REL_ERR``.

Four chips (``--chips 4``): only the sharded path and what it is
compared with — the same store, exact and indexed, data-sharded over
``jax.make_mesh((4,), ("data",))``, checked for 4 shards of N/4 rows on
4 distinct devices and compared with the one-chip engine in the same
process (golden-set overlap of ``select``, relative error of
``denoise`` and ``denoise_masked``).

Every check prints one ``PASS``/``FAIL`` line; any failure exits 1.
Only a run in which every check passed prints, as its last line,
``{"ok": true, "device": {"platform", "kind", "count"}}``.

  python chip_smoke.py                # one chip
  python chip_smoke.py --chips 4      # four chips
  JAX_PLATFORMS=cpu python chip_smoke.py --n 2048   # CPU rehearsal:
      every phase runs at the small size, then the platform check fails

Without ``--n`` a run that finds no TPU stops at the platform check (a
full-size deployment is not run on a host CPU).  The persistent
compilation cache goes where ``repro.utils.enable_compile_cache`` says.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.common import gather_rows  # noqa: E402

CIFAR10_N = 50_000
STEPS = 10
MAX_BATCH = 8
TIMESTEPS = (900, 300, 20)
# Both backends compute fp32 at HIGHEST matmul precision; they differ
# only in summation order (~1e-6 relative), which can swap a member at
# a near-tie on the golden-set boundary.
MIN_OVERLAP = 0.99
MAX_REL_ERR = 1e-3
REQUESTS = (1, 3, 8, 5)           # images per request
DEADLINE_S = 600.0
# Pallas kernels that the served masked step calls on the pallas
# backends (their ``kernel_name`` in the lowered program)
STAGE_KERNELS = {"screen": "_pdist_kernel", "re-rank": "_sqdist_kernel",
                 "aggregate": "_sagg_kernel"}


class Checks:
    def __init__(self):
        self.failed: list[str] = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"{'PASS' if ok else 'FAIL'} {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)
        return ok


def _noisy(store, schedule, t: int, seed: int, batch: int = MAX_BATCH):
    """x_t = a_t x0 + b_t eps for ``batch`` store rows drawn from seed."""
    rows = np.random.default_rng(seed).choice(store.n, batch, replace=False)
    eps = jax.random.normal(jax.random.PRNGKey(seed + t),
                            (batch, store.dim), jnp.float32)
    return (float(schedule.a[t]) * gather_rows(store.rows, rows)
            + float(schedule.b[t]) * eps)


def _overlap(a, b) -> float:
    a, b = np.asarray(a), np.asarray(b)
    return float(np.mean([len(set(r) & set(s)) / len(r)
                          for r, s in zip(a, b)]))


def _rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _in_parallel(jobs):
    """Run thunks on a thread pool: their compiles overlap (XLA
    compiles outside the GIL)."""
    with ThreadPoolExecutor(min(8, len(jobs))) as pool:
        return list(pool.map(lambda f: f(), jobs))


def _compare(checks, pairs, x_ts, masked: bool = False):
    """For each ``(name, eng, ref)``: ``select`` / ``denoise`` (and
    ``denoise_masked``) of ``eng`` against ``ref`` at each (t, x_t).
    All programs of all pairs compile side by side."""
    def run(e, masked_fn, t, x):
        out = {"sel": e.select(x, t), "den": e.denoise(x, t)}
        if masked_fn is not None:
            out["msk"] = masked_fn(x, jnp.int32(t))
        return jax.tree.map(np.asarray, out)

    jobs = []
    for _, eng, ref in pairs:
        for e in (eng, ref):
            fn = (e.jitter(lambda x_, t_, e=e: e.denoise_masked(x_, t_))
                  if masked else None)
            jobs += [lambda e=e, fn=fn, t=t, x=x: run(e, fn, t, x)
                     for t, x in x_ts.items()]
    outs = iter(_in_parallel(jobs))
    for name, _, _ in pairs:
        got_all = [next(outs) for _ in x_ts]
        want_all = [next(outs) for _ in x_ts]
        for t, got, want in zip(x_ts, got_all, want_all):
            ov = _overlap(got["sel"], want["sel"])
            errs = {k: _rel_err(got[k], want[k]) for k in got
                    if k != "sel"}
            fin = all(np.isfinite(got[k]).all() for k in errs)
            checks.check(
                f"{name} t={t}",
                ov >= MIN_OVERLAP and fin
                and all(v <= MAX_REL_ERR for v in errs.values()),
                f"golden-set overlap {ov:.6f} (>= {MIN_OVERLAP}), rel err "
                + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
                + f" (<= {MAX_REL_ERR:g}), finite {fin}")


def _stage_report(checks, eng) -> None:
    """Which implementation each stage of the served masked step runs,
    read from the kernels its lowered program calls."""
    engine = eng.engine
    pb = eng.plan.buckets[0]
    t = int(eng.plan.ts[pb.start])
    spec = jax.ShapeDtypeStruct((MAX_BATCH, eng.store.dim), jnp.float32)
    text = engine.lower(
        lambda x: eng.denoiser.call_masked(x, jnp.int32(t), pb.caps),
        spec).as_text()
    found = set(re.findall(r'kernel_name = "(\w+)"', text))
    streamed = engine.use_stream(MAX_BATCH)
    fused = engine._fused_masked(bool(pb.caps.indexed))
    print(f"backend: {engine.backend}  strategy: {engine.strategy}  "
          f"fused: {fused}  screen: "
          f"{'streamed' if streamed else 'materialized'}")
    expect = set()
    for stage, kern in STAGE_KERNELS.items():
        if stage == "screen" and streamed:
            impl = "lax.scan streamed top-m (XLA)"
        elif kern in found:
            impl = f"Pallas kernel {kern}"
        else:
            impl = "XLA reference math"
        if engine.backend != "xla" and not (stage == "screen" and streamed):
            expect.add(kern)
        print(f"stage {stage}: {impl}")
    checks.check("stage kernels", expect <= found and
                 (engine.backend != "xla" or not found),
                 f"expected {sorted(expect)}, lowered program calls "
                 f"{sorted(found)}")


def one_chip(checks, store, seed: int) -> None:
    from repro.core.engine import GoldDiffEngine
    from repro.launch.runtime import RuntimeConfig, ServeRuntime
    from repro.launch.serve import Request, ServeEngine

    eng = ServeEngine(store, num_steps=STEPS, max_batch=MAX_BATCH,
                      mode="plan")
    print(eng.plan.describe())
    _stage_report(checks, eng)
    rt = ServeRuntime(eng, RuntimeConfig(continuous=True))
    t0 = time.perf_counter()
    stats = rt.warmup()
    print(f"warmup: {stats['programs_total']} programs in "
          f"{time.perf_counter() - t0:.1f} s (compile included)", flush=True)

    tickets = [rt.submit(Request(i, k, seed=seed * 1000 + i,
                                 deadline_s=DEADLINE_S))
               for i, k in enumerate(REQUESTS)]
    t0 = time.perf_counter()
    rt.run_until_idle()
    wall = time.perf_counter() - t0
    h = rt.health()
    for tk in tickets:
        r = tk.request
        print(f"request {r.request_id}: {r.num_images} images, status "
              f"{tk.status}, degraded {tk.degraded}, latency "
              f"{tk.latency_s} s")
    print(f"served {sum(REQUESTS)} images in {wall:.3f} s (host clock, "
          f"{STEPS} steps)")
    shape = (MAX_BATCH,) + tuple(store.image_shape)
    checks.check("served", all(tk.status == "done" for tk in tickets),
                 str([tk.status for tk in tickets]))
    checks.check("not degraded", not any(tk.degraded for tk in tickets),
                 str([tk.degraded for tk in tickets]))
    breakers = {k: v for k, v in h.items() if k.startswith("breaker_")}
    checks.check("breakers closed",
                 all(v == "closed" for v in breakers.values()),
                 str(breakers))
    checks.check("zero post-warmup compiles",
                 h["compiles_post_warmup"] == 0,
                 f"{h['compiles_post_warmup']} compiles")
    checks.check("images finite", all(
        tk.images is not None and tk.images.shape[1:] == shape[1:]
        and np.isfinite(tk.images).all() for tk in tickets),
        f"{len(tickets)} requests, image shape {shape[1:]}")

    ref = GoldDiffEngine(store, eng.schedule, eng.engine.cfg,
                         backend="xla")
    x_ts = {t: _noisy(store, eng.schedule, t, seed) for t in TIMESTEPS}
    _compare(checks, [(f"{eng.engine.backend} vs xla", eng.engine, ref)],
             x_ts)


def four_chips(checks, store, seed: int) -> None:
    from repro.core import GoldDiffConfig, make_schedule
    from repro.core.engine import GoldDiffEngine
    from repro.index import build_index

    mesh = jax.make_mesh((4,), ("data",))
    schedule = make_schedule("ddpm_linear", 1000)
    cfg = GoldDiffConfig()
    t0 = time.perf_counter()
    index = build_index(store)
    print(f"index: {index.num_clusters} clusters in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    x_ts = {t: _noisy(store, schedule, t, seed) for t in TIMESTEPS}
    pairs = []
    for mode, kw in (("exact", {}),
                     ("indexed", {"index": index, "index_mode": "always"})):
        one = GoldDiffEngine(store, schedule, cfg, **kw)
        sharded = GoldDiffEngine(store, schedule, cfg, mesh=mesh, **kw)
        pairs.append((f"{mode} sharded vs one chip", sharded, one))
        layout = sharded._layout
        for name in ("X", "proxy"):
            shards = getattr(layout, name).addressable_shards
            rows = [s.data.shape[1] for s in shards]
            devices = {s.device for s in shards}
            ok = len(shards) == 4 and len(devices) == 4 and (
                rows == [store.n // 4] * 4 if mode == "exact"
                else sum(rows) >= store.n)
            checks.check(f"{mode} layout {name}", ok,
                         f"{len(shards)} shards of {rows} rows on "
                         f"{len(devices)} devices")
    _compare(checks, pairs, x_ts, masked=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, default=None,
                    help=f"store rows (default {CIFAR10_N}, CIFAR-10's "
                         f"training set); a smaller N is a rehearsal")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.data import make_dataset
    from repro.utils import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"device: {device}", flush=True)
    checks = Checks()
    on_tpu = checks.check("platform", dev.platform == "tpu",
                          f"jax.devices()[0].platform == {dev.platform!r}")
    if not on_tpu and args.n is None:
        return 1
    if not checks.check("device count", len(devices) >= args.chips,
                        f"{len(devices)} visible, {args.chips} needed"):
        return 1

    n = args.n or CIFAR10_N
    t0 = time.perf_counter()
    store = make_dataset("cifar_like", n=n, seed=args.seed)
    jax.block_until_ready(store.rows)
    print(f"store: cifar_like {store.rows.shape} {store.rows.dtype} built in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)

    (one_chip if args.chips == 1 else four_chips)(checks, store, args.seed)

    for d in devices[:args.chips]:
        mem = d.memory_stats() or {}
        if "peak_bytes_in_use" in mem:
            print(f"peak HBM {d}: {mem['peak_bytes_in_use'] / 2**30:.3f} "
                  f"GiB of {mem.get('bytes_limit', 0) / 2**30:.3f} GiB")
        else:
            print(f"peak HBM {d}: not reported by the backend")
    if checks.failed:
        print(f"FAILED: {', '.join(checks.failed)}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
