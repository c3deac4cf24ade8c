"""Batched analytical-diffusion sampling engine (the paper's serving kind).

A request is (dataset/class, num_images, seed); ``ServeEngine`` batches
requests per wave and runs GoldDiff DDIM sampling.  Three execution
modes, picked by ``mode=`` (``"auto"`` default):

* ``"plan"`` — the default with the Optimal base: the trajectory runs
  through ``sample_plan`` over a ``repro.core.plan.TrajectoryPlan`` —
  chained per-bucket ``lax.scan`` segments whose masked bodies are
  padded only to their bucket's (m_cap, k_cap, nprobe_cap).  A few
  (typically 3-4) compiled programs per batch shape keep ~all of static
  mode's trajectory FLOP savings (the paper's Posterior Progressive
  Concentration), instead of masked mode's worst-case padding or
  static mode's program-per-timestep cold start.
* ``"scan"`` — PR 4's single masked program per batch shape, padded to
  (m_max, k_max) at every step.
* ``"static"`` — per-step static programs (patch-family bases need
  static patch sizes, so they always serve this way).

Batch sizes are bucketed to powers of two up to ``max_batch``: a wave
of 5 requests runs at batch 8 and the padding rows are sliced off, so
the whole serving surface is ``len(batch_buckets) x plan.num_buckets``
programs — all of which ``warmup()`` precompiles before traffic, and
none of which recompile afterwards (guarded in CI by the emulated-mesh
recompile test).

Every request owns its noise stream: row i of request r draws its
terminal noise from ``fold_in(PRNGKey(r.seed), i)``, so a request's
images do not depend on which wave co-batched it (regression-tested in
``tests/test_serve_plan.py``; the pre-plan engine seeded a whole wave
from its first request's seed).

Under a mesh the golden store is data-sharded through the engine's
shard_map pipeline (``GoldDiff(mesh=...)``) in every mode.

(Historical note: this class used to be called ``GoldDiffEngine``,
shadowing the unrelated execution engine ``core.engine.GoldDiffEngine``
— it is the *serving* layer on top of that engine.)

  PYTHONPATH=src python -m repro.launch.serve --dataset cifar_like \
      --n 4096 --requests 2 --batch 8 --buckets 4
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (GoldDiff, GoldDiffConfig, build_plan, make_schedule,
                        sample, sample_plan, sample_scan)
from repro.core.dataset import DatasetStore
from repro.core.denoisers import OptimalDenoiser, make_denoiser
from repro.core.schedules import sampling_timesteps
from repro.data import make_dataset
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils import enable_compile_cache


@dataclasses.dataclass
class Request:
    request_id: int
    num_images: int
    seed: int
    class_id: int | None = None
    # per-request deadline (seconds from submission), honored by the
    # fault-tolerant runtime (launch/runtime.py); ``ServeEngine.serve``
    # itself is a synchronous batch call and ignores it
    deadline_s: float | None = None


@dataclasses.dataclass
class Result:
    request_id: int
    images: np.ndarray
    latency_s: float


class ServeEngine:
    """Training-free generation service over a fixed dataset store.

    The synchronous batch layer of the serving stack (the async layer
    with admission control, deadlines, and continuous batching is
    :class:`repro.launch.runtime.ServeRuntime`, which wraps a warmed
    instance of this class).  Owns three serving-specific concerns:

    * **batch buckets** — request waves are padded to the next
      power-of-two batch size, so the set of compilable batch shapes
      is logarithmic in ``max_batch``.
    * **per-request noise streams** — every row draws its terminal
      noise from ``fold_in(PRNGKey(request.seed), row)``, making
      outputs bitwise independent of how requests are packed into
      waves (the property continuous batching relies on).
    * **AOT warmup** — ``warmup()`` precompiles every (batch bucket x
      plan bucket x plan variant) program, including the mixed-cursor
      ``plan_seg_mix`` variants, so serving any request mix afterward
      compiles nothing (CI-guarded).

    ``plan_threshold`` / ``max_buckets`` forward to
    :func:`repro.core.plan.build_plan`: lower thresholds give
    finer-grained plans — more seams for the runtime to admit/expire
    at, at the cost of more programs to warm (see docs/SERVING.md).

    ``fused`` forwards to the engine (``GoldDiffEngine(fused=...)``):
    with the fused single-pass step on, ``warmup()`` precompiles the
    *fused* program kinds — the static ``fused_step`` programs and the
    fused-body plan/scan segments — so zero post-warmup compiles holds
    unchanged (the program cache keys the fused kind; the CI recompile
    guard runs with ``fused=True``).
    """

    def __init__(self, dataset: str | DatasetStore,
                 dataset_kw: dict | None = None,
                 base: str = "optimal", schedule: str = "ddpm_linear",
                 num_steps: int = 10, gd_cfg: GoldDiffConfig | None = None,
                 max_batch: int = 16, mesh=None, mode: str = "auto",
                 plan_threshold: float = 0.15,
                 max_buckets: int | None = None,
                 clip_value: float | None = 3.0, index=None,
                 index_mode: str = "auto", fused: str | bool = "auto",
                 batch_axis: str | None = None):
        # a DatasetStore passes through directly — the store-lifecycle
        # path (repro.index.ingest) serves its capacity-padded view
        # without a synthetic-dataset detour
        self.store = (dataset if isinstance(dataset, DatasetStore)
                      else make_dataset(dataset, **(dataset_kw or {})))
        self.schedule = make_schedule(schedule, 1000)
        self.num_steps = num_steps
        self.max_batch = max_batch
        self.clip_value = clip_value
        base_den = make_denoiser(base, self.store, self.schedule)
        self.denoiser = GoldDiff(base_den, gd_cfg or GoldDiffConfig(),
                                 mesh=mesh, index=index,
                                 index_mode=index_mode, fused=fused,
                                 batch_axis=batch_axis)
        # pinned here so baseline subclasses may swap ``denoiser`` (e.g.
        # unwrap to the full-scan base) and keep the program cache
        self._engine = self.denoiser.engine
        if mode == "auto":
            mode = "plan" if self._scan_compatible() else "static"
        if mode not in ("plan", "scan", "static"):
            raise ValueError(f"unknown serve mode {mode!r}")
        if mode in ("plan", "scan") and not self._scan_compatible():
            raise ValueError(f"mode={mode!r} needs the masked (Optimal-"
                             f"base) denoiser body; base {base!r} serves "
                             f"mode='static' only")
        self.mode = mode
        self.plan = build_plan(self.engine, num_steps,
                               threshold=plan_threshold,
                               max_buckets=max_buckets) \
            if mode == "plan" else None

    @property
    def engine(self):
        """The compiled-program cache owner (``core.GoldDiffEngine``)."""
        return self._engine

    def _scan_compatible(self) -> bool:
        """Masked-body serving needs a GoldDiff over the Optimal base
        (patch bases require static per-step patch sizes)."""
        return (hasattr(self.denoiser, "call_masked")
                and isinstance(getattr(self.denoiser, "base", None),
                               OptimalDenoiser))

    # -- batch buckets -------------------------------------------------------
    def batch_buckets(self) -> list[int]:
        """Power-of-two batch sizes served, ascending (max_batch last
        even when it is not itself a power of two)."""
        out, b = [], 1
        while b < self.max_batch:
            out.append(b)
            b *= 2
        out.append(self.max_batch)
        return out

    def _bucket_for(self, n: int) -> int:
        """Smallest batch bucket holding ``n`` rows."""
        for b in self.batch_buckets():
            if b >= n:
                return b
        return self.max_batch

    # -- per-request noise streams ------------------------------------------
    def _row_keys(self, wave: list, bucket: int):
        """One PRNG key per batch row: ``fold_in(PRNGKey(r.seed),
        ofs + i)`` for row i of a request chunk starting at global row
        ``ofs``, so a request's noise stream never depends on its
        wave-mates, the wave it lands in, or how an oversized request
        was chunked; padding rows (sliced off) fold a fixed throwaway
        seed (0).  ``wave`` holds ``(request, ofs, n)`` triples.

        Derivation is one fused vmapped program per batch bucket (a
        warmed, bounded shape set) rather than per-row ``fold_in``
        dispatches — the hot path stays zero-dispatch-per-row AND
        zero-compile after warmup."""
        seeds, idx = [], []
        for r, ofs, n in wave:
            seeds += [r.seed] * n
            idx += list(range(ofs, ofs + n))
        npad = bucket - len(idx)
        seeds += [0] * npad
        idx += list(range(npad))
        fn = self.engine.program(
            ("serve_keys", bucket),
            lambda: jax.jit(jax.vmap(lambda s, i: jax.random.fold_in(
                jax.random.PRNGKey(s), i))))
        return fn(jnp.asarray(seeds, jnp.int32),
                  jnp.asarray(idx, jnp.int32))

    def _init_noise(self, keys):
        """Terminal noise x_T = b_T * eps, one independent eps row per
        key; compiled once per batch bucket."""
        ts = sampling_timesteps(self.schedule, self.num_steps)
        b_t0 = float(self.schedule.b[int(ts[0])])
        dim = self.store.dim

        def build():
            return jax.jit(lambda k: b_t0 * jax.vmap(
                lambda kk: jax.random.normal(kk, (dim,)))(k))

        fn = self.engine.program(("serve_init", keys.shape[0], dim), build)
        return fn(keys)

    # -- sampling ------------------------------------------------------------
    def _scan_program(self, shape: tuple, compile_only: bool = False):
        """The cached one-masked-program sampler for a batch shape.
        ``compile_only`` AOT-lowers it (warmup) instead of jitting for
        a first executing call."""
        rng = jax.random.PRNGKey(0)          # split-consumed only: x_init
        key = ("serve_scan", shape, self.num_steps,  # carries randomness
               None if self.clip_value is None else float(self.clip_value))

        def body(xi):
            return sample_scan(
                self.denoiser.call_masked, self.schedule, shape, rng,
                num_steps=self.num_steps, clip_value=self.clip_value,
                x_init=xi)

        def build():
            specs = ((jax.ShapeDtypeStruct(shape, jnp.float32),)
                     if compile_only else None)
            return self.engine.jitter(body, aot_specs=specs)

        return self.engine.program(key, build)

    def _sample_bucket(self, bucket: int, keys) -> np.ndarray:
        """Run one wave at a (padded) batch-bucket size."""
        x_init = self._init_noise(keys)
        shape = (bucket, self.store.dim)
        if self.mode == "plan":
            x = sample_plan(self.denoiser.call_masked, self.schedule, shape,
                            jax.random.PRNGKey(0), self.plan,
                            clip_value=self.clip_value, x_init=x_init,
                            program_cache=self.engine.program,
                            jitter=self.engine.jitter)
        elif self.mode == "scan":
            x = self._scan_program(shape)(x_init)
        else:                                # per-step static programs
            x = sample(self.denoiser, self.schedule, shape,
                       jax.random.PRNGKey(0), num_steps=self.num_steps,
                       clip_value=self.clip_value, x_init=x_init)
        return np.asarray(x).reshape((bucket,) + self.store.image_shape)

    def warmup(self) -> dict:
        """Precompile every (batch-bucket x shape-bucket) program before
        traffic; a warm engine never touches the compiler again
        (asserted by the CI recompile guard).  Returns compile stats.

        Plan/scan programs are AOT-compiled (``jit(...).lower(shape)
        .compile()``, side by side in the engine's
        ``parallel_compiles`` pool) — no trajectory executes, so warmup
        pays compile time only.  Static mode (and any mode under a mesh,
        where an AOT executable would pin input shardings) warms by
        running one trajectory per batch bucket instead."""
        n0 = len(self.engine._programs)
        t0 = time.time()
        aot = self.engine.mesh is None and self.mode in ("plan", "scan")
        if aot:
            # the samplers' key-schedule split runs tiny op-level
            # programs (threefry split/unstack) that AOT lowering never
            # exercises — flush them now so the first real wave is pure
            # execution
            _, _ = jax.random.split(jax.random.PRNGKey(0))
        with self.engine.parallel_compiles():
            for b in self.batch_buckets():
                keys = self._row_keys([], b)
                self._init_noise(keys)       # tiny per-bucket key program
                if not aot:
                    self._sample_bucket(b, keys)
                elif self.mode == "plan":
                    sample_plan(self.denoiser.call_masked, self.schedule,
                                (b, self.store.dim), jax.random.PRNGKey(0),
                                self.plan, clip_value=self.clip_value,
                                program_cache=self.engine.program,
                                compile_only=True, jitter=self.engine.jitter)
                else:
                    self._scan_program((b, self.store.dim),
                                       compile_only=True)
        return {"programs_compiled": len(self.engine._programs) - n0,
                "batch_buckets": self.batch_buckets(),
                "shape_buckets": (self.plan.num_buckets if self.plan
                                  else (1 if self.mode == "scan"
                                        else self.num_steps)),
                "warmup_s": time.time() - t0}

    def serve(self, requests: Iterable[Request]) -> list[Result]:
        """Greedy batching: requests are packed up to max_batch per wave,
        each wave padded up to its power-of-two batch bucket.  Oversized
        requests are chunked across as many waves as they need — every
        requested image is delivered, and each row's noise stream stays
        tied to ``(seed, global row index)``, so chunking never changes
        a request's images."""
        reqs = list(requests)
        chunks = []                              # (req index, ofs, n)
        for ri, r in enumerate(reqs):
            ofs = 0
            while True:
                n = min(r.num_images - ofs, self.max_batch)
                chunks.append((ri, ofs, n))
                ofs += n
                if ofs >= r.num_images:
                    break
        parts = [[] for _ in reqs]
        lat = [0.0 for _ in reqs]
        queue = chunks
        while queue:
            wave, used = [], 0
            while queue and used + queue[0][2] <= self.max_batch:
                c = queue.pop(0)
                wave.append(c)
                used += c[2]
            if used == 0:        # only zero-image chunks: nothing to run
                continue
            bucket = self._bucket_for(used)
            keys = self._row_keys([(reqs[ri], ofs, n)
                                   for ri, ofs, n in wave], bucket)
            t0 = time.time()
            imgs = self._sample_bucket(bucket, keys)[:used]
            dt = time.time() - t0
            at = 0
            for ri, ofs, n in wave:
                parts[ri].append(imgs[at: at + n])
                lat[ri] += dt
                at += n
        out: list[Result] = []
        for ri, r in enumerate(reqs):
            imgs = (np.concatenate(parts[ri]) if parts[ri] else
                    np.zeros((0,) + self.store.image_shape, np.float32))
            out.append(Result(r.request_id, imgs, lat[ri]))
        return out


def main(argv: list[str] | None = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cifar_like")
    ap.add_argument("--n", type=int, default=2048)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--base", default="optimal",
                    choices=["optimal", "pca", "kamb"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--plan", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="bucketed trajectory plan (default); --no-plan "
                         "falls back to the single worst-case-padded "
                         "masked program")
    ap.add_argument("--buckets", type=int, default=None,
                    help="force at most this many shape buckets (floor: "
                         "one per indexed/exact routing region; default: "
                         "greedy merge under --threshold)")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="max padded-FLOP overhead per bucket")
    ap.add_argument("--no-warmup", action="store_true",
                    help="skip precompiling the (batch x shape) buckets")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable tracing (engine spans, plan segments, "
                         "dispatch events) and dump the event log as "
                         "JSONL to PATH on exit")
    ap.add_argument("--metrics", action="store_true",
                    help="count dispatches/compiles per program kind and "
                         "print a Prometheus text snapshot on exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tracer = (obs_trace.Tracer(capacity=1 << 16) if args.trace_out
              else obs_trace.NULL_TRACER)
    if args.trace_out or args.metrics:
        obs_trace.set_tracer(tracer)
        obs_trace.install_dispatch_tracing(
            tracer, obs_metrics.REGISTRY if args.metrics else None)

    mode = "auto"
    if args.base == "optimal":
        mode = "plan" if args.plan else "scan"
    eng = ServeEngine(args.dataset, {"n": args.n}, base=args.base,
                      num_steps=args.steps, max_batch=args.batch,
                      mode=mode, plan_threshold=args.threshold,
                      max_buckets=args.buckets)
    if eng.plan is not None:
        print(eng.plan.describe())
    if not args.no_warmup:
        stats = eng.warmup()
        print(f"warmup: {stats['programs_compiled']} programs "
              f"(batch buckets {stats['batch_buckets']} x "
              f"{stats['shape_buckets']} shape buckets) "
              f"in {stats['warmup_s']:.2f}s")
    reqs = [Request(i, args.batch, seed=100 + i) for i in range(args.requests)]
    t0 = time.time()
    results = eng.serve(reqs)
    total = time.time() - t0
    finite = [bool(np.isfinite(r.images).all()) for r in results]
    for r, ok in zip(results, finite):
        print(f"request {r.request_id}: {r.images.shape} "
              f"batch-latency={r.latency_s:.2f}s finite={ok}")
    n_img = sum(r.images.shape[0] for r in results)
    print(f"served {n_img} images in {total:.2f}s "
          f"({total/max(n_img,1):.3f}s/image, {args.steps} steps)")
    if args.trace_out:
        tracer.dump(args.trace_out)
        print(f"trace: {len(tracer.events())} events "
              f"({tracer.dropped} dropped) -> {args.trace_out}")
    if args.metrics:
        print(obs_metrics.REGISTRY.prometheus(), end="")
    bad = [r.request_id for r, ok in zip(results, finite) if not ok]
    if bad:
        raise SystemExit(f"non-finite images in requests {bad}")


if __name__ == "__main__":
    main()
