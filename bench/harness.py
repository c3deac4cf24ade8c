"""Run one cell: set-up, the measured window, correctness, metrics.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration (``bench/configs/<config>.json``: the deployment) and a
traffic mix (``bench/traffic/<mix>.json``).  Every metric is a reader
of its own, ``bench/metrics/<metric>.py``, found by the metric's name.

Set-up is the deployment's cold start: the store made on the device
from the seed, ``ServeEngine(store, mode="plan")``, a continuous
``ServeRuntime`` over it and the runtime's public ``warmup()``.  The
window is a single-threaded client loop, the body of the runtime's own
``start()`` loop: submit every request that is due, ``rt.pump()``, and
when nothing could run, sleep until the next request is due.  Its calls
sit inside ``jax.profiler.TraceAnnotation`` spans ("submit", "pump",
"wait"), so that a traced run can say what the host was doing in each
idle gap of the device.  After the window the delivered images of a
sample of the completed requests are compared with the plain fp32
reference (``bench/reference.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

from bench import costs, datagen, schedule, traffic as traffic_mod
from bench import tracereduce
from bench.reference import Reference

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = ROOT / ".bench_out"
CACHE_MIN_COMPILE_S = 10.0
DRAIN_S = 60.0                 # how long an open loop waits past the close
TRACER_CAPACITY = 1 << 20
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- the cell, as BENCHMARK.json and its files state it ----------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def reader(metric: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- process-wide JAX settings -----------------------------------------------
def enable_compile_cache(path: Path = CACHE_DIR) -> None:
    """JAX's persistent cache at a fixed path in the checkout, with no
    cap on its size (a machine may set one that evicts every entry).
    Programs that compile in under ``CACHE_MIN_COMPILE_S`` are left out:
    on this path those are the tiny key and noise programs and the
    Gaussian-fallback segments, which bake the seed's Wiener basis into
    the executable, so their key changes with every seed and caching
    them would only write ~1 GB a run."""
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      CACHE_MIN_COMPILE_S)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def cache_entries(path: Path = CACHE_DIR) -> int:
    return sum(1 for _ in path.glob("*-cache")) if path.is_dir() else 0


def device_info(chips: int, require_tpu: bool = True) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and (dev.platform != "tpu" or len(devices) < chips):
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX found {len(devices)} "
            f"{dev.platform} device(s) ({dev.device_kind})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


class CompileCounter:
    """Counts backend compiles and persistent-cache loads while armed."""

    _instance = None

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _dur(self, event, duration, **kw):
        if self.armed and event == COMPILE_EVENTS[0]:
            self.count += 1

    def _event(self, event, **kw):
        if self.armed and event == COMPILE_EVENTS[1]:
            self.count += 1


# -- set-up ------------------------------------------------------------------
@dataclasses.dataclass
class Served:
    store: object
    eng: object
    rt: object
    warmup: dict


def build(cell: Cell, seed: int, storage_dtype=None) -> Served:
    """The deployment's cold start, from the seed.  ``storage_dtype``
    switches on the program's low-precision storage (the control)."""
    from repro.core import GoldDiffConfig
    from repro.core.dataset import make_store
    from repro.launch import serve as serve_mod
    from repro.launch.runtime import RuntimeConfig, ServeRuntime

    cfg = cell.config
    X, labels = datagen.generate(cfg["dataset"], seed)
    jax.block_until_ready(X)
    store = make_store(X, tuple(cfg["dataset"]["image_shape"]),
                       labels=labels,
                       proxy_factor=int(cfg["golddiff"]["proxy_factor"]))
    gd = cfg["golddiff"]
    gd_cfg = GoldDiffConfig(
        m_min_frac=gd["m_min_frac"], m_max_frac=gd["m_max_frac"],
        k_min_frac=gd["k_min_frac"], k_max_frac=gd["k_max_frac"],
        proxy_factor=int(gd["proxy_factor"]))
    sv, smp = cfg["serving"], cfg["sampling"]
    kw = dict(schedule=smp["schedule"], num_steps=int(smp["num_steps"]),
              gd_cfg=gd_cfg, max_batch=int(sv["max_batch"]),
              mode=sv["mode"], clip_value=float(smp["clip"]))
    if storage_dtype is None:
        eng = serve_mod.ServeEngine(store, **kw)
    else:
        real = serve_mod.GoldDiff
        serve_mod.GoldDiff = lambda *a, **k: real(
            *a, storage_dtype=storage_dtype, **k)
        try:
            eng = serve_mod.ServeEngine(store, **kw)
        finally:
            serve_mod.GoldDiff = real
    rt = ServeRuntime(eng, RuntimeConfig(
        continuous=True, max_queue=int(cell.traffic["max_queue"])))
    stats = rt.warmup()
    return Served(store, eng, rt, stats)


# -- the measured window -----------------------------------------------------
@dataclasses.dataclass
class Record:
    rid: int
    n: int
    seed: int
    due: float                  # seconds from the window's start
    submitted: float
    finished: float | None = None
    status: str = "queued"      # done | expired | failed | rejected | open
    images: np.ndarray | None = None

    @property
    def latency(self) -> float | None:
        return None if self.finished is None else self.finished - self.due


def serve_window(rt, traffic: dict, seed: int, seconds: float,
                 clock=time.monotonic) -> tuple[list[Record], float]:
    """Drive ``rt`` with the mix for ``seconds``; returns the records of
    every request sent and the window's start on ``clock``.  An open
    loop then waits up to ``DRAIN_S`` for the requests due in the
    window; a closed loop stops at the close."""
    from repro.launch.runtime import QueueFullError
    from repro.launch.serve import Request

    closed = traffic["loop"] == "closed"
    deadline = traffic.get("deadline_s")
    stream = traffic_mod.Stream(traffic, seed)
    note = jax.profiler.TraceAnnotation
    records: list[Record] = []
    inflight: dict[int, tuple] = {}
    with note("window"):
        t0 = clock()
        if closed:
            pending = collections.deque(
                stream.next(0.0, client=c)
                for c in range(int(traffic["clients"])))
        else:
            pending = collections.deque(
                traffic_mod.open_schedule(traffic, seed, seconds))
        t_end, t_give_up = t0 + seconds, t0 + seconds + DRAIN_S
        while True:
            now = clock()
            if closed and now >= t_end:
                break
            if not closed and ((not pending and not inflight)
                               or now >= t_give_up):
                break
            with note("submit"):
                while pending and t0 + pending[0].due <= now:
                    p = pending.popleft()
                    rec = Record(p.rid, p.n, p.seed, p.due, clock() - t0)
                    records.append(rec)
                    try:
                        tk = rt.submit(Request(p.rid, p.n, seed=p.seed,
                                               deadline_s=deadline))
                    except QueueFullError:
                        rec.status = "rejected"
                        continue
                    inflight[p.rid] = (p, rec, tk)
            with note("pump"):
                ran = rt.pump()
            now = clock()
            for rid, (p, rec, tk) in list(inflight.items()):
                if tk.status not in ("done", "expired", "failed"):
                    continue
                del inflight[rid]
                rec.status = tk.status
                if tk.status == "done":
                    rec.finished = tk.submitted_at + tk.latency_s - t0
                    rec.images = tk.images
                else:
                    rec.finished = now - t0
                if closed and now < t_end:
                    pending.append(stream.next(rec.finished, p.client))
            if not ran:
                with note("wait"):
                    nxt = (t0 + pending[0].due if pending
                           else now + rt.cfg.idle_sleep_s)
                    time.sleep(max(0.0, min(nxt, t_give_up) - clock()))
    for p, rec, tk in inflight.values():
        rec.status = "open"
    return records, t0


# -- correctness ---------------------------------------------------------------
def sample_requests(records: list[Record], seed: int,
                    images: int) -> list[Record]:
    """Completed requests drawn from the seed until ``images`` images,
    the largest request first among them."""
    done = [r for r in records if r.status == "done"]
    if not done:
        return []
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                 0xC0FFEE])
    order = [done[i] for i in rng.permutation(len(done))]
    order.sort(key=lambda r: -r.n)
    out, total = [order[0]], order[0].n
    rest = order[1:]
    for r in (rest[i] for i in rng.permutation(len(rest))):
        if total >= images:
            break
        out.append(r)
        total += r.n
    return out


def compare(config: dict, X, sample: list[Record]) -> dict:
    """Relative L2 error of every sampled image against the reference."""
    ref = Reference(config, X)
    want = ref.sample([(r.seed, r.n) for r in sample])
    got = np.concatenate([r.images.reshape(r.n, -1) for r in sample])
    err = (np.linalg.norm(got.astype(np.float64) - want, axis=1)
           / np.maximum(np.linalg.norm(want.astype(np.float64), axis=1),
                        1e-30))
    return {"images": int(err.size), "rel_err": err}


def checks(config: dict, errs: dict, compiles: int,
           runtime_compiles: int) -> dict:
    """Each compared number beside its limit (``correctness.limits``
    in the configuration)."""
    lim = config["correctness"]["limits"]
    e = errs["rel_err"]
    out = {"compiles_in_window": {"value": compiles, "limit": 0},
           "runtime_compiles": {"value": runtime_compiles, "limit": 0}}
    if e.size:
        out["rel_err_p90"] = {"value": float(np.quantile(e, 0.9)),
                              "limit": lim["rel_err_p90"]}
    out["images_compared"] = {"value": int(e.size),
                              "limit": int(config["correctness"]
                                           ["min_images"])}
    return out


def passed(chk: dict) -> bool:
    ok = all(c["value"] <= c["limit"] for k, c in chk.items()
             if k != "images_compared")
    return ok and "rel_err_p90" in chk and (
        chk["images_compared"]["value"] >= chk["images_compared"]["limit"])


# -- one run -----------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    seconds: float
    setup_s: float
    window_s: float
    records: list[Record]
    segments: list[dict]            # wave.segment spans (traced run)
    trace: tracereduce.Reduced | None
    peak: dict | None               # the chip's peaks (traced run)
    steps: list                     # the trajectory's steps and sizes


def _segments(tracer) -> list[dict]:
    begins = {}
    out = []
    for e in tracer.events():
        if e["name"] != "wave.segment":
            continue
        if e["kind"] == "begin":
            begins[e["span"]] = e
        elif e["kind"] == "end" and e["span"] in begins:
            tags = dict(begins.pop(e["span"])["tags"])
            tags["dur"] = e["tags"]["dur"]
            out.append(tags)
    return out


def _stat(xs, q):
    return float(np.quantile(xs, q)) if len(xs) else None


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float | None = None, require_tpu: bool = True,
        served: Served | None = None, out=sys.stdout,
        err=sys.stderr) -> dict:
    """One run of ``cell``; returns the result line's object.  A test
    may hand in a deployment it built (and broke) itself: ``served``
    (set-up then counts as nothing)."""
    from repro.obs import trace as obs_trace

    t_start = time.time() if t_start is None else t_start
    device = device_info(cell.chips, require_tpu)
    if served is None:
        before = cache_entries()
        served = build(cell, seed)
        written = cache_entries() - before
        print(f"setup: {time.time() - t_start:.3f} s "
              f"({served.warmup['programs_total']} programs; compile "
              f"cache {before} entries before, {written} written: "
              f"{'first, compiling run' if written else 'warm'})",
              file=out, flush=True)
    setup_s = time.time() - t_start
    rt = served.rt
    counter = CompileCounter.get()
    tracer = None
    trace_dir = OUT_DIR / "trace" / cell.name
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = obs_trace.Tracer(capacity=TRACER_CAPACITY)
        obs_trace.set_tracer(tracer)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only, no call tracing
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    builds0 = rt.health()["compiles_post_warmup"]
    counter.count, counter.armed = 0, True
    try:
        records, _ = serve_window(rt, cell.traffic, seed, seconds)
    finally:
        counter.armed = False
        if trace:
            jax.profiler.stop_trace()
            obs_trace.set_tracer(None)
    runtime_compiles = rt.health()["compiles_post_warmup"] - builds0
    stats = [d.memory_stats() or {} for d in jax.devices()[:cell.chips]]
    device["memory_peak_bytes"] = max(int(s.get("peak_bytes_in_use", 0))
                                      for s in stats)
    segments = []
    if tracer is not None:
        if tracer.dropped:
            raise RuntimeError(f"tracer dropped {tracer.dropped} events")
        segments = _segments(tracer)
    X = served.store.X
    del rt
    served = None                    # free the program before the reference
    gc.collect()

    in_window = [r for r in records if r.due < seconds]
    late = [r.submitted - r.due for r in in_window
            if r.status != "rejected"]
    bad = [r for r in in_window if r.status in ("expired", "failed",
                                                "rejected")]
    if cell.traffic["loop"] == "open":
        bad += [r for r in in_window if r.status == "open"]
    done = [r for r in in_window if r.status == "done"]
    print(f"requests: sent {len(in_window)}, succeeded {len(done)}, "
          f"failed {len(bad)}, open at the close "
          f"{sum(r.status == 'open' for r in in_window)}", file=out)
    lat = [r for r in in_window if r.status == "done"]
    print(f"latency samples: {len(lat)} delivered, {len(bad)} missing; "
          f"generator lateness: median "
          f"{_stat(late, 0.5)} s, max {max(late) if late else None} s",
          file=out, flush=True)

    sample = sample_requests(records, seed,
                             int(cell.config["correctness"]["sample_images"]))
    errs = compare(cell.config, X, sample) if sample else \
        {"images": 0, "rel_err": np.zeros(0)}
    chk = checks(cell.config, errs, counter.count, runtime_compiles)

    reduced, peak = None, None
    if trace:
        reduced = tracereduce.reduce(tracereduce.find_trace(trace_dir))
        device["busy_s"] = reduced.busy_s()
        device["window_s"] = reduced.window_s
        if device["platform"] == "tpu":
            peak = costs.peaks(device["kind"])
    rec = Run(cell, float(seconds), setup_s, float(seconds), records,
              segments, reduced, peak, schedule.steps(cell.config))
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": passed(chk), "attempted": len(in_window),
              "failed": len(bad), "metrics": metrics, "device": device}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced.top_ops(),
                               "idle_gaps": reduced.idle_gaps()}
    result["checks"] = chk
    for k, c in chk.items():
        rel = ">=" if k == "images_compared" else "<="
        print(f"check {k}: {c['value']} (limit {rel} {c['limit']})",
              file=err)
    err.flush()
    return result
