"""Observability layer (repro/obs): tracing must be free when off and
cheap when on, metrics must be bounded and exact-enough, and the online
quality monitors must agree with the offline gated metrics.

The two load-bearing guarantees pinned here:

* **disabled == absent** — with the tracer off, engine and runtime
  outputs are bit-identical to an uninstrumented run and no program
  recompiles (the observability layer cannot perturb what it watches);
* **enabled == warm** — with tracing + monitors on, a warmed runtime
  still serves with zero post-warmup compiles (probe programs are part
  of warmup's contract).
"""
import gc
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.common import merge_bench_json
from repro.core import GoldDiffEngine, make_schedule
from repro.core.sampler import plan_segment
from repro.core.plan import full_scan_costs, step_stage_costs
from repro.data import gmm
from repro.index import build_index
from repro.index.store import screening_recall
from repro.kernels import ops
from repro.launch.faults import FaultConfig, injected
from repro.launch.runtime import CircuitBreaker, RuntimeConfig, ServeRuntime
from repro.launch.serve import Request, ServeEngine
from repro.obs import (NULL_TRACER, MetricsRegistry, QualityMonitor, Tracer,
                       install_dispatch_tracing, set_tracer, tracer,
                       uninstall_dispatch_tracing)
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

SCH = make_schedule("ddpm_linear", 1000)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


@pytest.fixture(autouse=True)
def _no_obs_leak():
    """Tests must restore the null tracer and the dispatch seam."""
    yield
    assert tracer() is NULL_TRACER, "a test leaked an installed tracer"
    assert ops.dispatch_hook() is None, "a test leaked a dispatch hook"
    assert obs_trace._gc_hook not in gc.callbacks, "a test leaked the gc hook"


def _engine(**kw):
    return GoldDiffEngine(gmm(256, dim=8, seed=0), SCH, **kw)


# -- tracer ------------------------------------------------------------------

def test_null_tracer_is_default_and_noop():
    assert tracer() is NULL_TRACER and not NULL_TRACER.enabled
    with NULL_TRACER.span("x", a=1):
        NULL_TRACER.event("y")
    assert NULL_TRACER.events() == [] and NULL_TRACER.dropped == 0


def test_set_tracer_returns_previous_and_none_restores_null():
    tr = Tracer()
    prev = set_tracer(tr)
    assert prev is NULL_TRACER and tracer() is tr
    assert set_tracer(None) is tr
    assert tracer() is NULL_TRACER


def test_span_nesting_and_durations_under_fake_clock():
    tr = Tracer(clock=FakeClock())
    with tr.span("outer", t=400):
        tr.event("mark", rows=3)
        with tr.span("inner"):
            pass
    ev = tr.events()
    kinds = [(e["kind"], e["name"]) for e in ev]
    assert kinds == [("begin", "outer"), ("point", "mark"),
                     ("begin", "inner"), ("end", "inner"), ("end", "outer")]
    b_out, mark, b_in, e_in, e_out = ev
    assert [e["seq"] for e in ev] == list(range(5))
    assert b_out["parent"] == 0 and b_out["tags"] == {"t": 400}
    assert mark["span"] == b_out["span"]          # point inside outer
    assert b_in["parent"] == b_out["span"]        # nesting recorded
    assert e_in["span"] == b_in["span"] and e_out["span"] == b_out["span"]
    # fake clock ticks once per read: every duration is deterministic
    assert e_in["tags"]["dur"] > 0 and e_out["tags"]["dur"] > 0
    assert e_out["tags"]["dur"] > e_in["tags"]["dur"]


def test_span_set_adds_end_tags():
    tr = Tracer(clock=FakeClock())
    with tr.span("a", before=1) as sp:
        sp.set(after=2)
        sp.set(more=3)
    begin, end = tr.events()
    assert begin["tags"] == {"before": 1}
    assert end["tags"]["after"] == 2 and end["tags"]["more"] == 3
    assert end["tags"]["dur"] > 0
    with NULL_TRACER.span("b") as sp:             # the null span takes it
        sp.set(after=2)
    assert NULL_TRACER.events() == []


def test_gc_hook_only_while_an_enabled_tracer_is_current():
    hooked = lambda: obs_trace._gc_hook in gc.callbacks  # noqa: E731
    assert not hooked()
    tr = Tracer(capacity=1 << 12)
    prev = set_tracer(tr)
    try:
        assert hooked()
        set_tracer(tr)                            # idempotent
        assert gc.callbacks.count(obs_trace._gc_hook) == 1
        gc.collect()
        set_tracer(NULL_TRACER)                   # a disabled tracer: off
        assert not hooked()
        n = len(tr.events())
        gc.collect()
        assert len(tr.events()) == n              # nothing recorded while off
        set_tracer(tr)
        assert hooked()
    finally:
        set_tracer(prev)
    assert not hooked()
    spans = [e for e in tr.events() if e["name"] == "gc.collect"]
    begins = [e for e in spans if e["kind"] == "begin"]
    ends = [e for e in spans if e["kind"] == "end"]
    assert any(e["tags"]["generation"] == 2 for e in begins)
    assert len(begins) == len(ends)
    assert all({"dur", "collected", "uncollectable"} <= set(e["tags"])
               for e in ends)


def test_ring_buffer_wrap_keeps_latest_and_counts_drops():
    tr = Tracer(capacity=4, clock=FakeClock())
    for i in range(10):
        tr.event(f"e{i}")
    ev = tr.events()
    assert [e["name"] for e in ev] == ["e6", "e7", "e8", "e9"]
    assert [e["seq"] for e in ev] == [6, 7, 8, 9]  # globally monotone
    assert tr.dropped == 6
    tr.clear()
    assert tr.events() == [] and tr.dropped == 0


def test_dump_round_trips_json_lines(tmp_path):
    tr = Tracer(clock=FakeClock())
    with tr.span("a", key=(1, 2)):
        tr.event("b")
    p = tmp_path / "trace.jsonl"
    assert tr.dump(str(p)) == 3
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert [e["name"] for e in lines] == ["a", "b", "a"]
    assert all(set(e) == {"seq", "ts", "kind", "name", "span", "parent",
                          "tags"} for e in lines)


# -- metrics -----------------------------------------------------------------

def test_counter_gauge_basics_and_type_collisions():
    r = MetricsRegistry()
    c = r.counter("req_total", "requests")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match="negative"):
        c.inc(-1)
    g = r.gauge("depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5.0
    assert r.counter("req_total") is c            # idempotent constructor
    with pytest.raises(TypeError, match="already registered"):
        r.gauge("req_total")


def test_histogram_exact_small_then_reservoir_accurate():
    h = obs_metrics.Histogram("lat", reservoir=64, seed=3)
    small = [5.0, 1.0, 9.0, 3.0]
    for v in small:
        h.observe(v)
    # count <= reservoir: the sample IS the stream, quantiles exact
    assert h.quantile(0.5) == np.percentile(small, 50)
    assert h.quantile(1.0) == 9.0 and h.min == 1.0 and h.max == 9.0
    # long stream: bounded memory, quantiles near the exact percentiles
    stream = [obs_metrics._unit(11, i) * 100.0 for i in range(4000)]
    h2 = obs_metrics.Histogram("lat2", reservoir=256, seed=0)
    for v in stream:
        h2.observe(v)
    assert len(h2._sample) == 256 and h2.count == 4000
    assert abs(h2.quantile(0.5) - np.percentile(stream, 50)) < 10.0
    assert abs(h2.quantile(0.99) - np.percentile(stream, 99)) < 5.0
    cell = h2.cell()
    assert cell["count"] == 4000 and cell["p50"] == h2.quantile(0.5)


def test_registry_snapshot_and_prometheus_round_trip():
    r = MetricsRegistry()
    r.counter("a_total", "things").inc(4)
    r.gauge("b_depth").set(2.5)
    h = r.histogram("c_lat", "latency", reservoir=16)
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["a_total"] == {"type": "counter", "value": 4.0}
    assert snap["b_depth"]["value"] == 2.5
    assert snap["c_lat"]["count"] == 3 and snap["c_lat"]["p50"] == 2.0
    json.dumps(snap)                              # JSON-clean
    prom = r.prometheus()
    assert "# TYPE a_total counter\na_total 4" in prom
    assert "b_depth 2.5" in prom
    assert '# TYPE c_lat summary' in prom
    assert 'c_lat{quantile="0.5"} 2' in prom
    assert "c_lat_sum 6" in prom and "c_lat_count 3" in prom


def test_register_adopts_external_metric_last_wins():
    r = MetricsRegistry()
    old = obs_metrics.Histogram("serve_latency_seconds", reservoir=4)
    r.register(old)
    new = obs_metrics.Histogram("serve_latency_seconds", reservoir=4)
    r.register(new)
    assert r.histogram("serve_latency_seconds") is new


# -- dispatch-seam tracing ---------------------------------------------------

def test_dispatch_spans_carry_compile_tags_and_count_metrics():
    eng = _engine()
    x = jnp.ones((2, 8))
    tr = Tracer(capacity=1 << 12)
    reg = MetricsRegistry()
    prev = set_tracer(tr)
    hook = install_dispatch_tracing(tr, registry=reg)
    try:
        eng.denoise(x, 500)                       # cold: compiles
        n_cold = len(tr.events())
        eng.denoise(x, 500)                       # warm: cache hits
    finally:
        uninstall_dispatch_tracing(hook)
        set_tracer(prev)
    assert ops.dispatch_hook() is None
    spans = [e for e in tr.events() if e["kind"] == "begin"
             and e["name"].startswith("dispatch.")]
    assert spans, "dispatches must be spanned"
    cold = [e for e in spans if e["seq"] < n_cold]
    warm = [e for e in spans if e["seq"] >= n_cold]
    assert all(e["tags"]["compile"] for e in cold)
    assert warm and not any(e["tags"]["compile"] for e in warm)
    compiles = reg.snapshot()["golddiff_compiles_total"]["value"]
    assert compiles == len(cold) == eng._builds
    # fused="auto" (the default) routes this dense-strategy static step
    # through the single-pass fused program kind
    kinds = {e["name"].split(".", 1)[1] for e in spans}
    assert kinds == {"fused_step"}
    assert reg.snapshot()["golddiff_dispatch_total_fused_step"]["value"] == 2


def test_disabled_tracer_is_bit_identical_with_zero_recompiles():
    eng = _engine()
    x = jnp.linspace(-1.0, 1.0, 16).reshape(2, 8)
    ref = {t: np.asarray(eng.denoise(x, t)) for t in (800, 300)}
    b0 = eng._builds
    # enabled tracing must reuse the same compiled programs and produce
    # the same bits; back to disabled must again change nothing
    tr = Tracer(capacity=1 << 12)
    prev = set_tracer(tr)
    try:
        traced = {t: np.asarray(eng.denoise(x, t)) for t in (800, 300)}
    finally:
        set_tracer(prev)
    after = {t: np.asarray(eng.denoise(x, t)) for t in (800, 300)}
    for t in ref:
        np.testing.assert_array_equal(traced[t], ref[t])
        np.testing.assert_array_equal(after[t], ref[t])
    assert eng._builds == b0, "tracing must not change program cache keys"
    names = {e["name"] for e in tr.events()}
    # fused="auto" (the default) routes these dense-strategy steps
    # through the single-pass fused program; its stage is a named scope
    # inside the compiled program, not a host event
    assert "engine.fused_step" in names
    assert not any(n.startswith("stage.") for n in names)
    text = jax.jit(lambda v: eng._fused_body(v, 800)).lower(x).as_text(
        debug_info=True)
    assert "stage.fused_step/" in text


def _scopes(text: str) -> set:
    return {s for s in ("stage.screen", "stage.ivf_screen", "stage.rerank",
                        "stage.aggregate", "stage.fused_step")
            if f"{s}/" in text}


def test_served_plan_segment_carries_stage_scopes():
    """The lowered plan segment the runtime serves names every stage in
    its op metadata: a device trace attributes each operation to one."""
    eng = ServeEngine("gmm", {"n": 256, "dim": 8}, num_steps=4,
                      max_batch=2, fused=False)
    plan = ServeRuntime(eng).plans["primary"]
    for b in plan.buckets:
        seg = plan_segment(eng.denoiser.call_masked, eng.schedule, plan, b,
                           eng.clip_value)
        text = jax.jit(seg).lower(
            jax.ShapeDtypeStruct((2, 8), jnp.float32)).as_text(
                debug_info=True)
        assert _scopes(text) == {"stage.screen", "stage.rerank",
                                 "stage.aggregate"}


@pytest.mark.parametrize("path,want", [
    ("masked_exact", {"stage.screen", "stage.rerank", "stage.aggregate"}),
    ("masked_indexed", {"stage.ivf_screen", "stage.rerank",
                        "stage.aggregate"}),
    ("masked_fused", {"stage.fused_step"}),
    ("static_exact", {"stage.screen", "stage.rerank", "stage.aggregate"}),
    ("static_indexed", {"stage.ivf_screen", "stage.rerank",
                        "stage.aggregate"}),
    ("select_exact", {"stage.screen", "stage.rerank"}),
])
def test_every_step_path_labels_its_stages(path, want):
    ix = build_index(gmm(256, dim=8, seed=0), num_clusters=8)
    eng = _engine(fused=path.endswith("fused"),
                  **({"index": ix, "index_mode": "always"}
                     if "indexed" in path else {}))
    x = jax.ShapeDtypeStruct((2, 8), jnp.float32)
    if path.startswith("masked"):
        fn = lambda v: eng.denoise_masked(v, jnp.int32(400))  # noqa: E731
    elif path.startswith("static"):
        fn = lambda v: eng._denoise_body(v, 400)              # noqa: E731
    else:
        fn = lambda v: eng._select_ids_body(v, 400)           # noqa: E731
    assert _scopes(jax.jit(fn).lower(x).as_text(debug_info=True)) == want


def test_fault_events_land_on_the_trace_stream():
    eng = _engine()
    x = jnp.ones((4, 8))
    tr = Tracer(capacity=1 << 12)
    prev = set_tracer(tr)
    try:
        with injected(FaultConfig(seed=42, nan_rate=0.5)) as inj:
            for t in (900, 600, 300, 100):
                eng.denoise(x, t)
    finally:
        set_tracer(prev)
    fault_ev = [e for e in tr.events() if e["name"].startswith("fault.")]
    assert len(inj.events) >= 1
    assert len(fault_ev) == len(inj.events)
    for e, (kind, program, n) in zip(fault_ev, inj.events):
        assert e["name"] == f"fault.{kind}" and e["kind"] == "point"
        assert e["tags"]["program"] == program and e["tags"]["counter"] == n


# -- analytic stage costs ----------------------------------------------------

def test_stage_costs_cover_the_pipeline_and_are_positive():
    eng = _engine()
    costs = step_stage_costs(eng, 400, batch=4)
    assert set(costs) == {"screen", "rerank", "aggregate"}
    ix = build_index(gmm(256, dim=8, seed=0), num_clusters=8)
    eng_ix = _engine(index=ix, index_mode="always")
    costs_ix = step_stage_costs(eng_ix, 400, batch=4)
    assert set(costs_ix) == {"ivf_screen", "rerank", "aggregate"}
    fs = full_scan_costs(eng, batch=4)
    assert set(fs) == {"full_scan"}
    for table in (costs, costs_ix, fs):
        for stage, c in table.items():
            assert c["flops"] > 0 and c["bytes"] > 0, stage
            assert set(c) == {"flops", "bytes"}, stage
    # full scan reads every row for distances AND aggregation: it must
    # dominate the selection path's screen traffic
    assert fs["full_scan"]["bytes"] > costs["screen"]["bytes"]


# -- online quality monitors -------------------------------------------------

def test_recall_probe_matches_direct_screening_recall():
    store = gmm(256, dim=8, seed=0)
    eng = GoldDiffEngine(store, SCH, index=build_index(store, num_clusters=8),
                         index_mode="always")
    mon = QualityMonitor(eng, registry=MetricsRegistry(), probe_rows=2)
    t = 400
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.float32)
    rec = mon.probe_recall(x, t)
    # recompute from the engine's own screens, outside the monitor
    a, _ = eng.constants(t)
    q = jnp.asarray(np.asarray(x[:2], np.float32) / float(a))
    m_t, _ = eng.sizes(t)
    exact_ids = np.asarray(eng.coarse(q, m_t))
    pos, pd2 = eng.coarse_indexed(q, eng.padded_m(t), eng.nprobe(t))
    direct = screening_recall(pos, pd2, eng.index.perm, exact_ids)
    assert rec == pytest.approx(direct)
    assert 0.0 <= rec <= 1.0
    h = mon.health()
    assert h["n_recall_probes"] == 1
    assert h["screen_recall_last"] == pytest.approx(rec)


def test_probe_is_static_shape_and_warmup_precompiles():
    store = gmm(256, dim=8, seed=0)
    eng = GoldDiffEngine(store, SCH, index=build_index(store, num_clusters=8),
                         index_mode="always")
    mon = QualityMonitor(eng, registry=MetricsRegistry(), probe_rows=2)
    assert mon.warmup([400, 700]) == 2
    b0 = eng._builds
    x4 = jnp.ones((4, 8))
    x1 = jnp.ones((1, 8))                         # short wave: tiled up
    assert mon.probe_recall(x4, 400) is not None
    assert mon.probe_recall(x1, 700) is not None
    assert eng._builds == b0, "warmed probes must not compile"


def test_maybe_probe_sampling_is_deterministic_and_concentration_records():
    store = gmm(256, dim=8, seed=0)
    eng = GoldDiffEngine(store, SCH, index=build_index(store, num_clusters=8),
                         index_mode="always")
    x = jnp.ones((2, 8))

    def decisions():
        mon = QualityMonitor(eng, registry=MetricsRegistry(),
                             sample_rate=0.5, seed=7)
        return [mon.maybe_probe_recall(x, 400) is not None
                for _ in range(16)]

    d1 = decisions()
    assert d1 == decisions(), "probe sampling must be reproducible"
    assert any(d1) and not all(d1), "rate 0.5 should mix probes and skips"
    # concentration curve: analytic, recorded on every reported step
    mon = QualityMonitor(eng, registry=MetricsRegistry())
    for t in (900, 500, 100):
        mon.record_step(t)
    snap = mon.registry.snapshot()
    assert snap["golddiff_steps_total"]["value"] == 3
    assert snap["golddiff_subset_frac"]["count"] == 3
    occ = [snap[f"golddiff_occupancy_t{t}"]["value"] for t in (900, 500, 100)]
    assert all(0.0 < o <= 1.0 for o in occ)
    with pytest.raises(ValueError, match="sample_rate"):
        QualityMonitor(eng, registry=MetricsRegistry(), sample_rate=1.5)


# -- serving runtime integration ---------------------------------------------

@pytest.fixture(scope="module")
def serve_eng():
    return ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=6,
                       max_batch=4)


@pytest.fixture(scope="module")
def obs_rt(serve_eng):
    r = ServeRuntime(serve_eng, RuntimeConfig(latency_reservoir=4),
                     monitor=QualityMonitor(serve_eng.engine,
                                            registry=MetricsRegistry()),
                     registry=MetricsRegistry())
    r.warmup()
    return r


def test_request_lifecycle_reconstructable_from_trace(serve_eng, obs_rt):
    tr = Tracer(capacity=1 << 14)
    prev = set_tracer(tr)
    try:
        tk = [obs_rt.submit(Request(i, 2, seed=50 + i)) for i in range(2)]
        obs_rt.run_until_idle()
    finally:
        set_tracer(prev)
    assert all(t.status == "done" for t in tk)
    assert obs_rt.health()["compiles_post_warmup"] == 0, \
        "tracing+monitoring must not compile post-warmup"
    ev = tr.events()
    admits = [e for e in ev if e["name"] == "request.admit"]
    delivers = [e for e in ev if e["name"] == "request.deliver"]
    assert {e["tags"]["request"] for e in admits} == {0, 1}
    assert {e["tags"]["request"] for e in delivers} == {0, 1}
    assert all(e["tags"]["latency_s"] >= 0 for e in delivers)
    waves = [e for e in ev
             if e["name"] == "wave.segment" and e["kind"] == "begin"]
    assert waves and all("bucket" in e["tags"] and "cursor" in e["tags"]
                         for e in waves)
    # lifecycle ordering: admit precedes the first segment precedes deliver
    assert admits[0]["seq"] < waves[0]["seq"] < delivers[-1]["seq"]


def _span_tree(events):
    """{span id: (name, parent id)} of the closed spans, and the end
    events' ``dur`` and tags by span id."""
    begins = {e["span"]: e for e in events if e["kind"] == "begin"}
    ends = {e["span"]: e for e in events if e["kind"] == "end"}
    tree = {sid: (b["name"], b["parent"]) for sid, b in begins.items()
            if sid in ends}
    return tree, ends


def test_runtime_pump_spans_nest_under_a_fake_clock(serve_eng, obs_rt):
    tr = Tracer(capacity=1 << 14, clock=FakeClock())
    prev = set_tracer(tr)
    try:
        tk = [obs_rt.submit(Request(20 + i, 2, seed=70 + i))
              for i in range(3)]
        obs_rt.run_until_idle()
    finally:
        set_tracer(prev)
    assert all(t.status == "done" for t in tk)
    tree, ends = _span_tree(tr.events())
    kids: dict = {}
    for sid, (name, parent) in sorted(tree.items()):
        kids.setdefault(parent, []).append(name)
    pumps = [sid for sid, (n, _) in tree.items() if n == "runtime.pump"]
    ran = [sid for sid in pumps if ends[sid]["tags"]["ran"]]
    assert ran and all(tree[sid][1] == 0 for sid in pumps)
    for sid in ran:
        names = [n for n in kids[sid] if not n.startswith(("gc.", "dispatch"))]
        assert names == ["runtime.admit", "wave.segment", "runtime.post"]
    segs = [sid for sid, (n, _) in tree.items() if n == "wave.segment"]
    assert len(segs) == len(ran)
    for sid in segs:
        names = [n for n in kids[sid] if not n.startswith(("gc.", "dispatch"))]
        assert names == ["wave.h2d", "wave.execute", "wave.d2h",
                         "wave.guard"]
    # noise round trips sit inside admission: a new wave, or a join
    noise = [sid for sid, (n, _) in tree.items() if n == "runtime.noise"]
    assert noise and all(tree[tree[sid][1]][0] == "runtime.admit"
                         for sid in noise)
    admits = [ends[sid]["tags"] for sid, (n, _) in tree.items()
              if n == "runtime.admit"]
    assert sum(a["waves"] + a["joins"] for a in admits) == len(noise)
    copies = [ends[sid] for sid, (n, _) in tree.items()
              if n in ("wave.h2d", "wave.d2h")]
    begins = {e["span"]: e for e in tr.events() if e["kind"] == "begin"}
    assert all(begins[e["span"]]["tags"]["bytes"] > 0 for e in copies)


def test_traced_serving_is_bit_identical_to_untraced(serve_eng, obs_rt):
    req = Request(7, 3, seed=99)
    ref = serve_eng.serve([req])[0]
    tr = Tracer(capacity=1 << 14)
    prev = set_tracer(tr)
    try:
        t = obs_rt.submit(Request(7, 3, seed=99))
        obs_rt.run_until_idle()
    finally:
        set_tracer(prev)
    assert t.status == "done"
    np.testing.assert_array_equal(t.images, ref.images)


def test_health_merges_monitor_and_exports_metrics(serve_eng, obs_rt):
    t = obs_rt.submit(Request(3, 2, seed=5))
    obs_rt.run_until_idle()
    assert t.status == "done"
    h = obs_rt.health()
    for k in ("p50_ms", "p95_ms", "p99_ms", "latency_samples",
              "dwell_exec_s", "dwell_screen_s", "dwell_oom_s",
              "dwell_compile_s", "screen_recall_p50", "subset_frac_p50",
              "n_steps_observed"):
        assert k in h, k
    assert h["p99_ms"] >= h["p50_ms"] >= 0.0
    assert h["n_steps_observed"] > 0     # concentration recorded per step
    # bounded latency sample regardless of traffic (satellite: the
    # unbounded _latencies list is gone)
    assert not hasattr(obs_rt, "_latencies")
    assert len(obs_rt._lat_hist._sample) <= 4
    assert obs_rt._lat_hist.count == h["latency_samples"] >= 4
    snap = obs_rt.metrics_snapshot()
    assert snap["serve_latency_seconds"]["count"] == h["latency_samples"]
    assert snap["serve_completed_total"]["value"] == \
        obs_rt.counters["completed"]
    prom = obs_rt.prometheus()
    assert "serve_latency_seconds_count" in prom
    assert "serve_queue_depth" in prom


def test_breaker_dwell_time_accounting():
    br = CircuitBreaker(threshold=2, window_s=10.0, cooldown_s=5.0)
    assert br.dwell_s(0.0) == 0.0
    br.record_failure(0.0)
    assert br.state(0.5) == "closed" and br.dwell_s(3.0) == 0.0
    br.record_failure(1.0)                        # trips: opens at t=1
    assert br.state(2.0) == "open"
    assert br.dwell_s(4.0) == pytest.approx(3.0)  # in-progress episode
    br.record_success(3.0)                        # still open: ignored
    assert br.dwell_s(4.0) == pytest.approx(3.0)
    assert br.state(7.0) == "half_open"           # past cooldown
    br.record_success(7.0)                        # probe succeeds: closes
    assert br.state(8.0) == "closed"
    assert br.dwell_s(100.0) == pytest.approx(6.0)   # frozen once closed
    br.record_failure(20.0)
    br.record_failure(21.0)                       # second episode
    assert br.dwell_s(25.0) == pytest.approx(6.0 + 4.0)


# -- bench record merge ------------------------------------------------------

def test_merge_bench_json_group_ownership(tmp_path):
    p = str(tmp_path / "BENCH_x.json")
    merge_bench_json(p, {"static/a/t1": 1.0, "static/b/t1": 2.0})
    merge_bench_json(p, {"roofline/peak/peak_gflops": 9.0,
                         "obs/denoise/obs_base_us": 5.0})
    rec = json.load(open(p))
    assert set(rec) == {"static/a/t1", "static/b/t1",
                        "roofline/peak/peak_gflops",
                        "obs/denoise/obs_base_us"}
    # re-emitting a group replaces ONLY that group's cells
    merge_bench_json(p, {"static/a/t1": 3.0})
    rec = json.load(open(p))
    assert rec["static/a/t1"] == 3.0 and "static/b/t1" not in rec
    assert rec["roofline/peak/peak_gflops"] == 9.0
    # corrupt prior record: start fresh rather than crash
    (tmp_path / "BENCH_y.json").write_text("{broken")
    py = str(tmp_path / "BENCH_y.json")
    merge_bench_json(py, {"static/a/t1": 1.0})
    assert json.load(open(py)) == {"static/a/t1": 1.0}
