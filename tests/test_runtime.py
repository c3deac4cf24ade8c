"""Fault-tolerant serving runtime (repro/launch/runtime.py): admission
validation, deadlines at plan seams, retry/fallback, the degradation
ladder, and the zero-compile guarantee on every warmed rung."""
import math
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.launch.faults import FaultConfig, injected
from repro.launch.runtime import (CircuitBreaker, QueueFullError,
                                  RuntimeConfig, ServeRuntime,
                                  validate_request)
from repro.launch.serve import Request, ServeEngine

REPO = Path(__file__).resolve().parent.parent


class FakeClock:
    """Deterministic clock + sleep pair for deadline/backoff tests."""

    def __init__(self):
        self.t = 0.0
        self.slept = []

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.slept.append(s)
        self.t += s


@pytest.fixture(scope="module")
def eng():
    e = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=6, max_batch=4)
    return e


@pytest.fixture(scope="module")
def rt(eng):
    r = ServeRuntime(eng, RuntimeConfig(backoff_base_s=0.001,
                                        backoff_max_s=0.005,
                                        breaker_cooldown_s=0.2))
    r.warmup()
    return r


def _fresh(eng, **kw):
    """A fresh runtime sharing the module engine's warm program cache
    (its warmup is all cache hits)."""
    kw.setdefault("backoff_base_s", 0.001)
    kw.setdefault("backoff_max_s", 0.005)
    kw.setdefault("breaker_cooldown_s", 0.2)
    r = ServeRuntime(eng, RuntimeConfig(**kw))
    r.warmup()
    return r


# -- satellite 1: admission validation ---------------------------------------

def test_validate_request_rejects_bad_inputs():
    with pytest.raises(ValueError, match="num_images must be an int"):
        validate_request(Request(0, 2.5, seed=0), 8)
    with pytest.raises(ValueError, match="num_images must be an int"):
        validate_request(Request(0, True, seed=0), 8)
    with pytest.raises(ValueError, match=">= 1"):
        validate_request(Request(0, 0, seed=0), 8)
    with pytest.raises(ValueError, match=">= 1"):
        validate_request(Request(0, -3, seed=0), 8)
    with pytest.raises(ValueError, match="exceeds the per-request cap"):
        validate_request(Request(0, 9, seed=0), 8)
    with pytest.raises(ValueError, match="seed must be an int"):
        validate_request(Request(0, 1, seed=1.5), 8)
    with pytest.raises(ValueError, match="seed must be an int"):
        validate_request(Request(0, 1, seed=False), 8)
    with pytest.raises(ValueError, match="seed must be >= 0"):
        validate_request(Request(0, 1, seed=-1), 8)
    with pytest.raises(ValueError, match="deadline_s must be positive"):
        validate_request(Request(0, 1, seed=0, deadline_s=0.0), 8)
    validate_request(Request(0, 8, seed=0, deadline_s=1.0), 8)  # all valid
    validate_request(Request(0, np.int64(2), seed=np.int32(3)), 8)


def test_submit_validates_and_bounds_queue(eng, rt):
    with pytest.raises(ValueError):
        rt.submit(Request(0, 0, seed=1))
    with pytest.raises(ValueError):
        rt.submit(Request(0, eng.max_batch + 1, seed=1))
    small = ServeRuntime(eng, RuntimeConfig(max_queue=2))
    small.warmup()
    small.submit(Request(0, 1, seed=1))
    small.submit(Request(1, 1, seed=2))
    with pytest.raises(QueueFullError):
        small.submit(Request(2, 1, seed=3))
    small.run_until_idle()               # admission control, not data loss
    assert small.counters["completed"] == 2


def test_static_mode_engine_rejected():
    e = ServeEngine("cifar_like", {"n": 64}, base="pca", num_steps=3)
    assert e.mode == "static"
    with pytest.raises(ValueError, match="static"):
        ServeRuntime(e)


# -- clean path: parity + zero compiles --------------------------------------

def test_clean_path_matches_serve_bitwise_with_zero_compiles(eng, rt):
    reqs = [Request(0, 3, seed=7), Request(1, 1, seed=9)]
    b0 = eng.engine._builds
    tickets = [rt.submit(Request(r.request_id, r.num_images, seed=r.seed))
               for r in reqs]
    rt.run_until_idle()
    res = eng.serve(reqs)
    for t, r in zip(tickets, res):
        assert t.status == "done" and not t.degraded
        assert t.latency_s is not None and t.latency_s >= 0.0
        np.testing.assert_array_equal(t.images, r.images)
    assert eng.engine._builds == b0, "clean serving must not compile"
    assert rt.health()["compiles_post_warmup"] == 0


# -- deadlines ---------------------------------------------------------------

def test_deadline_expiry_in_queue_and_at_seams(eng):
    clk = FakeClock()
    r = _fresh(eng, clock=clk, sleep=clk.sleep, default_deadline_s=None)
    # (a) expires while still queued: never runs
    t_q = r.submit(Request(0, 1, seed=1, deadline_s=5.0))
    clk.t = 10.0
    r.run_until_idle()
    assert t_q.status == "expired" and t_q.images is None
    # (b) expires between segments: rows dropped at the seam, wave-mates
    # unaffected and bit-identical to serving alone (compaction proof)
    assert eng.plan.num_buckets >= 2, "test needs >= 2 plan segments"
    t_a = r.submit(Request(1, 1, seed=21))                    # no deadline
    t_b = r.submit(Request(2, 2, seed=22, deadline_s=5.0))
    assert r.pump()                      # segment 1 at t=10, both running
    clk.t = 20.0                         # b is now past its deadline
    r.run_until_idle()
    assert t_b.status == "expired" and t_b.images is None
    assert t_a.status == "done"
    assert r.counters["repacks"] >= 1    # 3 rows -> 1 row: smaller bucket
    alone = eng.serve([Request(1, 1, seed=21)])[0]
    np.testing.assert_allclose(t_a.images, alone.images, rtol=0, atol=1e-5)
    # (c) strict delivery-time check: completed => within deadline
    t_c = r.submit(Request(3, 1, seed=23, deadline_s=1000.0))
    r.pump()
    clk.t = 20.0 + 2000.0
    r.run_until_idle()
    assert t_c.status == "expired"
    h = r.health()
    assert h["deadline_miss_rate"] == pytest.approx(3 / 4)
    assert h["n_completed"] == 1


# -- failure handling / degradation ladder -----------------------------------

def test_nan_storm_finite_guard_and_exact_rung(eng):
    r = _fresh(eng, breaker_threshold=1)
    with injected(FaultConfig(seed=3, nan_rate=1.0)):
        t1 = r.submit(Request(0, 2, seed=31))
        r.run_until_idle()
        t2 = r.submit(Request(1, 2, seed=32))   # screen breaker now open
        r.run_until_idle()
    for t in (t1, t2):
        assert t.status == "done" and t.degraded
        assert np.isfinite(t.images).all(), "NaN crossed a seam"
    assert r.counters["finite_trips"] >= 1
    assert r.counters["gauss_segments"] >= 1
    assert r.counters["exact_waves"] >= 1       # ladder switched rungs


def test_transient_errors_retry_then_succeed(eng):
    r = _fresh(eng, max_retries=100)
    with injected(FaultConfig(seed=5, error_rate=0.6)) as inj:
        t = r.submit(Request(0, 3, seed=41))
        r.run_until_idle()
    assert t.status == "done" and np.isfinite(t.images).all()
    assert any(e[0] == "error" for e in inj.events)
    assert r.counters["retries"] >= 1


def test_retries_exhausted_falls_back_to_gaussian(eng):
    r = _fresh(eng, max_retries=2)
    with injected(FaultConfig(seed=6, error_rate=1.0)):
        t = r.submit(Request(0, 2, seed=51))
        r.run_until_idle()
    assert t.status == "done" and t.degraded
    assert np.isfinite(t.images).all()
    assert r.counters["gauss_segments"] >= 1


def test_gauss_program_takes_the_basis_as_an_argument():
    """The Gaussian rung's program closes over no array of the store:
    lowered for a 64-row store, its text holds no constant of D x r
    elements or more (the schedule's 1,001-entry tables are smaller)."""
    import re

    import jax
    import jax.numpy as jnp
    e = ServeEngine("gmm", {"n": 64, "dim": 64}, num_steps=4, max_batch=2)
    r = ServeRuntime(e)
    d = rank = 64
    text = r._gauss_program(2).lower(
        jnp.zeros((2, d)), jnp.ones((r._gauss_nts,), jnp.int32),
        jnp.int32(0), jnp.int32(1), *r._basis(0)).as_text()
    sizes = [math.prod(int(v) for v in dims.split("x")[:-1])
             for dims in re.findall(r"stablehlo.constant .*: tensor<([^>]*)>",
                                    text)]
    assert sizes and max(sizes) < d * rank, sorted(sizes)[-3:]


def test_gauss_rung_follows_hot_swap_with_no_new_build():
    """After a hot swap to a store of the same shapes with a shifted
    mean, the Gaussian rung serves the new store's statistics through
    the programs warmed for the first: nothing compiles.  The swap
    builds the new epoch's basis in a ``runtime.wiener_basis`` span."""
    import io
    import logging

    import jax

    from repro.core.dataset import make_store
    from repro.obs.trace import Tracer, set_tracer
    e = ServeEngine("gmm", {"n": 128, "dim": 16}, num_steps=4, max_batch=2)
    r = _fresh(e, max_retries=0)
    shift = 0.5

    def gauss_only(rid):
        with injected(FaultConfig(seed=9, error_rate=1.0)):
            t = r.submit(Request(rid, 2, seed=61))
            r.run_until_idle()
        assert t.status == "done" and t.degraded
        return np.asarray(t.images)

    before = gauss_only(0)
    builds = e.engine._builds
    shifted = make_store(e.store.X + shift, (16,), proxy_factor=1)
    log = io.StringIO()
    handler = logging.StreamHandler(log)
    logging.getLogger("jax").addHandler(handler)
    try:
        with jax.log_compiles(True):
            tr = Tracer()
            set_tracer(tr)
            try:
                epoch = r.hot_swap(shifted)
            finally:
                set_tracer(None)
            after = gauss_only(1)
    finally:
        logging.getLogger("jax").removeHandler(handler)
    spans = [ev["tags"] for ev in tr.events()
             if ev["name"] == "runtime.wiener_basis" and ev["kind"] == "begin"]
    assert spans == [{"dim": 16, "rank": 16, "epoch": epoch}], spans
    assert e.engine._builds == builds
    assert "Compiling" not in log.getvalue(), log.getvalue()[:500]
    assert r.counters["gauss_segments"] >= 2
    assert abs(float(np.mean(after - before)) - shift) < 0.1 * shift


def test_oom_splits_wave_and_halves_admission(eng):
    r = _fresh(eng, max_retries=1, breaker_threshold=1)
    with injected(FaultConfig(seed=7, oom_rate=0.7)):
        t1 = r.submit(Request(0, 2, seed=61))
        t2 = r.submit(Request(1, 2, seed=62))
        r.run_until_idle()
    for t in (t1, t2):
        assert t.status == "done" and np.isfinite(t.images).all()
    assert r.counters["oom_splits"] >= 1
    h = r.health()
    assert h["n_short_waves"] >= 1 or h["n_oom_splits"] >= 1


def test_recompile_storm_trips_compile_breaker_to_scan_mode(eng):
    r = _fresh(eng, breaker_threshold=1)
    b0 = eng.engine._builds
    with injected(FaultConfig(seed=8, evict_rate=1.0)):
        t1 = r.submit(Request(0, 2, seed=71))
        r.run_until_idle()
        t2 = r.submit(Request(1, 2, seed=72))   # compile breaker open
        r.run_until_idle()
    for t in (t1, t2):
        assert t.status == "done" and np.isfinite(t.images).all()
    assert eng.engine._builds > b0              # real rebuilds happened
    assert r.health()["compiles_post_warmup"] > 0
    assert r.counters["scan_waves"] >= 1        # plan -> scan rung


def test_breaker_state_machine():
    br = CircuitBreaker(threshold=2, window_s=10.0, cooldown_s=5.0)
    assert br.state(0.0) == "closed"
    br.record_failure(1.0)
    assert br.state(1.0) == "closed"            # below threshold
    br.record_failure(2.0)
    assert br.state(2.0) == "open" and br.is_open(2.0)
    assert br.state(7.5) == "half_open" and not br.is_open(7.5)
    br.record_success(7.5)                      # half-open probe passes
    assert br.state(7.5) == "closed"
    # failures outside the window don't accumulate
    br.record_failure(100.0)
    br.record_failure(120.0)
    assert br.state(120.0) == "closed"


def test_backoff_is_deterministic_and_bounded(eng):
    clk1, clk2 = FakeClock(), FakeClock()
    cfgs = dict(max_retries=3, backoff_base_s=0.01, backoff_max_s=0.04,
                jitter_frac=0.25, seed=123)
    r1 = _fresh(eng, clock=clk1, sleep=clk1.sleep, **cfgs)
    r2 = _fresh(eng, clock=clk2, sleep=clk2.sleep, **cfgs)
    for r in (r1, r2):
        with injected(FaultConfig(seed=9, error_rate=1.0)):
            r.submit(Request(0, 1, seed=81))
            r.run_until_idle()
    assert clk1.slept == clk2.slept and len(clk1.slept) >= 1
    for s, attempt in zip(clk1.slept, range(1, len(clk1.slept) + 1)):
        cap = min(0.04, 0.01 * 2 ** (attempt - 1)) * 1.25
        assert 0.0 <= s <= cap + 1e-12


# -- observability / lifecycle ----------------------------------------------

def test_health_snapshot_shape(rt):
    h = rt.health()
    for k in ("queue_depth", "inflight_waves", "breaker_exec",
              "breaker_screen", "breaker_oom", "breaker_compile",
              "degraded_scan_mode", "degraded_exact_screen",
              "degraded_reduced_batch", "compiles_post_warmup",
              "p50_ms", "p99_ms", "deadline_miss_rate", "n_completed",
              "n_expired", "n_retries", "n_finite_trips"):
        assert k in h, k
    assert h["queue_depth"] == 0 and h["inflight_waves"] == 0
    assert h["p99_ms"] >= h["p50_ms"] >= 0.0


def test_background_thread_serves(eng, rt):
    rt.start()
    try:
        t = rt.submit(Request(0, 2, seed=91))
        deadline = time.time() + 60.0
        while t.status not in ("done", "expired", "failed"):
            assert time.time() < deadline, "background loop stalled"
            time.sleep(0.01)
        assert t.status == "done" and np.isfinite(t.images).all()
    finally:
        rt.stop()
    rt.start()                           # restartable
    rt.stop()


def test_scan_mode_engine_runtime(eng):
    e = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=6, max_batch=4,
                    mode="scan")
    r = ServeRuntime(e, RuntimeConfig(backoff_base_s=0.001))
    r.warmup()
    t = r.submit(Request(0, 2, seed=5))
    r.run_until_idle()
    assert t.status == "done" and not t.degraded
    assert np.isfinite(t.images).all()


@pytest.mark.slow
def test_shard_dropout_on_emulated_mesh_subprocess():
    """Chaos on an emulated 8-device mesh: shard-dropout faults at the
    dispatch seam must retry to completion with finite images."""
    code = """
import jax
import numpy as np
from repro.launch.faults import FaultConfig, injected
from repro.launch.runtime import RuntimeConfig, ServeRuntime
from repro.launch.serve import Request, ServeEngine

assert len(jax.devices()) == 8, jax.devices()
mesh = jax.make_mesh((8,), ("data",))
eng = ServeEngine("gmm", {"n": 1003, "dim": 16}, num_steps=5,
                  max_batch=4, mesh=mesh)
rt = ServeRuntime(eng, RuntimeConfig(backoff_base_s=0.001,
                                     max_retries=50))
rt.warmup()
with injected(FaultConfig(seed=2, shard_drop_rate=0.3)) as inj:
    tickets = [rt.submit(Request(i, 2, seed=100 + i)) for i in range(3)]
    rt.run_until_idle()
assert any(e[0] == "shard_drop" for e in inj.events), inj.events
for t in tickets:
    assert t.status == "done", t.status
    assert np.isfinite(t.images).all()
print("OK retries=", rt.counters["retries"])
"""
    env = dict(os.environ,
               PYTHONPATH=str(REPO / "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, cwd=REPO, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "OK retries=" in r.stdout
