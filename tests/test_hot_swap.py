"""Zero-downtime hot-swap: the engine's epoch machinery (operands as
arguments, not baked constants) and the serving runtime's swap protocol
(probe -> flip -> GC, in-flight waves pinned to their admission epoch).
The slow subprocess test is the acceptance guard: a mid-request swap
under ``jax.log_compiles`` with zero compiles and exactly-once
delivery (the CI chaos job runs it by file, so -m filters don't
apply).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
from repro.data import gmm
from repro.index import IngestConfig, StoreLifecycle, build_index
from repro.index.schedule import ProbeSchedule
from repro.launch.runtime import (EpochProbeError, RuntimeConfig,
                                  ServeRuntime)
from repro.launch.serve import Request, ServeEngine

REPO = Path(__file__).resolve().parent.parent


def grow(lc, b, seed):
    """Append ``b`` fresh rows and commit: the next epoch's view."""
    rows = np.random.default_rng(seed).normal(
        size=(b, lc.dim)).astype(np.float32)
    lc.append(rows)
    lc.commit()
    return lc.view()


@pytest.fixture(scope="module")
def swap_env(tmp_path_factory):
    root = tmp_path_factory.mktemp("swap_store")
    store = gmm(512, dim=16, seed=3)._replace(labels=None)
    index = build_index(store, num_clusters=8)
    lc = StoreLifecycle.create(str(root), store, index, IngestConfig())
    ds0, ix0 = lc.view()
    eng = GoldDiffEngine(ds0, make_schedule("ddpm_linear", 1000),
                         GoldDiffConfig(), index=ix0, index_mode="always",
                         probe_schedule=ProbeSchedule())
    return {"lc": lc, "eng": eng, "ds0": ds0, "ix0": ix0}


# -- engine-level epoch machinery ---------------------------------------------

def test_epoch_swap_sequence(swap_env):
    """The whole engine-side lifecycle in admission order: install a
    grown epoch, flip, serve it with ZERO new compiles, pin back to the
    old epoch bit-identically, then retire."""
    eng, lc = swap_env["eng"], swap_env["lc"]
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 16)).astype(np.float32))
    y0 = np.asarray(eng.denoise(x, 300))           # compiles once
    assert np.isfinite(y0).all()

    ds1, ix1 = grow(lc, 48, seed=42)
    builds = eng._builds
    eng.install_epoch(1, ds1, ix1)
    eng.set_serving_epoch(1)
    y1 = np.asarray(eng.denoise(x, 300))
    assert eng._builds == builds                    # zero-compile swap
    assert np.isfinite(y1).all()
    assert not np.array_equal(y0, y1)               # new rows are live

    with eng.at_epoch(0):                           # in-flight pinning
        y0_again = np.asarray(eng.denoise(x, 300))
    assert eng._builds == builds
    np.testing.assert_array_equal(y0, y0_again)

    with pytest.raises(ValueError, match="serving"):
        eng.retire_epoch(1)
    eng.retire_epoch(0)
    assert sorted(eng._epochs) == [1]
    with pytest.raises(KeyError):
        eng.set_serving_epoch(99)


def test_install_rejects_shape_mismatch(swap_env):
    eng = swap_env["eng"]
    other = gmm(256, dim=16, seed=9)._replace(labels=None)
    with pytest.raises(ValueError, match="cannot hot-swap"):
        eng.install_epoch(7, other, build_index(other, num_clusters=8))
    assert 7 not in eng._epochs


def test_swap_compat_reports_reasons(swap_env):
    eng, ds0 = swap_env["eng"], swap_env["ds0"]
    assert eng.swap_compat(ds0, swap_env["ix0"]) is None
    assert "indexed-ness" in eng.swap_compat(ds0, None)
    other_ix = build_index(swap_env["ds0"], num_clusters=4)
    assert "num_clusters" in eng.swap_compat(ds0, other_ix)


# -- runtime-level swap protocol ----------------------------------------------

@pytest.fixture(scope="module")
def serve_env(tmp_path_factory):
    root = tmp_path_factory.mktemp("swap_serve")
    store = gmm(512, dim=16, seed=3)._replace(labels=None)
    index = build_index(store, num_clusters=8)
    lc = StoreLifecycle.create(str(root), store, index, IngestConfig())
    ds, ix = lc.view()
    eng = ServeEngine(ds, num_steps=6, max_batch=4, index=ix,
                      index_mode="always")
    rt = ServeRuntime(eng, RuntimeConfig(backoff_base_s=0.001,
                                         backoff_max_s=0.005,
                                         breaker_cooldown_s=0.2))
    rt.warmup()
    return {"lc": lc, "rt": rt}


def _serve_one(rt, rid, seed):
    t = rt.submit(Request(rid, 1, seed=seed))
    rt.run_until_idle()
    assert t.status == "done"
    return np.asarray(t.images)


def test_runtime_hot_swap_zero_compiles(serve_env):
    rt, lc = serve_env["rt"], serve_env["lc"]
    y_pre = _serve_one(rt, 0, seed=5)
    before = rt.engine.serving_epoch
    ds, ix = grow(lc, 32, seed=50)
    epoch = rt.hot_swap(ds, ix)
    assert epoch == before + 1
    h = rt.health()
    assert h["serving_epoch"] == epoch
    assert h["epochs_resident"] == 1                # old epoch GC'd
    assert h["compiles_post_warmup"] == 0           # the headline number
    assert rt.counters["hot_swaps"] >= 1
    y_post = _serve_one(rt, 1, seed=5)
    assert np.isfinite(y_post).all()
    assert not np.array_equal(y_pre, y_post)        # new store is live
    assert rt.health()["compiles_post_warmup"] == 0


def test_inflight_wave_finishes_on_admission_epoch(serve_env):
    """A wave admitted before the swap completes on the OLD epoch:
    exactly-once delivery, bit-identical to a no-swap baseline."""
    rt, lc = serve_env["rt"], serve_env["lc"]
    assert rt.eng.plan.num_buckets >= 2             # multi-segment plan
    y_base = _serve_one(rt, 10, seed=77)            # no-swap baseline

    t = rt.submit(Request(11, 1, seed=77))
    assert rt.pump()                                # run exactly one seam
    assert t.status in ("queued", "running")        # still in flight
    ds, ix = grow(lc, 16, seed=60)
    rt.hot_swap(ds, ix)                             # swap mid-request
    rt.run_until_idle()
    assert t.status == "done"
    np.testing.assert_array_equal(np.asarray(t.images), y_base)
    assert rt.health()["compiles_post_warmup"] == 0
    assert rt.health()["epochs_resident"] == 1      # old epoch GC'd now

    y_new = _serve_one(rt, 12, seed=77)             # admitted post-swap
    assert not np.array_equal(y_new, y_base)


def test_probe_quarantines_poisoned_epoch(serve_env):
    """A candidate epoch that produces non-finite output NEVER becomes
    the serving epoch: the probe quarantines it and serving continues
    on the old store uninterrupted."""
    rt, lc = serve_env["rt"], serve_env["lc"]
    before = rt.engine.serving_epoch
    y_pre = _serve_one(rt, 20, seed=8)
    ds, ix = lc.view()
    poisoned = ds._replace(rows=jnp.full_like(ds.rows, jnp.nan))
    with pytest.raises(EpochProbeError):
        rt.hot_swap(poisoned, ix)
    assert rt.engine.serving_epoch == before        # flip never happened
    assert rt.counters["epoch_quarantined"] == 1
    assert rt.health()["epochs_resident"] == 1      # candidate retired
    y_post = _serve_one(rt, 21, seed=8)
    np.testing.assert_array_equal(y_pre, y_post)    # service undisturbed
    assert rt.health()["compiles_post_warmup"] == 0


def test_hot_swap_rejects_serving_epoch_id(serve_env):
    rt, lc = serve_env["rt"], serve_env["lc"]
    ds, ix = lc.view()
    with pytest.raises(ValueError, match="serving"):
        rt.hot_swap(ds, ix, epoch=rt.engine.serving_epoch)


@pytest.mark.slow
def test_seam_swap_log_compiles_guard_subprocess():
    """The acceptance guard: a hot-swap between a live wave's plan
    seams must be invisible to the compiler (jax.log_compiles captures
    NOTHING after warmup) and deliver every ticket exactly once."""
    code = r"""
import io, logging, tempfile
import jax, numpy as np
from repro.data import gmm
from repro.index import IngestConfig, StoreLifecycle, build_index
from repro.launch.runtime import RuntimeConfig, ServeRuntime
from repro.launch.serve import Request, ServeEngine

root = tempfile.mkdtemp(prefix="seam_swap_")
store = gmm(512, dim=16, seed=3)._replace(labels=None)
lc = StoreLifecycle.create(root, store, build_index(store, num_clusters=8),
                           IngestConfig())
ds, ix = lc.view()
eng = ServeEngine(ds, num_steps=6, max_batch=4, index=ix,
                  index_mode="always")
rt = ServeRuntime(eng, RuntimeConfig())
rt.warmup()

log = io.StringIO()
handler = logging.StreamHandler(log)
logging.getLogger("jax").addHandler(handler)
with jax.log_compiles(True):
    tickets = [rt.submit(Request(0, 2, seed=1)),
               rt.submit(Request(1, 1, seed=2))]
    rt.pump()                            # one seam on the old epoch
    lc.append(np.random.default_rng(0).normal(
        size=(32, 16)).astype(np.float32))
    lc.commit()
    rt.hot_swap(*lc.view())              # swap with waves in flight
    tickets.append(rt.submit(Request(2, 1, seed=3)))
    rt.run_until_idle()
logging.getLogger("jax").removeHandler(handler)

done = [t.status == "done" and np.isfinite(t.images).all()
        for t in tickets]
compiled = [ln for ln in log.getvalue().splitlines()
            if "Compiling" in ln and "jit(" in ln]
print("statuses:", [t.status for t in tickets])
print("post-warmup compiles:", compiled[:5])
print("health:", {k: rt.health()[k] for k in
                  ("serving_epoch", "epochs_resident",
                   "compiles_post_warmup")})
ok = (all(done) and not compiled
      and rt.health()["compiles_post_warmup"] == 0
      and rt.health()["serving_epoch"] == 1)
print("PASS" if ok else "FAIL")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, cwd=str(REPO), env=env)
    assert "PASS" in r.stdout, r.stdout + r.stderr
