"""Sharded GoldDiffEngine == single-host engine (emulated 8-device mesh).

The mesh tests run in subprocesses: ``XLA_FLAGS=--xla_force_host_
platform_device_count=8`` must be set before jax initializes, and the
parent test process runs on the single real CPU device (conftest pins
JAX_PLATFORMS=cpu, which the children inherit — with libtpu installed
but no TPU attached, platform autodetection hangs in TPU client init).
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

# The exact-parity test below stays in tier-1 (one subprocess, like the
# existing distributed-retrieval test); the other mesh subprocess tests
# are slow-marked — CI's `mesh` job selects this file by path with no
# -m filter, so they all still run there on every push/PR.


def _run_child(code: str, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=str(REPO), env=env)
    assert "PASS" in r.stdout, r.stdout + r.stderr
    return r.stdout


_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
from repro.data import gmm

def relerr(a, b):
    return np.abs(np.asarray(a) - np.asarray(b)).max() / \
        (np.abs(np.asarray(b)).max() + 1e-9)

def overlap(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return np.mean([len(set(a[i]) & set(b[i])) / a.shape[1]
                    for i in range(a.shape[0])])
"""


def test_sharded_engine_exact_parity_subprocess():
    """Exact mode: denoise / denoise_masked / select / full_scan match
    the single-host engine to fp32 reduction order, on an uneven
    N % devices != 0 store."""
    code = _PRELUDE + r"""
mesh = jax.make_mesh((8,), ("data",))
store = gmm(1003, dim=16, seed=0)            # 1003 % 8 != 0: padded tail
sch = make_schedule("ddpm_linear", 1000)
ref = GoldDiffEngine(store, sch, GoldDiffConfig())
sh = GoldDiffEngine(store, sch, GoldDiffConfig(), mesh=mesh)
x0 = store.X[:4]
ok = True
for t in (100, 500, 900):
    eps = jax.random.normal(jax.random.PRNGKey(t), x0.shape)
    xt = sch.add_noise(x0, eps, t)
    e1 = relerr(sh.denoise(xt, t), ref.denoise(xt, t))
    e2 = relerr(sh.denoise_masked(xt, jnp.asarray(t)),
                ref.denoise_masked(xt, jnp.asarray(t)))
    e3 = relerr(sh.full_scan(xt, t), ref.full_scan(xt, t))
    ov = overlap(sh.select(xt, t), ref.select(xt, t))
    print("t", t, e1, e2, e3, ov)
    ok &= e1 < 1e-5 and e2 < 1e-5 and e3 < 1e-5 and ov == 1.0
print("PASS" if ok else "FAIL")
"""
    _run_child(code)


@pytest.mark.slow
def test_sharded_engine_indexed_parity_subprocess():
    """Indexed mode: the globally-partitioned index reproduces the
    single-host probe set exactly, so indexed sharded screening is an
    equality test too (static and masked paths, 4-way data axis of a
    (4, 2) data/model mesh)."""
    code = _PRELUDE + r"""
from repro.index import build_index

mesh = jax.make_mesh((4, 2), ("data", "model"))
store = gmm(2003, dim=16, num_modes=32, spread=0.05, seed=0)
sch = make_schedule("ddpm_linear", 1000)
ix = build_index(store, num_clusters=32)
ref = GoldDiffEngine(store, sch, GoldDiffConfig(), index=ix,
                     index_mode="always")
sh = GoldDiffEngine(store, sch, GoldDiffConfig(), index=ix,
                    index_mode="always", mesh=mesh)
x0 = store.X[:4]
ok = True
for t in (100, 500, 900):
    eps = jax.random.normal(jax.random.PRNGKey(t), x0.shape)
    xt = sch.add_noise(x0, eps, t)
    e1 = relerr(sh.denoise(xt, t), ref.denoise(xt, t))
    e2 = relerr(sh.denoise_masked(xt, jnp.asarray(t)),
                ref.denoise_masked(xt, jnp.asarray(t)))
    ov = overlap(sh.select(xt, t), ref.select(xt, t))
    print("t", t, e1, e2, ov)
    ok &= e1 < 1e-5 and e2 < 1e-5 and ov == 1.0
print("PASS" if ok else "FAIL")
"""
    _run_child(code)


@pytest.mark.slow
def test_two_stage_merge_equals_global_softmax_subprocess():
    """Regression: the two-stage top-k + LSE merge primitives equal a
    global top-k + softmax computed in fp32 on one host."""
    code = _PRELUDE + r"""
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.distributed.sharding import (crossshard_kth, lse_merge_mean,
                                        shard_map_compat)
from repro.kernels import ops

mesh = jax.make_mesh((8,), ("data",))
S, B, kloc, nloc, D, k = 8, 5, 6, 32, 12, 17
rng = np.random.default_rng(0)
neg = rng.standard_normal((S, B, kloc)).astype(np.float32)
X = rng.standard_normal((S, nloc, D)).astype(np.float32)
idx = rng.integers(0, nloc, (S, B, kloc)).astype(np.int32)
s2 = 0.37

def local(neg_sh, X_sh, idx_sh):
    neg_l, X_l, idx_l = neg_sh[0], X_sh[0], idx_sh[0]
    kth = crossshard_kth(neg_l, k, k, "data")
    lg = jnp.where(neg_l >= kth[:, None], neg_l / (2.0 * s2), -1e30)
    acc, m, l = ops.golden_partial_aggregate(X_l[:, None, :], idx_l, lg)
    return lse_merge_mean(acc, m, l, "data")

sp = P("data")
put = lambda a: jax.device_put(jnp.asarray(a), NamedSharding(mesh, sp))
out = np.asarray(shard_map_compat(local, mesh, (sp, sp, sp), P())(
    put(neg), put(X), put(idx)))

# single-host oracle: global top-k + softmax over the gathered rows
flat_neg = neg.transpose(1, 0, 2).reshape(B, S * kloc)
rows = np.stack([np.concatenate([X[s][idx[s, b]] for s in range(S)])
                 for b in range(B)])                      # [B, S*kloc, D]
ref = np.zeros((B, D), np.float32)
for b in range(B):
    top = np.argsort(-flat_neg[b])[:k]
    lg = flat_neg[b][top] / (2.0 * s2)
    w = np.exp(lg - lg.max()); w /= w.sum()
    ref[b] = w @ rows[b][top]
err = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
print("merge rel err", err)
print("PASS" if err < 1e-5 else "FAIL")
"""
    _run_child(code)


@pytest.mark.slow
def test_sharded_golddiff_wrapper_and_scan_subprocess():
    """GoldDiff(mesh=...) end-to-end: static steps and the scan-based
    masked sampler both run sharded and stay on-manifold."""
    code = _PRELUDE + r"""
from repro.core import GoldDiff, OptimalDenoiser, sample_scan

mesh = jax.make_mesh((8,), ("data",))
store = gmm(1024, dim=16, num_modes=8, spread=0.05, seed=0)
sch = make_schedule("ddpm_linear", 1000)
gd_ref = GoldDiff(OptimalDenoiser(store, sch), GoldDiffConfig())
gd_sh = GoldDiff(OptimalDenoiser(store, sch), GoldDiffConfig(), mesh=mesh)
xt = sch.add_noise(store.X[:4],
                   jax.random.normal(jax.random.PRNGKey(0), (4, 16)), 300)
ok = relerr(gd_sh(xt, 300), gd_ref(xt, 300)) < 1e-5
out = sample_scan(gd_sh.call_masked, sch, (8, 16), jax.random.PRNGKey(1),
                  num_steps=6)
ok &= bool(jnp.isfinite(out).all())
d = jnp.sqrt(jnp.min(jnp.sum((out[:, None] - store.X[None]) ** 2, -1), -1))
ok &= float(d.mean()) < 0.5
print("scan dist", float(d.mean()))
print("PASS" if ok else "FAIL")
"""
    _run_child(code)


def test_partition_windows_host():
    """Window partition: monotone cuts covering all windows, balanced
    row counts, robust to skewed window sizes and S > C."""
    from repro.index.shard import partition_windows
    rng = np.random.default_rng(3)
    for sizes in (rng.integers(1, 50, 37), np.array([1000, 1, 1, 1]),
                  np.array([5])):
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        for s in (1, 4, 8):
            cuts = partition_windows(offsets, s)
            assert cuts[0] == 0 and cuts[-1] == len(offsets) - 1
            assert (np.diff(cuts) >= 0).all()
            rows = np.diff(offsets[cuts])
            assert rows.sum() == offsets[-1]
            if len(sizes) >= s:
                # no shard exceeds an even share by more than one window
                assert rows.max() <= offsets[-1] / s + sizes.max()


def test_sharded_layout_single_device():
    """shard_layout on a 1-device mesh is a plain (padded) re-stack:
    ids/rows round-trip and padding carries +inf norms."""
    import jax
    from repro.data import gmm
    from repro.index import build_index
    from repro.index.shard import shard_layout

    mesh = jax.make_mesh((1,), ("data",))
    store = gmm(257, dim=8, seed=0)
    lay = shard_layout(store, mesh, "data")
    assert lay.n_loc == 257 and not lay.indexed
    np.testing.assert_array_equal(np.asarray(lay.ids)[0], np.arange(257))
    np.testing.assert_allclose(np.asarray(lay.X)[0], np.asarray(store.rows))

    ix = build_index(store, num_clusters=8)
    lay = shard_layout(store, mesh, "data", index=ix)
    assert lay.indexed and lay.w_max == ix.num_clusters
    perm = np.asarray(ix.perm)
    np.testing.assert_array_equal(np.asarray(lay.ids)[0], perm)
    np.testing.assert_allclose(np.asarray(lay.X)[0],
                               np.asarray(store.rows)[perm])
    np.testing.assert_array_equal(np.asarray(lay.offsets)[0],
                                  np.asarray(ix.offsets))
