"""Streamed one-pass screening == lax.top_k semantics, on every backend.

Property tests for ``ops.screen_topm`` / ``kernels.screen`` (tied
distances, ``m >= N`` edge cases, ragged tile remainders) plus
regressions pinning that routing the engine's coarse stage, masked
path, full scan, and sharded screen through the streamed form leaves
every output unchanged.

Integer-valued inputs make the distance arithmetic exact in fp32, so
the streamed result must equal the materialized oracle BIT-FOR-BIT
including tie order (carry-first merge == lax.top_k's lowest-index-wins
rule).  Float inputs get tolerance on distances (XLA blocks GEMMs
differently per shape, so last-ulp wiggle is expected) and exact
candidate-set equality away from ties.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container lacks hypothesis
    from _hypothesis_fallback import given, settings, st

from repro.core import (GoldDiff, GoldDiffConfig, GoldDiffEngine,
                        OptimalDenoiser, make_schedule)
from repro.data import gmm
from repro.kernels import ops, ref

SCH = make_schedule("ddpm_linear", 1000)

BACKENDS = ["xla", "pallas_interpret"]
if any(d.platform == "tpu" for d in jax.devices()):
    BACKENDS.append("pallas")


def _int_data(key, b, n, d, lo=-4, hi=5):
    kq, kx = jax.random.split(jax.random.PRNGKey(key))
    q = jax.random.randint(kq, (b, d), lo, hi).astype(jnp.float32)
    x = jax.random.randint(kx, (n, d), lo, hi).astype(jnp.float32)
    return q, x


def _assert_matches_oracle(q, x, m, backend, **kw):
    ri, rd = ref.screen_topm_ref(q, x, m)
    si, sd = ops.screen_topm(q, x, m, backend=backend, **kw)
    # distances equal everywhere (+inf marks the same surplus slots)...
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(rd))
    # ...and indices equal on every real slot, including tie order
    fin = np.isfinite(np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(si)[fin], np.asarray(ri)[fin])
    # surplus (m > N) slots stay gather-safe: in-range indices
    assert np.asarray(si).min() >= 0
    assert np.asarray(si).max() < x.shape[0]


@settings(max_examples=10)
@given(st.integers(0, 10 ** 6), st.integers(1, 400), st.integers(1, 450),
       st.integers(4, 200))
def test_screen_topm_property(seed, n, m, tile):
    """Streamed == materialized oracle for arbitrary (n, m, tile) —
    small integer coordinates force MANY exact distance ties; m may
    exceed n."""
    q, x = _int_data(seed, 3, n, 8)
    for backend in BACKENDS:
        _assert_matches_oracle(q, x, m, backend, tile=tile)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("n,m,tile", [
    (1000, 64, 256),     # plain streaming
    (1000, 64, 1024),    # single tile covers everything
    (100, 100, 32),      # m == N
    (50, 80, 16),        # m > N: surplus slots +inf, clamped indices
    (4097, 7, 512),      # ragged final tile
    (16, 1, 8),          # m == 1
])
def test_screen_topm_shapes(backend, n, m, tile):
    q, x = _int_data(7, 5, n, 16)
    _assert_matches_oracle(q, x, m, backend, tile=tile)


@pytest.mark.parametrize("n,m,tile", [
    (1000, 64, 128),     # many tiles, deep merge tree
    (1000, 64, 250),     # ragged final tile, odd level-0 count
    (999, 30, 100),      # odd tile count at every tree level
    (1200, 1500, 256),   # m > N: surplus slots survive the tree
])
def test_screen_topm_hier_matches_oracle(n, m, tile):
    """The opt-in two-level hierarchical merge (per-tile top-m + tree
    reduce) is bit-identical to the oracle AND to the default carry,
    including lowest-index tie order (integer data forces ties)."""
    from repro.kernels.screen import screen_topm_scan
    q, x = _int_data(11, 4, n, 8)
    ri, rd = ref.screen_topm_ref(q, x, m)
    hi_, hd = screen_topm_scan(q, x, m, tile=tile, hier=True)
    ci, cd = screen_topm_scan(q, x, m, tile=tile)
    np.testing.assert_array_equal(np.asarray(hd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(hd), np.asarray(cd))
    fin = np.isfinite(np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(hi_)[fin], np.asarray(ri)[fin])
    np.testing.assert_array_equal(np.asarray(hi_)[fin], np.asarray(ci)[fin])
    assert np.asarray(hi_).min() >= 0 and np.asarray(hi_).max() < n


@pytest.mark.parametrize("backend", BACKENDS)
def test_screen_topm_all_tied(backend):
    """Fully degenerate store (every distance identical): the streamed
    selection must reproduce lax.top_k's lowest-index-first order."""
    x = jnp.ones((40, 4))
    q = jnp.zeros((2, 4))
    ri, rd = ref.screen_topm_ref(q, x, 12)
    si, sd = ops.screen_topm(q, x, 12, backend=backend, tile=8)
    np.testing.assert_array_equal(np.asarray(si), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(sd), np.asarray(rd))


def test_screen_topm_float_parity():
    """Float data: distances allclose; candidate sets identical."""
    kq, kx = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (6, 24))
    x = jax.random.normal(kx, (2000, 24))
    ri, rd = ref.screen_topm_ref(q, x, 128)
    for backend in BACKENDS:
        si, sd = ops.screen_topm(q, x, 128, backend=backend, tile=512)
        np.testing.assert_allclose(np.asarray(sd), np.asarray(rd),
                                   rtol=1e-5, atol=1e-5)
        for i in range(q.shape[0]):
            assert set(np.asarray(si)[i]) == set(np.asarray(ri)[i])


def test_screen_topm_padded_rows_excluded():
    """+inf norms (the sharded layouts' padding convention) never screen
    in: their slots carry +inf distance markers."""
    q, x = _int_data(3, 4, 64, 8)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, -1).at[50:].set(jnp.inf)
    for backend in BACKENDS:
        idx, d2 = ops.screen_topm(q, x, 60, x_norms=xn, backend=backend,
                                  tile=16)
        idx, d2 = np.asarray(idx), np.asarray(d2)
        assert (idx[np.isfinite(d2)] < 50).all()
        assert (~np.isfinite(d2)).sum(-1).min() >= 10  # 14 real rows short
        assert np.isfinite(d2[:, :50]).all()


def test_full_scan_stream_matches_dense():
    """Streaming LSE full scan == dense [B, N]-logits aggregate, and the
    partial states LSE-merge to the same mean."""
    kq, kx = jax.random.split(jax.random.PRNGKey(1))
    q = jax.random.normal(kq, (4, 16))
    x = jax.random.normal(kx, (777, 16))
    for sig2 in (0.05, 0.7, 4.0):
        dense = np.asarray(ref.golden_aggregate_ref(q, x, sig2))
        stream = np.asarray(ops.golden_aggregate(
            q, x[:, None, :], sig2, backend="xla", stream=True, tile=128))
        np.testing.assert_allclose(stream, dense, rtol=1e-5, atol=1e-5)
        acc_s, m_s, l_s = ops.golden_full_partial(
            q, x[:, None, :], sig2, stream=True, tile=100)   # ragged tail
        acc_d, m_d, l_d = ops.golden_full_partial(q, x[:, None, :], sig2,
                                                  stream=False)
        np.testing.assert_allclose(np.asarray(m_s), np.asarray(m_d),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(acc_s / l_s[:, None]),
                                   np.asarray(acc_d / l_d[:, None]),
                                   rtol=1e-5, atol=1e-5)


# -- engine regressions: streaming must not change any output ----------------

@pytest.fixture(scope="module")
def gmm_setup():
    store = gmm(700, dim=16, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    return store, x


@pytest.mark.parametrize("backend", BACKENDS)
def test_engine_streamed_parity(gmm_setup, backend):
    """denoise / select / full_scan identical whichever screen mode the
    engine compiles."""
    store, x = gmm_setup
    ref_eng = GoldDiffEngine(store, SCH, GoldDiffConfig(), backend=backend,
                             screen="materialized")
    st_eng = GoldDiffEngine(store, SCH, GoldDiffConfig(), backend=backend,
                            screen="streamed", screen_tile=128)
    for t in (800, 300, 50):
        np.testing.assert_allclose(
            np.asarray(st_eng.denoise(x, t)),
            np.asarray(ref_eng.denoise(x, t)), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(st_eng.full_scan(x, t)),
            np.asarray(ref_eng.full_scan(x, t)), rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(
            np.sort(np.asarray(st_eng.select(x, t)), -1),
            np.sort(np.asarray(ref_eng.select(x, t)), -1))


def test_masked_streamed_parity(gmm_setup):
    """Masked (scan/pjit) mode unchanged when screening is streamed."""
    store, x = gmm_setup
    gd_ref = GoldDiff(OptimalDenoiser(store, SCH), screen="materialized")
    gd_st = GoldDiff(OptimalDenoiser(store, SCH), screen="streamed",
                     screen_tile=96)
    for t in (900, 400, 50):
        np.testing.assert_allclose(
            np.asarray(gd_st.call_masked(x, jnp.asarray(t))),
            np.asarray(gd_ref.call_masked(x, jnp.asarray(t))),
            rtol=2e-4, atol=2e-4)


def test_streamed_cache_keys_distinct(gmm_setup):
    """Streamed and materialized programs never collide in the cache,
    and the tile size is part of the streamed program's identity."""
    store, x = gmm_setup
    st_eng = GoldDiffEngine(store, SCH, GoldDiffConfig(),
                            screen="streamed", screen_tile=128)
    mat_eng = GoldDiffEngine(store, SCH, GoldDiffConfig(),
                             screen="materialized")
    k_st = st_eng._key("denoise", 500, x)
    k_mat = mat_eng._key("denoise", 500, x)
    assert k_st != k_mat
    assert ("screen", "streamed", 128) in k_st
    assert ("screen", "materialized") in k_mat
    st_eng2 = GoldDiffEngine(store, SCH, GoldDiffConfig(),
                             screen="streamed", screen_tile=256)
    assert st_eng2._key("denoise", 500, x) != k_st


def test_engine_rejects_unknown_screen_mode(gmm_setup):
    store, _ = gmm_setup
    with pytest.raises(ValueError):
        GoldDiffEngine(store, SCH, screen="lazy")


def test_auto_crossover_policy(gmm_setup):
    """auto == materialized below the byte budget, streamed above it."""
    store, _ = gmm_setup
    eng = GoldDiffEngine(store, SCH, GoldDiffConfig())
    assert not eng.use_stream(8)                   # tiny store: dense
    eng._screen_budget = 4 * 8 * store.n - 1
    assert eng.use_stream(8)                       # budget crossed
    assert not eng.use_stream(8, n=4)              # local-n override


def test_sharded_streamed_parity_subprocess():
    """Sharded engine outputs unchanged (vs the single-host MATERIALIZED
    engine) when every shard-local screen streams — the candidate
    partition and two-stage merge are unaffected by how the local top-m
    is computed."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
from repro.data import gmm

def relerr(a, b):
    return np.abs(np.asarray(a) - np.asarray(b)).max() / \
        (np.abs(np.asarray(b)).max() + 1e-9)

mesh = jax.make_mesh((8,), ("data",))
store = gmm(1003, dim=16, seed=0)            # uneven N % 8: padded tails
sch = make_schedule("ddpm_linear", 1000)
ref = GoldDiffEngine(store, sch, GoldDiffConfig(), screen="materialized")
sh = GoldDiffEngine(store, sch, GoldDiffConfig(), mesh=mesh,
                    screen="streamed", screen_tile=64)
x0 = store.X[:4]
ok = True
for t in (100, 500, 900):
    eps = jax.random.normal(jax.random.PRNGKey(t), x0.shape)
    xt = sch.add_noise(x0, eps, t)
    e1 = relerr(sh.denoise(xt, t), ref.denoise(xt, t))
    e2 = relerr(sh.denoise_masked(xt, jnp.asarray(t)),
                ref.denoise_masked(xt, jnp.asarray(t)))
    e3 = relerr(sh.full_scan(xt, t), ref.full_scan(xt, t))
    a, b = np.asarray(sh.select(xt, t)), np.asarray(ref.select(xt, t))
    ov = np.mean([len(set(a[i]) & set(b[i])) / a.shape[1]
                  for i in range(a.shape[0])])
    print("t", t, e1, e2, e3, ov)
    ok &= e1 < 1e-5 and e2 < 1e-5 and e3 < 1e-5 and ov == 1.0
print("PASS" if ok else "FAIL")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    repo = str(Path(__file__).resolve().parent.parent)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, cwd=repo, env=env)
    assert "PASS" in r.stdout, r.stdout + r.stderr
