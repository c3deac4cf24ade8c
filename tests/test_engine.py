"""GoldDiffEngine backend/dtype parity: xla == pallas_interpret == eager.

The engine routes the coarse -> fine -> aggregate pipeline through
``repro.kernels.ops`` with two execution strategies (dense GEMM form on
``xla``, tiled gather kernels on ``pallas*``).  These tests pin all of
them to the plain eager-jnp formulation the seed used (gather +
broadcast-subtract + recompute), for every stage and end-to-end, in
fp32 and bf16 storage.

The real-TPU ``pallas`` backend is exercised automatically when a TPU
platform is present (it cannot lower on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GoldDiff, GoldDiffConfig, GoldDiffEngine,
                        OptimalDenoiser, make_schedule)
from repro.core.dataset import downsample_proxy
from repro.core.golddiff import coarse_screen, golden_select
from repro.kernels import ops
from repro.data import cifar_like, gmm

SCH = make_schedule("ddpm_linear", 1000)

BACKENDS = ["xla", "pallas_interpret"]
if any(d.platform == "tpu" for d in jax.devices()):
    BACKENDS.append("pallas")


def _eager_coarse(store, q, m, factor):
    """The seed's inline coarse screen (broadcast proxy distances)."""
    q_img = q.reshape(q.shape[:-1] + tuple(store.image_shape))
    qp = downsample_proxy(q_img, factor)
    d2 = (jnp.sum(qp * qp, -1, keepdims=True) + store.proxy_norms[None, :]
          - 2.0 * qp @ store.proxy.T)
    return jax.lax.top_k(-d2, m)[1]


def _eager_step(store, sch, cfg, x_t, t):
    """The seed GoldDiff static step: gather + broadcast-subtract,
    distances recomputed in the aggregation stage."""
    from repro.core.engine import schedule_sizes
    m_t, k_t = schedule_sizes(cfg, sch, t, store.n)
    a = float(sch.a[t])
    sig2 = float(sch.sigma_np(t)) ** 2
    q = x_t / a
    cand = _eager_coarse(store, q, m_t, cfg.proxy_factor)
    xs = store.X[cand]
    d2 = jnp.sum((q[:, None, :] - xs) ** 2, -1)
    pos = jax.lax.top_k(-d2, k_t)[1]
    idx = jnp.take_along_axis(cand, pos, -1)
    xs_k = store.X[idx]
    d2k = jnp.sum((q[:, None, :] - xs_k) ** 2, -1)
    w = jax.nn.softmax(-d2k / (2.0 * sig2), -1)
    return jnp.einsum("bk,bkd->bd", w, xs_k)


@pytest.fixture(scope="module")
def image_setup():
    store = cifar_like(512, seed=0)
    x = jax.random.normal(jax.random.PRNGKey(0), (6, store.dim))
    return store, x


@pytest.fixture(scope="module")
def gmm_setup():
    store = gmm(512, dim=16, seed=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 16))
    return store, x


@pytest.mark.parametrize("backend", BACKENDS)
def test_coarse_screen_parity(image_setup, backend):
    store, x = image_setup
    m = 128
    eager = _eager_coarse(store, x, m, 4)
    got = coarse_screen(store, x, m, 4, backend=backend)
    assert np.array_equal(np.sort(np.asarray(got), -1),
                          np.sort(np.asarray(eager), -1))


@pytest.mark.parametrize("backend", BACKENDS)
def test_golden_rerank_parity(gmm_setup, backend):
    store, x = gmm_setup
    b = x.shape[0]
    cand = jnp.tile(jnp.arange(256)[None], (b, 1))
    idx, d2 = ops.golden_rerank(x, store.rows, cand, 32,
                                x_norms=store.x_norms, backend=backend)
    # eager oracle: broadcast-subtract distances, top-k
    d2_all = jnp.sum((x[:, None] - store.X[cand]) ** 2, -1)
    neg, pos = jax.lax.top_k(-d2_all, 32)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(
                              jnp.take_along_axis(cand, pos, -1)), -1))
    np.testing.assert_allclose(np.asarray(d2), np.asarray(-neg),
                               rtol=1e-4, atol=1e-4)


def test_golden_select_matches_eager(gmm_setup):
    store, x = gmm_setup
    cand = jnp.tile(jnp.arange(store.n)[None], (x.shape[0], 1))
    for backend in BACKENDS:
        idx = golden_select(store, x, cand, 24, backend=backend)
        d2 = jnp.sum((x[:, None] - store.X[None]) ** 2, -1)
        ref = jax.lax.top_k(-d2, 24)[1]
        assert np.array_equal(np.sort(np.asarray(idx), -1),
                              np.sort(np.asarray(ref), -1))


@pytest.mark.parametrize("backend", BACKENDS)
def test_golden_support_aggregate_parity(gmm_setup, backend):
    store, x = gmm_setup
    b = x.shape[0]
    idx = jnp.argsort(jax.random.normal(jax.random.PRNGKey(2),
                                        (b, store.n)), -1)[:, :40]
    d2 = jnp.sum((x[:, None] - store.X[idx]) ** 2, -1)
    lg = -d2 / 0.7
    out = ops.golden_support_aggregate(store.rows, idx, lg, backend=backend)
    w = jax.nn.softmax(lg, -1)
    eager = jnp.einsum("bk,bkd->bd", w, store.X[idx])
    np.testing.assert_allclose(np.asarray(out), np.asarray(eager),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
def test_full_scan_parity(gmm_setup, backend):
    store, x = gmm_setup
    den = OptimalDenoiser(store, SCH, backend=backend)
    t = 300
    out = den(x, t)
    lg = den.logits(x, t)
    eager = jnp.einsum("bn,nd->bd", jax.nn.softmax(lg, -1), store.X)
    np.testing.assert_allclose(np.asarray(out), np.asarray(eager),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("storage", [None, jnp.bfloat16])
def test_golddiff_call_end_to_end_parity(image_setup, backend, storage):
    store, x = image_setup
    cfg = GoldDiffConfig()
    gd = GoldDiff(OptimalDenoiser(store, SCH), cfg, backend=backend,
                  storage_dtype=storage)
    for t in (800, 300):
        out = np.asarray(gd(x, t), np.float32)
        eager = np.asarray(_eager_step(store, SCH, cfg, x, t))
        tol = 5e-2 if storage == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(out, eager, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("storage", [None, jnp.bfloat16])
def test_call_masked_end_to_end_parity(gmm_setup, backend, storage):
    store, x = gmm_setup
    gd = GoldDiff(OptimalDenoiser(store, SCH), backend=backend,
                  storage_dtype=storage)
    ref = GoldDiff(OptimalDenoiser(store, SCH))      # xla fp32 baseline
    for t in (900, 400, 50):
        out = np.asarray(gd.call_masked(x, jnp.asarray(t)), np.float32)
        base = np.asarray(ref.call_masked(x, jnp.asarray(t)), np.float32)
        tol = 5e-2 if storage == jnp.bfloat16 else 1e-4
        np.testing.assert_allclose(out, base, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
def test_on_support_parity(gmm_setup, backend):
    """Explicit support= path (the plug-in hook) across backends."""
    store, x = gmm_setup
    den = OptimalDenoiser(store, SCH, backend=backend)
    idx = jnp.argsort(jax.random.normal(jax.random.PRNGKey(3),
                                        (x.shape[0], store.n)), -1)[:, :30]
    t = 200
    out = den(x, t, support=idx)
    a = float(SCH.a[t])
    sig2 = float(SCH.sigma_np(t)) ** 2
    q = x / a
    d2 = jnp.sum((q[:, None] - store.X[idx]) ** 2, -1)
    w = jax.nn.softmax(-d2 / (2 * sig2), -1)
    eager = jnp.einsum("bk,bkd->bd", w, store.X[idx])
    np.testing.assert_allclose(np.asarray(out), np.asarray(eager),
                               rtol=1e-4, atol=1e-4)


def test_engine_program_cache_reuse(gmm_setup):
    """One compiled program per (kind, t, shape, dtype, backend)."""
    store, x = gmm_setup
    eng = GoldDiffEngine(store, SCH, GoldDiffConfig(), backend="xla")
    eng.denoise(x, 500)
    n0 = len(eng._programs)
    eng.denoise(x, 500)                               # hit
    assert len(eng._programs) == n0
    eng.denoise(x, 100)                               # new t -> new program
    eng.denoise(x[:2], 500)                           # new shape -> new program
    assert len(eng._programs) == n0 + 2


def test_engine_rejects_unknown_backend(gmm_setup):
    store, _ = gmm_setup
    with pytest.raises(ValueError):
        GoldDiffEngine(store, SCH, backend="cuda")


def test_masked_distances_computed_once(gmm_setup, monkeypatch):
    """The masked path must call the exact-distance op exactly once per
    step (the seed computed candidate distances twice)."""
    store, x = gmm_setup
    gd = GoldDiff(OptimalDenoiser(store, SCH))
    calls = {"n": 0}
    orig = ops.support_distances

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr("repro.core.engine.ops.support_distances", counting)
    gd.call_masked(x, jnp.asarray(300))
    assert calls["n"] == 1, calls
