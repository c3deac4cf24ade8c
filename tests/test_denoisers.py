"""Denoiser correctness: Eq. 2 vs brute force, Wiener optimality, patch paths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (OptimalDenoiser, PCADenoiser, PatchDenoiser,
                        WienerDenoiser, make_schedule)
from repro.core.dataset import pairwise_sq_dists
from repro.data import cifar_like, gmm, mnist_like

SCH = make_schedule("ddpm_linear", 1000)


def test_optimal_matches_bruteforce():
    store = gmm(300, dim=6, seed=0)
    den = OptimalDenoiser(store, SCH, chunk=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    t = 400
    a = float(SCH.a[t]); sig2 = float(SCH.sigma(t)) ** 2
    d2 = np.asarray(pairwise_sq_dists(x / a, store.X))
    w = jax.nn.softmax(jnp.asarray(-d2 / (2 * sig2)), -1)
    ref = np.asarray(w @ store.X)
    np.testing.assert_allclose(np.asarray(den(x, t)), ref, rtol=1e-4,
                               atol=1e-5)


def test_optimal_support_restriction():
    store = gmm(300, dim=6, seed=1)
    den = OptimalDenoiser(store, SCH)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 6))
    idx = jnp.tile(jnp.arange(300)[None], (3, 1))
    np.testing.assert_allclose(np.asarray(den(x, 300, support=idx)),
                               np.asarray(den(x, 300)), rtol=1e-4, atol=1e-5)


def test_wiener_is_linear_mmse_on_gaussian():
    """On exactly Gaussian data the Wiener filter IS the optimal denoiser
    in expectation; check it beats the mean-predictor on heldout noise."""
    rng = np.random.default_rng(0)
    cov_half = rng.normal(size=(8, 8)) * 0.3
    x = rng.normal(size=(2048, 8)) @ cov_half
    from repro.core.dataset import make_store
    store = make_store(x.astype(np.float32), (8,), proxy_factor=1)
    den = WienerDenoiser(store, SCH)
    t = 500
    x0 = jnp.asarray(x[:64], jnp.float32)
    eps = jax.random.normal(jax.random.PRNGKey(2), x0.shape)
    xt = SCH.add_noise(x0, eps, t)
    est = den(xt, t)
    mse_w = float(jnp.mean((est - x0) ** 2))
    mse_mean = float(jnp.mean((jnp.asarray(x.mean(0)) - x0) ** 2))
    mse_id = float(jnp.mean((xt / float(SCH.a[t]) - x0) ** 2))
    assert mse_w < mse_mean and mse_w < mse_id


def _spread_data(n, d, seed):
    """[n, d] rows off the origin with a spread spectrum (scales 3 to
    0.3 along a random rotation), so each eigenvector is well defined."""
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, d)))
    scales = np.geomspace(3.0, 0.3, d)
    return (rng.normal(size=(n, d)) * scales) @ q.T + 0.7


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float64) - b)
                 / np.linalg.norm(b))


@pytest.mark.parametrize("n,d", [(24, 40), (300, 16)])
def test_wiener_basis_matches_float64_svd(n, d):
    """The device basis (fp32 Gram matrix and ``eigh``) against the SVD
    of the centred rows in float64, for N < D and N > D: the
    eigenvalues, the projectors onto the leading eigenvectors (their
    signs are free) and the Wiener x0 at three timesteps.  Tolerance:
    1e-4 relative, since an fp32 eigendecomposition is compared with a
    float64 one."""
    from repro.core.dataset import make_store
    from repro.core.denoisers import wiener_basis, wiener_x0
    x = _spread_data(n, d, seed=n)
    store = make_store(x.astype(np.float32), (d,), proxy_factor=1)
    mu, V, lam = wiener_basis(store.rows, store.x_norms)
    r = min(n, d)
    assert V.shape == (d, r) and lam.shape == (r,)

    x64 = np.asarray(store.X, np.float64)
    mu_ref = x64.mean(0)
    _, s, vt = np.linalg.svd(x64 - mu_ref, full_matrices=False)
    lam_ref, v_ref = s ** 2 / n, vt.T
    assert _rel(mu, mu_ref) < 1e-4
    assert _rel(lam, lam_ref) < 1e-4
    V = np.asarray(V, np.float64)
    for k in {r // 2, min(n - 1, d)}:    # rank n - 1 once centred
        assert _rel(V[:, :k] @ V[:, :k].T, v_ref[:, :k] @ v_ref[:, :k].T) \
            < 1e-4, k
    xt = np.random.default_rng(1).normal(size=(4, d))
    for t in (900, 500, 100):
        a, b = float(SCH.a[t]), float(SCH.b[t])
        coeff = a * lam_ref / (a * a * lam_ref + b * b)
        ref = mu_ref + (((xt - a * mu_ref) @ v_ref) * coeff) @ v_ref.T
        got = wiener_x0((mu, jnp.asarray(V, jnp.float32), lam),
                        jnp.asarray(xt, jnp.float32), a, b)
        assert _rel(got, ref) < 1e-4, t


def test_wiener_basis_leaves_out_padding_rows():
    """Rows with ``+inf`` norms (capacity padding) do not enter the
    mean or the covariance."""
    from repro.core.dataset import make_store
    from repro.core.denoisers import wiener_basis
    x = _spread_data(200, 8, seed=3).astype(np.float32)
    store = make_store(x, (8,), proxy_factor=1)
    padded = make_store(np.concatenate([x, np.zeros((56, 8), np.float32)]),
                        (8,), proxy_factor=1)
    padded = padded._replace(
        x_norms=padded.x_norms.at[200:].set(jnp.inf))
    want, got = wiener_basis(store.rows, store.x_norms), \
        wiener_basis(padded.rows, padded.x_norms)
    np.testing.assert_allclose(got.mu, want.mu, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got.lam, want.lam, rtol=1e-4)


def test_patch_denoiser_patch_schedule():
    store = cifar_like(64, seed=0)
    den = PatchDenoiser(store, SCH, patch_min=3, patch_max=11)
    assert den.patch_size(999) >= den.patch_size(10)
    assert den.patch_size(999) % 2 == 1 and den.patch_size(10) % 2 == 1


@pytest.mark.parametrize("cls", [PatchDenoiser, PCADenoiser])
def test_patch_denoisers_shapes_and_finiteness(cls):
    store = mnist_like(128, seed=0)
    den = cls(store, SCH, chunk=64)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, store.dim))
    for t in (900, 400, 30):
        out = den(x, t)
        assert out.shape == x.shape
        assert bool(jnp.isfinite(out).all())


def test_pca_full_vs_support_consistency():
    """Support=all indices must reproduce the full-scan (unbiased) path."""
    store = mnist_like(96, seed=1)
    den = PCADenoiser(store, SCH, weighting="ss", chunk=96)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, store.dim))
    idx = jnp.tile(jnp.arange(96)[None], (2, 1))
    full = den(x, 300)
    sub = den(x, 300, support=idx)
    np.testing.assert_allclose(np.asarray(full), np.asarray(sub),
                               rtol=2e-4, atol=2e-4)


def test_schedules_consistency():
    for name in ("ddpm_linear", "cosine", "edm_vp", "edm_ve"):
        sch = make_schedule(name, 256)
        assert sch.num_steps == 256
        sig = np.asarray([float(sch.sigma(t)) for t in (1, 128, 256)])
        assert np.all(np.diff(sig) > 0), f"{name}: sigma must increase"
        g = np.asarray([float(sch.g(t)) for t in (1, 128, 256)])
        assert g[0] <= g[1] <= g[2] and g[0] == 0.0 and g[2] == 1.0
