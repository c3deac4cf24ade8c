"""Sampler + data pipeline + distributed retrieval (single device)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GoldDiff, OptimalDenoiser,
                        make_schedule, sample, sample_scan,
                        denoise_trajectory, sampling_timesteps)
from repro.core.dataset import downsample_proxy
from repro.data import (TokenPipeline, TokenPipelineConfig, cifar_like,
                        fast_batch, gmm, moons)

SCH = make_schedule("ddpm_linear", 1000)


def test_sampling_timesteps_grid():
    ts = sampling_timesteps(SCH, 10)
    assert ts[0] == 1000 and ts[-1] == 0
    assert all(a > b for a, b in zip(ts, ts[1:]))
    assert len(ts) == 11


def test_sample_lands_near_manifold():
    """DDIM with the full-scan optimal denoiser lands on/near data points
    (the memorization property of the exact denoiser, Sec. 2)."""
    store = gmm(512, dim=8, num_modes=4, spread=0.05, seed=0)
    den = OptimalDenoiser(store, SCH)
    out = sample(den, SCH, (8, 8), jax.random.PRNGKey(0), num_steps=20)
    d2 = jnp.min(jnp.sum((out[:, None] - store.X[None]) ** 2, -1), -1)
    assert float(jnp.sqrt(d2).mean()) < 0.35, float(jnp.sqrt(d2).mean())


def test_scan_and_perstep_agree():
    store = gmm(256, dim=4, seed=1)
    gd = GoldDiff(OptimalDenoiser(store, SCH))
    x1 = sample(gd, SCH, (4, 4), jax.random.PRNGKey(3), num_steps=10,
                clip_value=None)
    x2 = sample_scan(gd.call_masked, SCH, (4, 4), jax.random.PRNGKey(3),
                     num_steps=10, clip_value=None)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=2e-3,
                               atol=2e-3)


def test_paired_trajectory_deterministic():
    store = moons(512)
    den = OptimalDenoiser(store, SCH)
    xT = jax.random.normal(jax.random.PRNGKey(5), (4, 2))
    a, xs_a = denoise_trajectory(den, SCH, xT, num_steps=10)
    b, xs_b = denoise_trajectory(den, SCH, xT, num_steps=10)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(xs_a) == 11


def test_downsample_proxy_dims():
    x = jnp.zeros((5, 32, 32, 3))
    p = downsample_proxy(x, 4)
    assert p.shape == (5, 8 * 8 * 3)
    # low-dim data falls back to identity flatten
    q = jnp.zeros((5, 2))
    assert downsample_proxy(q, 4).shape == (5, 2)


def test_dataset_stores():
    st = cifar_like(64, seed=0)
    assert st.X.shape == (64, 3072) and st.proxy.shape == (64, 192)
    assert st.labels is not None and st.labels.shape == (64,)
    assert bool(jnp.isfinite(st.X).all())
    # standardized
    assert abs(float(st.X.mean())) < 0.1
    assert 0.5 < float(st.X.std()) < 2.0


def test_fourier_field_matches_serial_sum():
    """The threaded row-block sum equals the plain serial loop bit for
    bit: same draws in the same order, modes added in the same order."""
    from repro.data.synthetic import _fourier_field
    h, w, c, max_freq, count = 8, 8, 3, 4, 37

    rng = np.random.default_rng(7)
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    want = np.zeros((count, h, w, c), np.float32)
    for f in range(1, max_freq + 1):
        for (gy, gx) in ((f, 0), (0, f), (f, f)):
            phase = rng.uniform(0, 2 * np.pi, (count, 1, 1, c))
            amp = rng.normal(0, 1.0 / f, (count, 1, 1, c))
            base = 2 * np.pi * (gy * yy + gx * xx)
            want += amp * np.cos(base[None, :, :, None] + phase)
    got = _fourier_field(np.random.default_rng(7), h, w, c, max_freq, count)
    np.testing.assert_array_equal(got, want)


def test_token_pipeline_deterministic():
    cfg = TokenPipelineConfig(vocab_size=512, seq_len=64, global_batch=4,
                              seed=3)
    tp = TokenPipeline(cfg)
    b1 = tp.batch(5)
    b2 = TokenPipeline(cfg).batch(5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert b1["tokens"].shape == (4, 64)
    assert int(b1["tokens"].max()) < 512
    # labels are next-token shifted
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["labels"][:, :-1]))
    fb = fast_batch(cfg, 0)
    assert fb["tokens"].shape == (4, 64)


def test_conditional_store_restriction():
    from repro.core.dataset import restrict
    st = cifar_like(128, seed=0)
    idx = jnp.nonzero(st.labels == 0)[0]
    sub = restrict(st, idx)
    assert sub.n == int(idx.shape[0])
    assert bool((sub.labels == 0).all())


@pytest.mark.parametrize("extra", [-4059, 0, 37])
def test_make_store_rows_and_chunked_proxy(extra):
    """The store keeps its rows once as [N, 1, D]; ``X`` reads them on
    the host as [N, D], and the proxy pooled a chunk of rows at a time
    (the last chunk overlapping back) equals the proxy of the whole
    image array, bit for bit."""
    from repro.core.dataset import PROXY_CHUNK, make_store
    n = PROXY_CHUNK + extra
    x = jax.random.normal(jax.random.PRNGKey(3), (n, 8 * 8 * 3))
    store = make_store(x, (8, 8, 3))
    assert store.rows.shape == (n, 1, 8 * 8 * 3)
    assert (store.n, store.dim) == (n, 8 * 8 * 3)
    assert isinstance(store.X, np.ndarray)
    np.testing.assert_array_equal(store.X, np.asarray(x))
    whole = downsample_proxy(x.reshape(n, 8, 8, 3), 4)
    np.testing.assert_array_equal(np.asarray(store.proxy), np.asarray(whole))
    np.testing.assert_allclose(np.asarray(store.x_norms),
                               np.sum(np.asarray(x) ** 2, -1), rtol=1e-6)
