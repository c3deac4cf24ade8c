"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.golden_attention import select_golden_blocks


@pytest.mark.parametrize("b,n,d", [(1, 16, 8), (7, 100, 32), (37, 1000, 96),
                                   (128, 257, 64), (4, 4096, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pdist_sweep(b, n, d, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, d), dtype)
    x = jax.random.normal(jax.random.PRNGKey(1), (n, d), dtype)
    out = ops.pdist(q, x, backend="pallas_interpret")
    expect = ref.pdist_ref(q, x)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,n,d,sigma2", [
    (1, 64, 8, 1.0), (5, 500, 32, 0.25), (16, 1000, 64, 4.0),
    (3, 130, 16, 0.01),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_golden_aggregate_sweep(b, n, d, sigma2, dtype):
    q = jax.random.normal(jax.random.PRNGKey(2), (b, d), dtype)
    x = jax.random.normal(jax.random.PRNGKey(3), (n, d), dtype)
    out = ops.golden_aggregate(q, x[:, None, :], sigma2,
                               backend="pallas_interpret")
    expect = ref.golden_aggregate_ref(q, x, sigma2)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_golden_aggregate_matches_optimal_denoiser():
    """Kernel == the core library's full-scan posterior mean (Eq. 2)."""
    from repro.core import OptimalDenoiser, make_schedule
    from repro.data import gmm
    store = gmm(512, dim=16, seed=0)
    sch = make_schedule("ddpm_linear", 1000)
    den = OptimalDenoiser(store, sch)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 16))
    t = 300
    a = float(sch.a[t])
    out_k = ops.golden_aggregate(x / a, store.rows, float(sch.sigma(t)) ** 2,
                                 backend="pallas_interpret")
    out_d = den(x, t)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_d),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,hkv,g,dh,s,bs,kb", [
    (1, 1, 1, 32, 256, 64, 2), (2, 4, 3, 64, 1024, 128, 5),
    (3, 2, 8, 64, 512, 128, 4), (2, 8, 1, 128, 2048, 256, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_golden_attention_sweep(b, hkv, g, dh, s, bs, kb, dtype):
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(keys[0], (b, hkv, g, dh), dtype)
    k = jax.random.normal(keys[1], (b, hkv, s, dh), dtype)
    v = jax.random.normal(keys[2], (b, hkv, s, dh), dtype)
    idx, valid = select_golden_blocks(q, k, kb, bs)
    valid = valid.at[:, :, -1].set(0)           # exercise padding mask
    out = ops.golden_attention_decode(q, k, v, idx, valid, bs,
                                      backend="pallas_interpret")
    expect = ref.golden_attention_decode_ref(q, k, v, idx, valid, bs)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_golden_attention_full_blocks_equals_dense():
    """Selecting ALL blocks reproduces exact attention."""
    b, hkv, g, dh, s, bs = 2, 2, 2, 32, 512, 64
    keys = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(keys[0], (b, hkv, g, dh))
    k = jax.random.normal(keys[1], (b, hkv, s, dh))
    v = jax.random.normal(keys[2], (b, hkv, s, dh))
    nb = s // bs
    idx = jnp.tile(jnp.arange(nb)[None, None], (b, hkv, 1)).astype(jnp.int32)
    valid = jnp.ones_like(idx)
    out = ops.golden_attention_decode(q, k, v, idx, valid, bs,
                                      backend="pallas_interpret")
    scores = jnp.einsum("bhgd,bhsd->bhgs", q, k) / dh ** 0.5
    dense = jnp.einsum("bhgs,bhsd->bhgd", jax.nn.softmax(scores, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)


def test_xla_backend_dispatch():
    q = jax.random.normal(jax.random.PRNGKey(7), (3, 16))
    x = jax.random.normal(jax.random.PRNGKey(8), (64, 16))
    np.testing.assert_allclose(
        np.asarray(ops.pdist(q, x, backend="xla")),
        np.asarray(ops.pdist(q, x, backend="pallas_interpret")),
        rtol=1e-4, atol=1e-4)


# -- row-fetch kernels: candidate rows DMA'd from the [N, 1, D] store ---------

FETCH_CASES = [
    # (b, n, d, m, tile): m not a multiple of the tile; D=3072 and
    # D=12288 take their real 256- and 128-row tiles at small N
    (3, 200, 64, 37, None), (2, 300, 64, 300, 128), (2, 257, 3072, 300, None),
    (2, 150, 12288, 300, None),
]


def _fetch_inputs(b, n, d, m, dtype, seed):
    """Store, queries and ids that repeat and hit rows 0 and N-1."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (n, d), jnp.float32).astype(dtype)
    q = jax.random.normal(keys[1], (b, d), jnp.float32)
    idx = jax.random.randint(keys[2], (b, m), 0, n, jnp.int32)
    idx = idx.at[:, 0].set(0).at[:, 1].set(n - 1).at[:, 2].set(0)
    idx = idx.at[0, -1].set(n - 1)
    xn = jnp.sum(x.astype(jnp.float32) ** 2, -1)
    return x, q, idx, xn


@pytest.mark.parametrize("b,n,d,m,tile", FETCH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_support_sqdist_fetch_sweep(b, n, d, m, tile, dtype):
    x, q, idx, xn = _fetch_inputs(b, n, d, m, dtype, seed=10)
    out = ops.support_distances(q, x[:, None, :], idx, x_norms=xn,
                                backend="pallas_interpret", bm=tile)
    expect = ref.support_sqdist_ref(q, x[idx], xn[idx])
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-3)


@pytest.mark.parametrize("b,n,d,m,tile", FETCH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_support_aggregate_fetch_sweep(b, n, d, m, tile, dtype):
    x, _, idx, _ = _fetch_inputs(b, n, d, m, dtype, seed=11)
    lg = 4.0 * jax.random.normal(jax.random.PRNGKey(12), idx.shape)
    out = ops.golden_support_aggregate(x[:, None, :], idx, lg,
                                       backend="pallas_interpret", bk=tile)
    expect = ref.golden_support_aggregate_ref(x[idx], lg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["rerank", "aggregate"])
def test_fetch_inf_norm_pad_rows(kernel):
    """Rows whose stored norm is +inf (a sharded store's padding) give
    +inf distances, and their +inf distances give zero weight, however
    large the values the kernel fetches from them."""
    b, n, d, m, pad = 2, 260, 256, 300, 4
    x, q, idx, xn = _fetch_inputs(b, n, d, m, jnp.float32, seed=13)
    x = x.at[n - pad:].set(1e3)
    xn = xn.at[n - pad:].set(jnp.inf)
    is_pad = np.asarray(idx) >= n - pad
    assert is_pad.any() and not is_pad.all()
    d2 = ops.support_distances(q, x[:, None, :], idx, x_norms=xn,
                               backend="pallas_interpret")
    if kernel == "rerank":
        got = np.asarray(d2)
        assert np.all(np.isposinf(got[is_pad]))
        np.testing.assert_allclose(
            got[~is_pad],
            np.asarray(ref.support_sqdist_ref(q, x[idx], xn[idx]))[~is_pad],
            rtol=1e-5, atol=1e-3)
        return
    lg = jnp.maximum(-d2 / (2.0 * 50.0), ref.NEG_INF)
    out = ops.golden_support_aggregate(x[:, None, :], idx, lg,
                                       backend="pallas_interpret")
    real = jnp.where(jnp.asarray(is_pad), -jnp.inf, lg)
    expect = ref.golden_support_aggregate_ref(
        jnp.where(jnp.asarray(is_pad)[..., None], 0.0, x[idx]), real)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)
