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
    out = ops.golden_aggregate(q, x, sigma2, backend="pallas_interpret")
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
    out_k = ops.golden_aggregate(x / a, store.X, float(sch.sigma(t)) ** 2,
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
