"""Pallas flash-attention kernel vs dense oracle: shape/dtype/causal sweep."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("b,hkv,g,s,dh,qc,kc", [
    (1, 1, 1, 64, 32, 32, 32),
    (2, 2, 3, 128, 64, 32, 64),
    (1, 4, 5, 256, 64, 64, 128),   # GQA, uneven tiles over diagonal
    (2, 1, 2, 96, 32, 32, 48),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hkv, g, s, dh, qc, kc, causal, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (b, hkv, g, s, dh), dtype)
    k = jax.random.normal(keys[1], (b, hkv, s, dh), dtype)
    v = jax.random.normal(keys[2], (b, hkv, s, dh), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, qc=qc, kc=kc,
                              backend="pallas_interpret")
    expect = ref.flash_attention_ref(q, k, v, causal)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_layer_attention():
    """Kernel agrees with the pure-JAX production path (models.layers)."""
    from repro.models.layers import AttnDims, flash_attention as jax_flash
    b, s, h, hkv, dh = 2, 128, 4, 2, 32
    dims = AttnDims(h, hkv, dh)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, s, h, dh))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, dh))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, s, hkv, dh))
    o_jax = jax_flash(q, k, v, dims, q_chunk=32, kv_chunk=64)
    qg = q.reshape(b, s, hkv, h // hkv, dh).transpose(0, 2, 3, 1, 4)
    o_k = ops.flash_attention(qg, k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), qc=32, kc=64,
                              backend="pallas_interpret")
    o_k = o_k.transpose(0, 3, 1, 2, 4).reshape(b, s, h, dh)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_jax),
                               rtol=2e-5, atol=2e-5)
