"""Finite-output guards in the kernel layer (PR 6 satellite 2).

Degenerate inputs that used to NaN (or host-crash) silently:

* extreme / zero / negative sigma^2 -> ``1 / (2 sigma2)`` overflow or
  ZeroDivisionError, then ``0 * inf`` NaN logits;
* all-masked supports (every logit at the hard ``-inf`` or the NEG_INF
  sentinel) -> softmax 0/0;
* ``m > N`` surplus screen slots (+inf distances) -> ``-inf`` logits
  meeting the clamp;
* every shard carrying a hard ``-inf`` running max -> ``-inf - -inf``
  NaN in the LSE merge scale.

All of these must now degrade to FINITE outputs (uniform / data-mean
aggregates), on every backend, streamed and materialized — the serving
runtime's per-segment finite guard is the last line of defense, not the
only one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import gmm
from repro.distributed.sharding import lse_merge_mean, shard_map_compat
from repro.kernels import ops, ref

STORE = gmm(128, dim=8, seed=0)
X = STORE.X
ROWS = STORE.rows                 # the store rows [N, 1, D] the ops take
XN = STORE.x_norms
Q = jnp.asarray(np.random.default_rng(1).normal(size=(3, 8)), jnp.float32)

BACKENDS = ("xla", "pallas_interpret")
DEGENERATE_SIGMA2 = (0.0, -1.0, 1e-45, float("nan"))


def test_finite_inv_two_sigma2():
    assert ref.finite_inv_two_sigma2(0.5) == 1.0
    assert ref.finite_inv_two_sigma2(2.0) == 0.25
    for s in DEGENERATE_SIGMA2:
        assert ref.finite_inv_two_sigma2(s) == ref.MAX_INV_TWO_SIGMA2
    # tiny-but-positive sigma2 clamps instead of overflowing fp32
    inv = ref.finite_inv_two_sigma2(1e-40)
    assert inv == ref.MAX_INV_TWO_SIGMA2
    assert np.isfinite(np.float32(inv))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("sigma2", DEGENERATE_SIGMA2 + (1e6,))
def test_full_scan_finite_at_extreme_sigma(backend, sigma2):
    """golden_aggregate degrades to a finite (data-mean-ish) estimate
    at degenerate sigma2 on every backend, streamed and dense."""
    outs = [np.asarray(ops.golden_aggregate(Q, ROWS, sigma2, x_norms=XN,
                                            backend=backend, stream=s))
            for s in ((False, True) if backend == "xla" else (False,))]
    for out in outs:
        assert np.isfinite(out).all(), (backend, sigma2)
    # degenerate sigma2 clamps every logit -> uniform weights = mean
    if sigma2 in DEGENERATE_SIGMA2:
        mean = np.asarray(X).mean(0)
        for out in outs:
            np.testing.assert_allclose(out, np.tile(mean, (Q.shape[0], 1)),
                                       rtol=0, atol=1e-4)


@pytest.mark.parametrize("sigma2", DEGENERATE_SIGMA2)
def test_full_scan_partial_states_finite(sigma2):
    """The shard-local halves (dense + streamed) stay finite and agree
    under degenerate sigma2 (they used to ZeroDivisionError / NaN)."""
    for stream in (False, True):
        acc, m, l = ops.golden_full_partial(Q, ROWS, sigma2, x_norms=XN,
                                            stream=stream, tile=32)
        assert np.isfinite(np.asarray(acc)).all()
        assert np.isfinite(np.asarray(m)).all()     # NEG_INF sentinel, not -inf
        assert np.isfinite(np.asarray(l)).all() and (np.asarray(l) > 0).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_all_masked_support_aggregate_finite(backend):
    """Every support slot masked to NEG_INF: uniform weights over the
    gathered rows, never 0/0."""
    idx = jnp.tile(jnp.arange(4)[None, :], (Q.shape[0], 1))
    lg = jnp.full((Q.shape[0], 4), ref.NEG_INF, jnp.float32)
    out = np.asarray(ops.golden_support_aggregate(ROWS, idx, lg,
                                                  backend=backend))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.tile(np.asarray(X[:4]).mean(0),
                                            (Q.shape[0], 1)), atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_surplus_screen_slots_stay_finite(backend):
    """m > N: surplus slots carry d2=+inf out of the screen; the masked
    aggregation path must clamp them to zero weight, not NaN."""
    n = X.shape[0]
    m = n + 16
    idx, d2 = ops.screen_topm(Q, X, m, x_norms=XN, stream=True, tile=32,
                              backend=backend)
    d2 = np.asarray(d2)
    assert np.isinf(d2[:, n:]).all() and np.isfinite(d2[:, :n]).all()
    # feed the screen's +inf straight into logits like denoise does
    lg = jnp.maximum(-jnp.asarray(d2) * ref.finite_inv_two_sigma2(0.25),
                     ref.NEG_INF)
    lg = jnp.where(jnp.isnan(lg), ref.NEG_INF, lg)
    out = np.asarray(ops.golden_support_aggregate(
        ROWS, jnp.asarray(idx), lg,
        backend=backend, strategy="gather"))
    assert np.isfinite(out).all()


def test_lse_merge_mean_all_hard_neg_inf():
    """Every shard reporting a hard -inf max (degenerate all-masked
    candidate sets): the merge degrades to finite zeros instead of the
    -inf - -inf NaN scale."""
    mesh = jax.make_mesh((1,), ("data",))

    def body(acc, m, l):
        return lse_merge_mean(acc, m, l, "data")

    from jax.sharding import PartitionSpec as P
    fn = shard_map_compat(body, mesh, (P("data"), P("data"), P("data")),
                          P("data"))
    acc = jnp.zeros((2, 4), jnp.float32)
    m = jnp.full((2,), -jnp.inf, jnp.float32)
    l = jnp.zeros((2,), jnp.float32)
    out = np.asarray(fn(acc, m, l))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out, np.zeros_like(out))
    # and the normal finite-sentinel path still merges exactly
    m2 = jnp.full((2,), ref.NEG_INF, jnp.float32)
    acc2 = jnp.ones((2, 4), jnp.float32)
    l2 = jnp.ones((2,), jnp.float32)
    out2 = np.asarray(fn(acc2, m2, l2))
    np.testing.assert_allclose(out2, np.ones_like(out2), rtol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_normal_sigma_unchanged(backend):
    """The guard is an identity in the normal regime: multiplying by
    the precomputed 1/(2 sigma2) equals the old division bit-for-bit
    against the reference."""
    sigma2 = 0.37
    out = np.asarray(ops.golden_aggregate(Q, ROWS, sigma2, x_norms=XN,
                                          backend=backend))
    d2 = np.asarray(ref.pdist_ref(Q, X, x_norms=XN), np.float64)
    w = np.exp(-(d2 - d2.min(1, keepdims=True)) / (2 * sigma2))
    expect = (w / w.sum(1, keepdims=True)) @ np.asarray(X, np.float64)
    np.testing.assert_allclose(out, expect, rtol=2e-4, atol=2e-5)
