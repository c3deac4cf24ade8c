"""Plain fp32 GoldDiff sampler: the reference that decides ``correct``.

Straight ``jax.numpy`` at ``HIGHEST`` matmul precision, independent of
the program under test: it builds its own proxy (average pooling of
the store's images), its own schedule (``bench/schedule.py``) and each
request's terminal noise from the request's seed.  Each DDIM step of a
trajectory, for a block of rows:

1. proxy screen: the m_t rows nearest the query on the pooled proxy;
2. re-rank: of those candidates, the k_t rows nearest the rescaled
   query ``x_t / a_t`` in full resolution (the golden support);
3. aggregate: the posterior mean over the golden support, softmax of
   ``-||x_t / a_t - x_i||^2 / (2 sigma_t^2)``;
4. DDIM (eta = 0) with the x0 estimate clipped to ``[-clip, clip]``.

Distances are taken over the whole store and masked to each stage's
set (a threshold at the m_t-th and k_t-th smallest), which needs no
gather and touches each row once per step.  Rows are taken in blocks so
that the [block, N] distance matrices stay small.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import schedule as sched

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 64


def pool(x: jax.Array, image_shape: tuple, factor: int) -> jax.Array:
    """[rows, D] -> [rows, D / factor**2]: mean over factor x factor
    pixel blocks of each channel."""
    h, w, c = image_shape
    v = x.reshape(x.shape[0], h // factor, factor, w // factor, factor, c)
    return v.mean(axis=(2, 4)).reshape(x.shape[0], -1)


def terminal_noise(requests: list[tuple[int, int]], dim: int,
                   b_T: float) -> jax.Array:
    """x_T for each ``(seed, n)`` request: row i of a request is
    ``b_T * N(0, I)`` drawn from ``fold_in(PRNGKey(seed), i)``."""
    seeds = np.array([s for s, n in requests for _ in range(n)], np.int32)
    rows = np.array([i for _, n in requests for i in range(n)], np.int32)
    return _noise(jnp.asarray(seeds), jnp.asarray(rows), dim,
                  jnp.float32(b_T))


@partial(jax.jit, static_argnames=("dim",))
def _noise(seeds, rows, dim, b_T):
    def one(s, i):
        key = jax.random.fold_in(jax.random.PRNGKey(s), i)
        return jax.random.normal(key, (dim,), jnp.float32)
    return b_T * jax.vmap(one)(seeds, rows)


def _sqdist(q, xs, x_norms):
    return (jnp.sum(q * q, axis=-1, keepdims=True) + x_norms[None, :]
            - 2.0 * jnp.dot(q, xs.T, precision=HIGHEST))


def _kth_smallest(d, k):
    """[rows] value of the k-th smallest entry of each row (k traced)."""
    s = jnp.sort(d, axis=-1)
    return jnp.take_along_axis(s, jnp.broadcast_to(k - 1, (d.shape[0], 1)),
                               axis=-1)


@partial(jax.jit, static_argnames=("image_shape", "factor", "clip"))
def _trajectory(X, Xp, x_norms, p_norms, x, coef, ms, ks, image_shape,
                factor, clip):
    def step(x, i):
        a, b, a_next, b_next = coef[i, 0], coef[i, 1], coef[i, 2], coef[i, 3]
        sig2 = (b / a) ** 2
        q = x / a
        pd = _sqdist(pool(q, image_shape, factor), Xp, p_norms)
        cand = pd <= _kth_smallest(pd, ms[i])
        d2 = jnp.where(cand, _sqdist(q, X, x_norms), jnp.inf)
        gold = d2 <= _kth_smallest(d2, ks[i])
        logits = jnp.where(gold, -d2 / (2.0 * sig2), -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        x0 = jnp.clip(jnp.dot(w, X, precision=HIGHEST), -clip, clip)
        eps = (x - a * x0) / b
        return a_next * x0 + b_next * eps, None

    x, _ = jax.lax.scan(step, x, jnp.arange(ms.shape[0]))
    return x


class Reference:
    """The reference sampler for one configuration and store."""

    def __init__(self, config: dict, X: jax.Array):
        self.shape = tuple(int(s) for s in config["dataset"]["image_shape"])
        self.factor = int(config["golddiff"]["proxy_factor"])
        self.clip = float(config["sampling"]["clip"])
        self.X = X
        self.Xp = pool(X, self.shape, self.factor)
        self.x_norms = jnp.sum(X * X, axis=-1)
        self.p_norms = jnp.sum(self.Xp * self.Xp, axis=-1)
        steps = sched.steps(config)
        a, b = sched.coefficients(config["sampling"])
        self.b_T = float(b[steps[0].t])
        self.coef = jnp.asarray(
            [[a[s.t], b[s.t], a[s.t_next], b[s.t_next]] for s in steps],
            jnp.float32)
        self.ms = jnp.asarray([s.m for s in steps], jnp.int32)
        self.ks = jnp.asarray([s.k for s in steps], jnp.int32)

    def sample(self, requests: list[tuple[int, int]]) -> np.ndarray:
        """Final images [rows, D] for the ``(seed, n)`` requests, in
        order, computed ``BLOCK`` rows at a time."""
        x = terminal_noise(requests, self.X.shape[1], self.b_T)
        out = []
        for s in range(0, x.shape[0], BLOCK):
            blk = x[s: s + BLOCK]
            rows = blk.shape[0]
            if rows < BLOCK:
                blk = jnp.concatenate(
                    [blk, jnp.zeros((BLOCK - rows, blk.shape[1]), blk.dtype)])
            y = _trajectory(self.X, self.Xp, self.x_norms, self.p_norms, blk,
                            self.coef, self.ms, self.ks, self.shape,
                            self.factor, self.clip)
            out.append(np.asarray(y)[:rows])
        return np.concatenate(out)
