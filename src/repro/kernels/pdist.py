"""Pallas TPU kernel: tiled pairwise squared distances (coarse screening).

The O(N d) proxy-screening term of GoldDiff (paper Tab. 1).  Distances are
computed in the MXU-friendly matmul form

    ||q - x||^2 = ||q||^2 + ||x||^2 - 2 q . x

with row norms precomputed once per dataset (DatasetStore), so the kernel
body is a single (bq x d) @ (d x bn) matmul per tile plus rank-1 adds.
Tiles are MXU-aligned (multiples of 128 on the contracted/output dims);
fp32 accumulation regardless of input dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import dot_f32

DEFAULT_BQ = 128
DEFAULT_BN = 512


def _pdist_kernel(q_ref, x_ref, qn_ref, xn_ref, out_ref):
    acc = dot_f32(q_ref[...], x_ref[...], ((1,), (1,)))
    d2 = qn_ref[...] + xn_ref[...] - 2.0 * acc
    out_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def pdist(q: jnp.ndarray, x: jnp.ndarray,
          q_norms: jnp.ndarray | None = None,
          x_norms: jnp.ndarray | None = None,
          bq: int = DEFAULT_BQ, bn: int = DEFAULT_BN,
          interpret: bool = False) -> jnp.ndarray:
    """||q_i - x_j||^2 for q: [B, d], x: [N, d] -> [B, N] (fp32).

    ``interpret=True`` runs the kernel body on the CPU (validation only).
    """
    b, d = q.shape
    n = x.shape[0]
    if q_norms is None:
        q_norms = jnp.sum(q.astype(jnp.float32) ** 2, -1)
    if x_norms is None:
        x_norms = jnp.sum(x.astype(jnp.float32) ** 2, -1)

    bq = min(bq, b)
    bn = min(bn, n)
    pb = (-b) % bq
    pn = (-n) % bn
    qp = jnp.pad(q, ((0, pb), (0, 0)))
    xp = jnp.pad(x, ((0, pn), (0, 0)))
    qn = jnp.pad(q_norms, (0, pb)).reshape(-1, 1)
    xn = jnp.pad(x_norms, (0, pn)).reshape(1, -1)
    grid = ((b + pb) // bq, (n + pn) // bn)

    out = pl.pallas_call(
        _pdist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(((b + pb), (n + pn)), jnp.float32),
        interpret=interpret,
    )(qp, xp, qn, xn)
    return out[:b, :n]
