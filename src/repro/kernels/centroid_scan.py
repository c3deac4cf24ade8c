"""Pallas TPU kernel: tiled query -> centroid distances (IVF level 1).

First stage of indexed coarse screening: distances from each query's
proxy embedding to the C k-means centroids, in the MXU matmul form

    ||q - c||^2 = ||q||^2 + ||c||^2 - 2 q . c

with centroid norms precomputed once at index build (GoldenIndex).  The
centroid table is tiny (C ~ sqrt(N)), so unlike ``pdist`` — whose N
axis streams through VMEM in 512-wide tiles — the whole centroid tile
usually fits in one block; the default bc=128 keeps the lane dimension
MXU-aligned while letting multi-thousand-cluster indexes still tile.
Padded centroids carry +inf norms so their distances are +inf and the
probe top-k never selects them.  fp32 accumulation regardless of the
query/centroid storage dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import dot_f32

DEFAULT_BQ = 8
DEFAULT_BC = 128


def _centroid_kernel(q_ref, c_ref, qn_ref, cn_ref, out_ref):
    acc = dot_f32(q_ref[...], c_ref[...], ((1,), (1,)))
    d2 = qn_ref[...] + cn_ref[...] - 2.0 * acc
    out_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def centroid_scan(q: jnp.ndarray, centroids: jnp.ndarray,
                  c_norms: jnp.ndarray | None = None,
                  bq: int = DEFAULT_BQ, bc: int = DEFAULT_BC,
                  interpret: bool = False) -> jnp.ndarray:
    """||q_i - c_j||^2 for q: [B, d], centroids: [C, d] -> [B, C] fp32.

    ``interpret=True`` runs the kernel body on the CPU (validation only).
    """
    b, d = q.shape
    c = centroids.shape[0]
    if c_norms is None:
        c_norms = jnp.sum(centroids.astype(jnp.float32) ** 2, -1)
    q_norms = jnp.sum(q.astype(jnp.float32) ** 2, -1)

    bq = min(bq, b)
    bc = min(bc, c)
    pb = (-b) % bq
    pc = (-c) % bc
    qp = jnp.pad(q, ((0, pb), (0, 0)))
    cp = jnp.pad(centroids, ((0, pc), (0, 0)))
    qn = jnp.pad(q_norms, (0, pb)).reshape(-1, 1)
    # +inf norms on padded centroids -> +inf distance -> never probed
    cn = jnp.pad(c_norms.astype(jnp.float32), (0, pc),
                 constant_values=jnp.inf).reshape(1, -1)
    grid = ((b + pb) // bq, (c + pc) // bc)

    out = pl.pallas_call(
        _centroid_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bc), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b + pb, c + pc), jnp.float32),
        interpret=interpret,
    )(qp, cp, qn, cn)
    return out[:b, :c]
