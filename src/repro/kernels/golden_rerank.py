"""Pallas TPU kernel: fused exact re-ranking distances over candidate tiles.

GoldDiff's precision stage (paper Eq. 5).  The seed implementation
materialized two ``[B, m, D]`` broadcast-subtract temporaries
(``(q[:, None] - xs) ** 2`` and its square); here distances are computed
in the MXU-friendly matmul form over gathered candidate tiles

    ||q_b - x_c||^2 = ||q_b||^2 + ||x_c||^2 - 2 q_b . x_c

with dataset row norms *gathered* (O(B m) scalars, precomputed once per
dataset in ``DatasetStore``) instead of recomputed, and fp32
accumulation regardless of the storage dtype.

Every query owns a different candidate set, so the grid walks one query
per row and one ``(bm, D)`` candidate tile per column: the kernel body
is a ``(1, D) . (D, bm)`` contraction plus rank-1 adds.  (Mosaic cannot
lower a batched 3-D contraction over a ``(bq, bm, D)`` block, and such a
block would not fit scoped VMEM at D=3072 anyway.)  ``bm`` comes from
``common.row_tile``; the candidate list is padded to a tile multiple
*before* the row gather, so no ``[B, m, D]`` padding copy exists.

The ops-layer ``golden_rerank`` wrapper adds the top-k and returns the
selected indices *and their distances*, so downstream aggregation reuses
selection distances instead of recomputing them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_LIMIT_BYTES, dot_f32, row_tile


def _sqdist_kernel(q_ref, xs_ref, xn_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)                         # [1, D]
    qn = jnp.sum(q * q, -1, keepdims=True)                     # [1, 1]
    dot = dot_f32(q, xs_ref[...], ((1,), (1,)))                # [1, bm]
    # +inf norms (masked/padded rows) propagate to +inf distances
    out_ref[...] = jnp.maximum(qn + xn_ref[...] - 2.0 * dot, 0.0)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def support_sqdist(q: jnp.ndarray, x: jnp.ndarray, idx: jnp.ndarray,
                   x_norms: jnp.ndarray, bm: int | None = None,
                   interpret: bool = False) -> jnp.ndarray:
    """Exact distances from each query to its own candidate rows.

    q: [B, D], x: [N, D] (the store), idx: [B, M] row ids, x_norms: [N]
    (``||x||^2``) -> [B, M] fp32.  ``interpret=True`` runs the kernel
    body on the CPU (validation only).
    """
    b, d = q.shape
    m = idx.shape[1]
    bm = row_tile(d, m) if bm is None else min(bm, m)
    pm = (-m) % bm
    idxp = jnp.pad(idx, ((0, 0), (0, pm)))       # pad slots gather row 0
    xs = x[idxp]                                 # [B, Mp, D]
    xn = x_norms.astype(jnp.float32)[idxp][:, None, :]

    out = pl.pallas_call(
        _sqdist_kernel,
        grid=(b, (m + pm) // bm),
        in_specs=[
            pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, bm, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, 1, bm), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, bm), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, m + pm), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(q[:, None, :], xs, xn)
    return out[:, 0, :m]
