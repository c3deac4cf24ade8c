"""Pallas TPU kernel: exact re-ranking distances over rows it fetches.

GoldDiff's precision stage (paper Eq. 5).  Distances take the matmul
form

    ||q_b - x_c||^2 = ||q_b||^2 + ||x_c||^2 - 2 q_b . x_c

with dataset row norms *gathered* (O(B m) scalars, precomputed once per
dataset in ``DatasetStore``) instead of recomputed, and fp32
accumulation regardless of the storage dtype.

Every query owns a different candidate set, so the grid walks one
``(query, tile)`` pair per step, in order, and the kernel reads each
candidate row straight from the ``[N, 1, D]`` store in HBM: the padded
ids reach SMEM by scalar prefetch, and ``common.fetch_tile`` issues one
row DMA per candidate into a double-buffered ``(bm, 1, D)`` VMEM tile,
starting the next step's rows before this step's contraction.  No
``[B, m, D]`` copy of the candidates exists.  The ``q . x_c`` sums run
on the VPU in fp32 (``_row_dots``): with one query row the MXU at
``HIGHEST`` takes six bf16 passes for little work, and on a v5e at
CIFAR-10 width the call took 3.6 ms this way against 4.5 ms.  The
norms are the one gather left to XLA: it costs less than computing them
from the fetched rows (4.5 ms against 5.5 ms a call, both MXU forms),
and a stored ``+inf`` norm (a padded row) gives a ``+inf`` distance.
``bm`` comes from ``common.fetch_tile_rows``; pad slots fetch row 0 and
are sliced off.  A 16-bit store cannot be fetched a row at a time (XLA
lays it out in ``[N, D]`` tiles): its candidates are gathered by XLA,
as before the fetch, and read in ``(bm, D)`` blocks
(``common.candidate_rows``).

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

from repro.kernels.common import (LANE, VMEM_LIMIT_BYTES, candidate_rows,
                                  candidate_tile, fetch_tile_rows)


def _row_dots(xs, q):
    """``(1, bm)``: each row of ``xs`` [bm, D] dotted with ``q`` [1, D],
    in fp32 on the VPU — 128-lane partial sums, one ``(bm, 128)``
    transpose, a sublane sum."""
    prod = xs * q
    d = prod.shape[1]
    if d % LANE:                    # a toy width: one lane reduction
        return jnp.sum(prod, 1)[None, :]
    part = prod[:, :LANE]
    for c in range(LANE, d, LANE):
        part = part + prod[:, c:c + LANE]
    return jnp.sum(part.T, 0, keepdims=True)


def _sqdist_kernel(ids_ref, q_ref, xn_ref, x_ref, out_ref, *fetch):
    xs = candidate_tile(ids_ref, x_ref, fetch)                 # [bm, D]
    q = q_ref[...].astype(jnp.float32)                         # [1, D]
    qn = jnp.sum(q * q, -1, keepdims=True)                     # [1, 1]
    dot = _row_dots(xs, q)                                     # [1, bm]
    # +inf norms (masked/padded rows) propagate to +inf distances
    out_ref[...] = jnp.maximum(qn + xn_ref[...] - 2.0 * dot, 0.0)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def support_sqdist(q: jnp.ndarray, x: jnp.ndarray, idx: jnp.ndarray,
                   x_norms: jnp.ndarray, bm: int | None = None,
                   interpret: bool = False) -> jnp.ndarray:
    """Exact distances from each query to its own candidate rows.

    q: [B, D], x: [N, 1, D] (the store rows; or a 2-D table, such as
    the proxy, whose rows are gathered by XLA), idx: [B, M] row ids,
    x_norms: [N] (``||x||^2``) -> [B, M] fp32.  ``interpret=True`` runs
    the kernel body on the CPU (validation only).
    """
    b, d = q.shape
    m = idx.shape[1]
    bm = fetch_tile_rows(d, m, bm)
    pm = (-m) % bm
    idxp = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, pm)))  # pads: row 0
    xn = x_norms.astype(jnp.float32)[idxp][:, None, :]
    xs, x_spec, fetch = candidate_rows(x, idxp, bm)
    tiles = (m + pm) // bm

    out = pl.pallas_call(
        _sqdist_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * tiles,),
            in_specs=[
                pl.BlockSpec((None, 1, d), lambda s, ids: (s // tiles, 0, 0)),
                pl.BlockSpec((None, 1, bm),
                             lambda s, ids: (s // tiles, 0, s % tiles)),
                x_spec,
            ],
            out_specs=pl.BlockSpec((None, 1, bm),
                                   lambda s, ids: (s // tiles, 0, s % tiles)),
            scratch_shapes=fetch),
        out_shape=jax.ShapeDtypeStruct((b, 1, m + pm), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idxp.reshape(-1), q[:, None, :], xn, xs)
    return out[:, 0, :m]
