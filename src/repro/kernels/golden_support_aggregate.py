"""Pallas TPU kernel: streaming softmax aggregation over golden supports.

The support-set sibling of ``golden_aggregate`` (which scans the whole
dataset): values here are each query's golden rows ``x[idx[b]]`` (k rows
per query, selected upstream by ``golden_rerank``) and the logits are
**reused from selection** rather than recomputed — the fused step the
seed was missing (it regathered ``X[idx]`` and recomputed
``(q - xs)**2`` for the final softmax).

FlashAttention-style online softmax (Dao et al., 2022): the grid walks
one ``(query, tile)`` pair per step, in order, and each query's support
streams through VMEM in ``(bk, D)`` tiles while a (max, denom,
accumulator) carry lives in scratch; the weighted sum per tile is an
fp32 VPU sum of the rows scaled by their weights, which take a column
``(bk, 1)`` (on a v5e at CIFAR-10 width 1.5 ms a call against 1.75 ms
for the ``(1, bk) . (bk, D)`` MXU contraction at ``HIGHEST``).  The
tiles are fetched in-kernel from the ``[N, 1, D]`` store in
HBM, one row DMA per support slot, double-buffered across grid steps
(``common.fetch_tile``), so no ``[B, k, D]`` copy of the support
exists.  fp32 accumulation regardless of the storage dtype.  ``bk``
comes from ``common.fetch_tile_rows``; pad slots fetch row 0 and carry
a hard ``-inf`` logit, so they have exactly zero weight even when every
real logit sits at the finite ``NEG_INF`` floor (that row then averages
its real rows).  A 16-bit store cannot be fetched a row at a time (XLA
lays it out in ``[N, D]`` tiles): its support is gathered by XLA, as
before the fetch, and read in ``(bk, D)`` blocks
(``common.candidate_rows``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import (VMEM_LIMIT_BYTES, candidate_rows,
                                  candidate_tile, check_rows,
                                  fetch_tile_rows)

NEG_INF = -1e30


def _sagg_kernel(ids_ref, lg_ref, x_ref, out_ref, m_ref, l_ref, acc_ref,
                 *fetch, nk: int):
    j = pl.program_id(0) % nk
    xs = candidate_tile(ids_ref, x_ref, fetch)          # [bk, D] f32

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lg = lg_ref[...]                                    # [bk, 1] f32
    m_prev = m_ref[...]                                 # [1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(lg, 0, keepdims=True))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(lg - m_new)                             # [bk, 1]
    l_ref[...] = l_ref[...] * scale + jnp.sum(p, 0, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale + jnp.sum(xs * p, 0, keepdims=True)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def golden_support_aggregate(x: jnp.ndarray, idx: jnp.ndarray,
                             logits: jnp.ndarray, bk: int | None = None,
                             interpret: bool = False) -> jnp.ndarray:
    """softmax(logits)-weighted mean of each query's support rows.

    x: [N, 1, D] (the store rows), idx: [B, K] row ids, logits: [B, K]
    (validity masking — e.g. the scan-compatible k_t mask — is applied
    by the caller as NEG_INF entries) -> [B, D] fp32.
    """
    check_rows(x)
    b, k = idx.shape
    d = x.shape[-1]
    bk = fetch_tile_rows(d, k, bk)
    pk = (-k) % bk
    idxp = jnp.pad(idx.astype(jnp.int32), ((0, 0), (0, pk)))  # pads: row 0
    xs, x_spec, fetch = candidate_rows(x, idxp, bk)
    lgp = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, pk)),
                  constant_values=-jnp.inf)[:, :, None]
    nk = (k + pk) // bk

    out = pl.pallas_call(
        functools.partial(_sagg_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * nk,),
            in_specs=[
                pl.BlockSpec((None, bk, 1),
                             lambda s, ids: (s // nk, s % nk, 0)),
                x_spec,
            ],
            out_specs=pl.BlockSpec((None, 1, d),
                                   lambda s, ids: (s // nk, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, 1), jnp.float32),    # running max
                pltpu.VMEM((1, 1), jnp.float32),    # running denom
                pltpu.VMEM((1, d), jnp.float32),    # weighted accumulator
            ] + fetch),
        out_shape=jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(idxp.reshape(-1), lgp, xs)
    return out[:, 0, :]
