"""Pallas TPU kernel: streaming softmax aggregation over golden supports.

The support-set sibling of ``golden_aggregate`` (which scans the whole
dataset): values here are each query's golden rows ``x[idx[b]]`` (k rows
per query, selected upstream by ``golden_rerank``) and the logits are
**reused from selection** rather than recomputed — the fused step the
seed was missing (it regathered ``X[idx]`` and recomputed
``(q - xs)**2`` for the final softmax).

FlashAttention-style online softmax (Dao et al., 2022): one query per
grid row, its support streaming through VMEM in ``(bk, D)`` tiles while
a (max, denom, accumulator) carry lives in scratch; the weighted sum per
tile is one ``(1, bk) . (bk, D)`` contraction (Mosaic cannot lower the
batched 3-D form).  fp32 accumulation regardless of the storage dtype.
``bk`` comes from ``common.row_tile``; the support is padded to a tile
multiple before the row gather, and padded slots carry a hard ``-inf``
logit, so they have exactly zero weight even when every real logit sits
at the finite ``NEG_INF`` floor (that row then averages its real rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import VMEM_LIMIT_BYTES, dot_f32, row_tile

NEG_INF = -1e30


def _sagg_kernel(lg_ref, xs_ref, out_ref, m_ref, l_ref, acc_ref, *, nk: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lg = lg_ref[...]                                    # [1, bk] f32
    m_prev = m_ref[...]                                 # [1, 1]
    m_new = jnp.maximum(m_prev, jnp.max(lg, -1, keepdims=True))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(lg - m_new)                             # [1, bk]
    l_ref[...] = l_ref[...] * scale + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale + dot_f32(p, xs_ref[...],
                                                  ((1,), (0,)))
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _emit():
        out_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def golden_support_aggregate(x: jnp.ndarray, idx: jnp.ndarray,
                             logits: jnp.ndarray, bk: int | None = None,
                             interpret: bool = False) -> jnp.ndarray:
    """softmax(logits)-weighted mean of each query's support rows.

    x: [N, D] (the store), idx: [B, K] row ids, logits: [B, K]
    (validity masking — e.g. the scan-compatible k_t mask — is applied
    by the caller as NEG_INF entries) -> [B, D] fp32.
    """
    b, k = idx.shape
    d = x.shape[1]
    bk = row_tile(d, k) if bk is None else min(bk, k)
    pk = (-k) % bk
    xs = x[jnp.pad(idx, ((0, 0), (0, pk)))]             # [B, Kp, D]
    lgp = jnp.pad(logits.astype(jnp.float32), ((0, 0), (0, pk)),
                  constant_values=-jnp.inf)[:, None, :]
    nk = (k + pk) // bk

    out = pl.pallas_call(
        functools.partial(_sagg_kernel, nk=nk),
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((None, 1, bk), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),    # running max
            pltpu.VMEM((1, 1), jnp.float32),    # running denom
            pltpu.VMEM((1, d), jnp.float32),    # weighted accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(lgp, xs)
    return out[:, 0, :]
