"""Pallas TPU kernel: streaming-softmax weighted aggregation (Eq. 2).

The per-step hot loop of the analytical denoiser: a *single-query-class
attention* over the (golden) support where keys == values == training
points.  FlashAttention-style online softmax: the dataset streams through
VMEM in MXU-aligned tiles while a (max, denom, accumulator) carry lives in
scratch; logits come from the matmul distance form.  This is the
TPU-native replacement for the paper's CUDA streaming softmax (DESIGN §3).

out[b] = sum_i softmax_i( -(||q_b||^2 + ||x_i||^2 - 2 q_b.x_i) / (2 s2) ) x_i
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref
from repro.kernels.common import VMEM_LIMIT_BYTES, dot_f32, row_tile

NEG_INF = -1e30
DEFAULT_BQ = 8


def _agg_kernel(q_ref, x_ref, qn_ref, xn_ref, out_ref,
                m_ref, l_ref, acc_ref, *, inv_two_sigma2: float, nn: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    dot = dot_f32(q_ref[...], x, ((1,), (1,)))
    d2 = qn_ref[...] + xn_ref[...] - 2.0 * dot          # [bq, bn]
    # real rows clamp at the finite NEG_INF floor (extreme sigma -> a
    # uniform aggregate, never NaN); padded rows (d2 = +inf from the
    # +inf-norm pad) keep a hard -inf so they stay weightless even in
    # the all-clamped degenerate case
    logits = jnp.where(d2 == jnp.inf, -jnp.inf,
                       jnp.maximum(-d2 * inv_two_sigma2, NEG_INF))

    m_prev = m_ref[...]                                  # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(logits, -1, keepdims=True))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)                          # [bq, bn]
    l_ref[...] = l_ref[...] * scale + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * scale + dot_f32(p, x, ((1,), (0,)))
    m_ref[...] = m_new

    @pl.when(j == nn - 1)
    def _emit():
        out_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sigma2", "bq", "bn", "interpret"))
def golden_aggregate(q: jnp.ndarray, x: jnp.ndarray, sigma2: float,
                     x_norms: jnp.ndarray | None = None,
                     bq: int = DEFAULT_BQ, bn: int | None = None,
                     interpret: bool = False) -> jnp.ndarray:
    """Full-scan empirical-Bayes posterior mean.  q: [B, D], x: [N, D] -> [B, D].

    ``q`` must already be the rescaled query ``x_t / a_t``; ``sigma2`` is the
    noise-to-signal ratio sigma_t^2 (static: one program per timestep, the
    per-step-jit execution mode of DESIGN §3).  ``bn=None`` sizes the
    store tile to scoped VMEM (``common.row_tile``).
    """
    b, d = q.shape
    n = x.shape[0]
    if x_norms is None:
        x_norms = jnp.sum(x.astype(jnp.float32) ** 2, -1)
    q_norms = jnp.sum(q.astype(jnp.float32) ** 2, -1)

    bq = min(bq, b)
    bn = row_tile(d, n) if bn is None else min(bn, n)
    pb = (-b) % bq
    pn = (-n) % bn
    qp = jnp.pad(q, ((0, pb), (0, 0)))
    xp = jnp.pad(x, ((0, pn), (0, 0)))
    qn = jnp.pad(q_norms, (0, pb)).reshape(-1, 1)
    # +inf norm on padded rows -> -inf logits -> zero weight
    xn = jnp.pad(x_norms, (0, pn), constant_values=jnp.inf).reshape(1, -1)
    nb, nn = (b + pb) // bq, (n + pn) // bn

    out = pl.pallas_call(
        functools.partial(_agg_kernel,
                          inv_two_sigma2=ref.finite_inv_two_sigma2(sigma2),
                          nn=nn),
        grid=(nb, nn),
        in_specs=[
            pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b + pb, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),   # weighted accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(qp, xp, qn, xn)
    return out[:b]
