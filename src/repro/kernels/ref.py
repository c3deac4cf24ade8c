"""Pure-jnp oracles for every Pallas kernel (tests assert allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import HIGHEST, dot_f32

NEG_INF = -1e30

# Largest inverse-temperature the aggregation softmax will use: chosen
# so ``-d2 * inv`` stays an ordinary fp32 overflow (clamped at NEG_INF)
# instead of the silent-NaN ``0 * inf`` that an unguarded ``1/(2*0.0)``
# produces.  3e37 < fp32 max, and any sigma2 small enough to hit the
# clamp already drives every finite logit to the NEG_INF floor.
MAX_INV_TWO_SIGMA2 = 3.0e37


def finite_inv_two_sigma2(sigma2) -> float:
    """``1 / (2 sigma2)`` clamped to an fp32-finite inverse temperature.

    Degenerate noise levels (``sigma2 <= 0``, NaN, or denormal) return
    the finite ``MAX_INV_TWO_SIGMA2`` cap instead of raising
    ``ZeroDivisionError`` or overflowing to +inf — callers pair the
    result with a ``NEG_INF`` logit clamp, so the extreme-sigma limit
    degrades to a uniform (data-mean) aggregate, never NaN.
    """
    s = float(sigma2)
    if not s > 0.0:                      # 0, negative, or NaN
        return MAX_INV_TWO_SIGMA2
    inv = 1.0 / (2.0 * s)
    return min(inv, MAX_INV_TWO_SIGMA2)


def pdist_ref(q: jnp.ndarray, x: jnp.ndarray,
              q_norms: jnp.ndarray | None = None,
              x_norms: jnp.ndarray | None = None) -> jnp.ndarray:
    """Matmul-form pairwise squared distances; accepts precomputed row
    norms (e.g. +inf on padded/masked dataset rows -> +inf distance)."""
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    qn = jnp.sum(q * q, -1) if q_norms is None else q_norms.astype(jnp.float32)
    xn = jnp.sum(x * x, -1) if x_norms is None else x_norms.astype(jnp.float32)
    d2 = qn[:, None] + xn[None, :] - 2.0 * dot_f32(q, x, ((1,), (1,)))
    return jnp.maximum(d2, 0.0)


def materialized_topm(d2: jnp.ndarray, m: int
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-m of a materialized [B, N] distance matrix with the shared
    slot semantics: ``(idx, d2)`` ascending; ``m > N`` surplus slots
    carry ``d2 = +inf`` and an in-range index.  The ONE definition of
    the materialized-screen contract — both ``screen_topm_ref`` and the
    pallas-pdist materialized path of ``ops.screen_topm`` route here.
    """
    n = d2.shape[-1]
    k = min(m, n)
    neg, idx = jax.lax.top_k(-d2, k)
    if m > k:
        pad = ((0, 0), (0, m - k))
        neg = jnp.pad(neg, pad, constant_values=-jnp.inf)
        idx = jnp.pad(idx, pad)
    return idx, -neg


def screen_topm_ref(q: jnp.ndarray, x: jnp.ndarray, m: int,
                    q_norms: jnp.ndarray | None = None,
                    x_norms: jnp.ndarray | None = None
                    ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materialized top-m oracle: full [B, N] pdist + one ``lax.top_k``.

    This is both the parity oracle for ``kernels.screen`` and the dense
    path the engine keeps below the streamed-vs-materialized crossover.
    """
    return materialized_topm(pdist_ref(q, x, q_norms, x_norms), m)


def support_sqdist_ref(q: jnp.ndarray, xs: jnp.ndarray,
                       x_norms: jnp.ndarray | None = None) -> jnp.ndarray:
    """Distances to per-query gathered rows.  q: [B, D], xs: [B, M, D],
    x_norms: [B, M] -> [B, M] fp32 (matmul form, no [B, M, D] temporaries)."""
    q32 = q.astype(jnp.float32)
    xs32 = xs.astype(jnp.float32)
    xn = (jnp.sum(xs32 * xs32, -1) if x_norms is None
          else x_norms.astype(jnp.float32))
    qn = jnp.sum(q32 * q32, -1, keepdims=True)
    dot = jnp.einsum("bd,bmd->bm", q32, xs32, precision=HIGHEST)
    return jnp.maximum(qn + xn - 2.0 * dot, 0.0)


def golden_aggregate_ref(q: jnp.ndarray, x: jnp.ndarray, sigma2: float,
                         x_norms: jnp.ndarray | None = None) -> jnp.ndarray:
    # Logits clamp at the finite NEG_INF sentinel (matching the Pallas
    # kernel and the streamed LSE): an all-clamped row — every distance
    # overflowed at extreme sigma — softmaxes to a uniform (data-mean)
    # aggregate instead of the NaN an all--inf softmax produces.
    inv = finite_inv_two_sigma2(sigma2)
    lg = jnp.maximum(-pdist_ref(q, x, x_norms=x_norms) * inv, NEG_INF)
    w = jax.nn.softmax(lg, axis=-1)
    return dot_f32(w, x, ((1,), (0,))).astype(q.dtype)


def scatter_aggregate_ref(x: jnp.ndarray, idx: jnp.ndarray,
                          logits: jnp.ndarray) -> jnp.ndarray:
    """softmax(logits)-weighted mean of x[idx] per query -> [B, D] fp32.

    Dense scatter + GEMM form: on XLA:CPU row gathers run ~50x slower
    per element than GEMM, so scattering the k weights into a [B, N]
    matrix and multiplying by the (contiguous) dataset is much faster
    than gathering [B, k, D] rows.  ``.add`` handles duplicate indices
    exactly (their weights sum, as in the gathered formulation).
    """
    b, n = logits.shape[0], x.shape[0]
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    ws = jnp.zeros((b, n), jnp.float32).at[
        jnp.arange(b)[:, None], idx].add(w)
    return dot_f32(ws, x, ((1,), (0,)))


def golden_support_aggregate_ref(xs: jnp.ndarray,
                                 logits: jnp.ndarray) -> jnp.ndarray:
    """Gathered-values oracle for the Pallas support-aggregate kernel."""
    w = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.einsum("bk,bkd->bd", w, xs.astype(jnp.float32),
                      precision=HIGHEST)


def partial_aggregate_ref(xs: jnp.ndarray, logits: jnp.ndarray
                          ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Unnormalized softmax partial state over gathered rows.

    Returns ``(acc [B, D], m [B], l [B])``: the exp-weighted sum, the
    max logit, and the partition sum — ``streaming.merge`` semantics, so
    shard-partial states combine exactly with a log-sum-exp merge
    (``sharding.lse_merge_mean``).  All-masked rows (every logit at the
    finite NEG_INF sentinel) yield a NEG_INF max whose merge scale
    underflows to 0, not NaN.
    """
    lg = logits.astype(jnp.float32)
    m = jnp.max(lg, axis=-1)
    p = jnp.exp(lg - m[:, None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bk,bkd->bd", p, xs.astype(jnp.float32),
                     precision=HIGHEST)
    return acc, m, l


def scatter_partial_aggregate_ref(x: jnp.ndarray, idx: jnp.ndarray,
                                  logits: jnp.ndarray
                                  ) -> tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """Dense scatter + GEMM form of :func:`partial_aggregate_ref` (the
    XLA:CPU-fast shape: no [B, k, D] row gathers)."""
    b, n = logits.shape[0], x.shape[0]
    lg = logits.astype(jnp.float32)
    m = jnp.max(lg, axis=-1)
    p = jnp.exp(lg - m[:, None])
    l = jnp.sum(p, axis=-1)
    ws = jnp.zeros((b, n), jnp.float32).at[
        jnp.arange(b)[:, None], idx].add(p)
    return dot_f32(ws, x, ((1,), (0,))), m, l


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        causal: bool = True) -> jnp.ndarray:
    """q: [B,Hkv,G,S,dh]; k/v: [B,Hkv,S,dh] — dense softmax attention."""
    dh = q.shape[-1]
    s = q.shape[3]
    scores = jnp.einsum("bhgqd,bhkd->bhgqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * dh ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgqk,bhkd->bhgqd", w,
                      v.astype(jnp.float32)).astype(q.dtype)


def golden_attention_decode_ref(q: jnp.ndarray, k: jnp.ndarray,
                                v: jnp.ndarray, block_idx: jnp.ndarray,
                                valid: jnp.ndarray,
                                block_size: int = 128) -> jnp.ndarray:
    """Gather golden blocks densely, mask invalid, softmax-attend."""
    b, hkv, g, dh = q.shape
    s = k.shape[2]
    kb = block_idx.shape[-1]
    nb = s // block_size
    idx = jnp.clip(block_idx, 0, nb - 1)
    kblk = k.reshape(b, hkv, nb, block_size, dh)
    vblk = v.reshape(b, hkv, nb, block_size, dh)
    kg = jnp.take_along_axis(kblk, idx[..., None, None].repeat(block_size, -2)
                             .repeat(dh, -1), axis=2)           # [B,H,kb,Bs,dh]
    vg = jnp.take_along_axis(vblk, idx[..., None, None].repeat(block_size, -2)
                             .repeat(dh, -1), axis=2)
    kg = kg.reshape(b, hkv, kb * block_size, dh).astype(jnp.float32)
    vg = vg.reshape(b, hkv, kb * block_size, dh).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhsd->bhgs", q.astype(jnp.float32), kg)
    scores = scores / (dh ** 0.5)
    mask = jnp.repeat(valid.astype(bool), block_size, axis=-1)   # [B,H,kb*Bs]
    scores = jnp.where(mask[:, :, None, :], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", w, vg).astype(q.dtype)
