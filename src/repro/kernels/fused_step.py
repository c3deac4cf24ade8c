"""Fused GoldDiff step: screen + re-rank + aggregate in ONE store pass.

The staged engine runs a denoise step as separate programs — coarse
proxy screen, exact re-rank, softmax aggregation — each round-tripping
candidates through HBM (the PR 7 roofline pins the exact screen at
~0.01 of peak bytes/s for exactly this reason).  This module fuses the
step: store tiles stream through once, each tile contributes its proxy
distances AND its exact distances, and a running top-m carry threads
both through the same selection, so by the end of the single pass the
carry holds the staged pipeline's candidate set *with its re-rank
distances already attached*.  A small epilogue (top-k + clamped logits
+ gathered online-softmax aggregate over the k golden rows) turns that
carry straight into the posterior mean — no second read of the store,
no [B, N] re-rank matrix, and no [B, m, D] candidate materialization.
Peak live memory is O(B * (m + tile)) + the k aggregated rows.

Selection math is ``kernels.screen``'s carry-first tie merge extended
with one more threaded operand: the concatenation [carry | tile] is
re-selected by ONE ``lax.top_k`` on the negated proxy distances, and
``take_along_axis`` carries (index, exact d2) pairs along.  Because the
proxy keys and merge order are identical to ``screen_topm_scan``, the
fused candidate list — and therefore the epilogue's top-k input — is
bit-for-bit the staged screen's output; the exact distances are
computed by the same clamped matmul form as ``ref.pdist_ref`` (the d
contraction is unaffected by N tiling), so fused-vs-staged agree to
fp32 *reduction order* (the aggregation sums in gathered instead of
scattered order), ~1e-7.

``fused_candidates_scan`` runs the pass as a ``lax.scan`` over store
tiles (ragged tails overlap back, re-seen columns masked; no padded
copy) on every backend, the TPU included: like ``kernels.screen``, the
merge needs ``top_k``, which Mosaic cannot lower inside a Pallas
kernel.  ``fused_posterior`` is the epilogue — on the pallas backends
its aggregate is the ``golden_support_aggregate`` kernel;
``ops.fused_step`` is the dispatching entry point (it also provides the
materialized form used below the streamed-screen byte crossover).

Slot semantics (shared with ``ops.screen_topm``): ``m > N`` surplus
slots carry exact ``d2 = +inf`` and a clamped in-range index, so they
re-rank last and aggregate with exactly zero weight — *not* the
staged dense path's aliased row-0 distances, which only stays correct
because the engine never schedules m > N.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.common import dot_rows, gather_rows, row_sq_norms
from repro.kernels.golden_support_aggregate import (
    golden_support_aggregate as _sagg)

NEG_INF = -1e30
# Scan-path default tile for the FUSED pass.  Unlike the proxy-only
# screen scan (``screen.SCAN_TILE`` = 16384, dp ~ 49), every fused tile
# carries the exact [B, tile] GEMM over the full D, so the working set
# per tile is ~16x larger and wants to stay cache-resident: measured on
# XLA:CPU at D=784, B=32 the fused step runs 199/303 ms (m=512/1024) at
# tile=2048 vs 597/552 ms at 16384 for N=65536, and 29 vs 43 ms at
# N=4096 — tile=2048 wins at both scales.
FUSED_SCAN_TILE = 2048


def _merge_topm_carry(vals, idx, ex, neg_tile, idx_tile, ex_tile, m: int):
    """Running top-m step threading (index, exact-d2) with the selection.

    Same carry-first concatenation as ``screen._merge_topm`` (ties go to
    the lowest dataset index, matching ``lax.top_k``), with the exact
    distances re-gathered by the same ``sel`` so every carried slot
    keeps its re-rank key.
    """
    cat_v = jnp.concatenate([vals, neg_tile], axis=-1)
    cat_i = jnp.concatenate([idx, idx_tile], axis=-1)
    cat_e = jnp.concatenate([ex, ex_tile], axis=-1)
    new_v, sel = jax.lax.top_k(cat_v, m)
    return (new_v, jnp.take_along_axis(cat_i, sel, axis=-1),
            jnp.take_along_axis(cat_e, sel, axis=-1))


def _tile_d2(q, xt, qn, xnt):
    """Clamped matmul-form squared distances for one tile (fp32)."""
    return jnp.maximum(qn + xnt[None, :] - 2.0 * dot_rows(q, xt), 0.0)


# -- lax.scan candidate pass -------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "tile"))
def fused_candidates_scan(qp: jnp.ndarray, q: jnp.ndarray,
                          proxy: jnp.ndarray, x: jnp.ndarray, m: int,
                          proxy_norms: jnp.ndarray | None = None,
                          x_norms: jnp.ndarray | None = None,
                          tile: int | None = None):
    """One-pass screened candidates with exact distances attached.

    qp: [B, dp] proxy queries, q: [B, D] exact queries; proxy: [N, dp],
    x: [N, 1, D] (the store rows; each tile is contracted on its last
    axis, not reshaped) -> ``(idx, d2)`` [B, m]: the proxy top-m candidate list
    (ascending proxy distance, ``lax.top_k`` tie order) with each
    slot's EXACT squared distance.  Surplus slots (m > N) carry
    ``d2 = +inf`` and clamped indices.

    Same ragged-tail handling as ``screen_topm_scan``: the final tile
    overlaps back (``dynamic_slice`` clamp) with re-seen proxy keys
    masked to -inf, so no padded store copy exists for any N.  Peak
    live memory O(B * (m + tile)); ``tile=None`` picks the fused-pass
    default ``FUSED_SCAN_TILE`` (smaller than the proxy screen's —
    each fused tile carries the full-D exact GEMM).
    """
    n = x.shape[0]
    if tile is None:
        tile = FUSED_SCAN_TILE
    b = qp.shape[0]
    qp32 = qp.astype(jnp.float32)
    q32 = q.astype(jnp.float32)
    qpn = jnp.sum(qp32 ** 2, -1)[:, None]
    qn = jnp.sum(q32 ** 2, -1)[:, None]
    if proxy_norms is None:
        proxy_norms = jnp.sum(proxy.astype(jnp.float32) ** 2, -1)
    if x_norms is None:
        x_norms = row_sq_norms(x)
    proxy_norms = proxy_norms.astype(jnp.float32)
    x_norms = x_norms.astype(jnp.float32)
    tile = min(tile, max(n, 1))

    def body(carry, start):
        vals, idx, ex = carry
        eff = jnp.minimum(start, n - tile)     # ragged tail: overlap back
        xpt = jax.lax.dynamic_slice_in_dim(proxy, eff, tile
                                           ).astype(jnp.float32)
        xpnt = jax.lax.dynamic_slice_in_dim(proxy_norms, eff, tile)
        xt = jax.lax.dynamic_slice_in_dim(x, eff, tile).astype(jnp.float32)
        xnt = jax.lax.dynamic_slice_in_dim(x_norms, eff, tile)
        pd2 = _tile_d2(qp32, xpt, qpn, xpnt)
        ed2 = _tile_d2(q32, xt, qn, xnt)
        cols = eff + jax.lax.broadcasted_iota(jnp.int32, pd2.shape, 1)
        neg = jnp.where(cols >= start, -pd2, -jnp.inf)  # mask re-seen rows
        return _merge_topm_carry(vals, idx, ex, neg, cols, ed2, m), None

    init = (jnp.full((b, m), -jnp.inf, jnp.float32),
            jnp.zeros((b, m), jnp.int32),
            jnp.full((b, m), jnp.inf, jnp.float32))
    (vals, idx, ex), _ = jax.lax.scan(
        body, init,
        jnp.arange(0, -(-n // tile) * tile, tile, dtype=jnp.int32))
    return jnp.minimum(idx, max(n - 1, 0)), ex


# -- shared epilogue ----------------------------------------------------------

def fused_posterior(x: jnp.ndarray, idx: jnp.ndarray, d2: jnp.ndarray,
                    k: int, sigma2, backend: str,
                    m_t=None, k_t=None, interpret: bool = False,
                    strategy: str | None = None) -> jnp.ndarray:
    """Candidates + exact distances -> posterior mean [B, D] fp32.

    The O(B * (m + k D)) tail of the fused step: exact top-k inside the
    candidate list, clamped logits, and a softmax aggregate over only
    the k golden rows — the store is never re-read densely.

    ``strategy`` picks the xla aggregation form exactly like
    ``ops.golden_support_aggregate``: "gather" (the default — row
    gather + einsum, sublinear in N, the streaming story) or "dense"
    (scatter + [B, N] GEMM — on XLA:CPU the [B, k, D] row gather is
    the slowest op in the whole step, so dense-strategy engines keep
    their scatter form; it is the same op the staged body runs, which
    also keeps fused-vs-staged sharded parity bitwise).

    ``sigma2`` may be a traced scalar (the masked path); ``m_t`` /
    ``k_t`` (optional traced scalars) mask candidate slots at or past
    the scheduled sizes, exactly like the engine's staged masked body:
    slots >= ``m_t`` re-rank at +inf, logit slots >= ``k_t`` clamp to
    the finite ``NEG_INF`` sentinel (an all-masked row degrades to a
    uniform average of its gathered rows, never NaN).
    """
    if m_t is not None:
        live = jnp.arange(d2.shape[-1])[None, :] < m_t
        d2 = jnp.where(live, d2, jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k)
    gid = jnp.take_along_axis(idx, pos, axis=-1)
    lg = jnp.maximum(neg / (2.0 * sigma2), NEG_INF)
    if k_t is not None:
        lg = jnp.where(jnp.arange(k)[None, :] < k_t, lg, NEG_INF)
    if backend == "xla":
        if (strategy or "gather") == "dense":
            return ref.scatter_aggregate_ref(x, gid, lg)
        return ref.golden_support_aggregate_ref(gather_rows(x, gid), lg)
    return _sagg(x, gid, lg, interpret=interpret)
