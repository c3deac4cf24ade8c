"""Streaming one-pass screening: fused tiled pdist + running top-m.

The coarse stage of GoldDiff screens every dataset row, and the
materialized form (``ops.pdist`` -> ``lax.top_k``) allocates the full
``[B, N]`` proxy-distance matrix and sorts all N columns — at
ImageNet-1K scale that buffer IS the memory wall.  This module removes
it: the store streams through in N-tiles, each tile's distances are
computed in the MXU matmul form, and a running top-m carry
(values + indices) is merged per tile, so peak live memory is
O(B * (m + tile)) instead of O(B * N) and the store is read exactly
once.

Merge math (the same two-stage trick as the cross-shard top-k in
``distributed/sharding.py``, applied across tiles instead of shards):
the carry holds the m best negated distances seen so far; each tile
contributes its ``tile`` raw candidates and ONE ``lax.top_k`` over the
``[B, m + tile]`` concatenation re-selects the running top-m.  Because
the carry precedes the tile in the concatenation and tiles scan
left-to-right, ties resolve to the lowest dataset index — exactly
``lax.top_k``'s tie order — so the streamed result equals the
materialized ``lax.top_k(-pdist, m)`` bit-for-bit (per-element distance
dot products reduce over d in the same order regardless of N tiling).

Two implementations share that math:

* ``screen_topm_scan``   — ``lax.scan`` over N-tiles with the carry; it
  is the streamed screen on every backend, the TPU included.  Mosaic
  has no ``top_k`` (nor a sort) to lower the merge inside a Pallas
  kernel, so a Pallas form of this screen cannot compile for the chip;
  XLA runs the tile GEMM and the merge as ordinary TPU ops.
* ``ref.screen_topm_ref`` — materialized oracle (pdist + top_k).

``full_scan_partial_stream`` applies the identical tiling to the exact
posterior mean (Eq. 2): an online-softmax (max, denom, accumulator)
carry over N-tiles — the XLA twin of the Pallas
``golden_aggregate`` kernel — so ``full_scan`` baselines run at N where
the dense ``[B, N]`` logits matrix cannot be allocated at all.

Slot semantics (shared with ``ops.ivf_screen``): when ``m`` exceeds the
number of rows, surplus slots carry ``d2 = +inf`` and an in-range
(clamped) index, so downstream gathers stay valid and +inf marks
padding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.common import (check_rows, dot_f32, dot_rows,
                                  row_sq_norms, weigh_rows)

NEG_INF = -1e30
DEFAULT_TILE = 4096
# Scan-path default N-tile.  The lax.scan form holds only [B, tile]
# live distances, so it affords a 4x larger tile than DEFAULT_TILE —
# and XLA:CPU wall-clock improves monotonically with tile size
# (fewer merge dispatches, fatter GEMMs): at N=65536, B=32 the carry
# merge measures 42/118/421 ms (m=512/1638/6553) at tile=4096 vs
# 33/64/204 ms at 16384, recovering most of the streamed-vs-
# materialized gap (materialized: 20/40/130 ms where the [B, N] buffer
# fits).  Callers pass ``tile=None`` to get this per-path default.
SCAN_TILE = 16384


def _merge_topm(vals, idx, neg_tile, idx_tile, m: int):
    """One running-top-m step: re-select m from [carry | tile].

    ``vals`` descending negated distances [B, m]; tile operands raw
    [B, tile].  Carry-first concatenation keeps ``lax.top_k`` tie order
    (lowest dataset index wins).
    """
    cat_v = jnp.concatenate([vals, neg_tile], axis=-1)
    cat_i = jnp.concatenate([idx, idx_tile], axis=-1)
    new_v, sel = jax.lax.top_k(cat_v, m)
    return new_v, jnp.take_along_axis(cat_i, sel, axis=-1)


# -- lax.scan streamed screen ------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "tile", "hier"))
def screen_topm_scan(q: jnp.ndarray, x: jnp.ndarray, m: int,
                     q_norms: jnp.ndarray | None = None,
                     x_norms: jnp.ndarray | None = None,
                     tile: int | None = None, hier: bool = False):
    """Streaming top-m over x for q: [B, d], x: [N, d] -> (idx, d2) [B, m].

    ``d2`` ascending fp32; +inf marks slots past the real rows (m > N),
    whose indices are clamped in-range.  Peak live memory
    O(B * (m + tile)); the [N, d] store is sliced in place
    (``dynamic_slice``), never padded or re-materialized — a
    ragged final tile slides back to ``[N - tile, N)`` (the
    dynamic-slice clamp) and the already-seen overlap columns are
    masked to -inf, so no O(N d) padded copy exists for any N.
    ``tile=None`` picks :data:`SCAN_TILE` (CPU wall-clock improves with
    the tile — see the constant's comment).

    ``hier=True`` switches the merge to a two-level hierarchical form:
    each tile selects its own top-m independently inside the scan, the
    [nt, B, m] level-0 lists stack as scan outputs, and a log2(nt)-deep
    pairwise tree re-selects the global top-m.  Left-first
    concatenation at every level keeps ``lax.top_k``'s lowest-index tie
    rule, so both forms are bit-identical to the materialized screen.
    It is OFF by default on measurement: XLA:CPU's TopK custom call is
    strongly data-dependent (a descending-sorted prefix — exactly the
    carry-merge's input — runs ~10x faster than random input), and
    ``lax.scan`` serializes on every backend, so removing the merge
    from the carry buys no critical-path win while the independent
    per-tile top-k forfeits the fast path (measured ~3x slower end to
    end on CPU at N=65536).  The flag remains for backends whose
    per-tile top-k vectorizes across tiles.
    """
    n, d = x.shape
    if tile is None:
        tile = SCAN_TILE
    q32 = q.astype(jnp.float32)
    if q_norms is None:
        q_norms = jnp.sum(q32 ** 2, -1)
    if x_norms is None:
        x_norms = jnp.sum(x.astype(jnp.float32) ** 2, -1)
    x_norms = x_norms.astype(jnp.float32)
    tile = min(tile, max(n, 1))
    b = q.shape[0]
    qn = q_norms.astype(jnp.float32)[:, None]
    starts = jnp.arange(0, -(-n // tile) * tile, tile, dtype=jnp.int32)
    nt = starts.shape[0]

    def tile_neg(start):
        eff = jnp.minimum(start, n - tile)     # ragged tail: overlap back
        xt = jax.lax.dynamic_slice_in_dim(x, eff, tile).astype(jnp.float32)
        xnt = jax.lax.dynamic_slice_in_dim(x_norms, eff, tile)
        dot = dot_f32(q32, xt, ((1,), (1,)))
        d2 = jnp.maximum(qn + xnt[None, :] - 2.0 * dot, 0.0)
        cols = eff + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        return jnp.where(cols >= start, -d2, -jnp.inf), cols  # mask re-seen

    if hier and m < tile and nt > 1:
        # Two-level hierarchical merge (opt-in; see docstring): per-tile
        # independent top-m stacked as scan outputs (O(B N m/tile) —
        # strictly below the materialized [B, N] when m < tile), then a
        # log2(nt)-deep pairwise tree re-selects the global top-m.
        def level0(carry, start):
            neg, cols = tile_neg(start)
            v, sel = jax.lax.top_k(neg, m)
            return carry, (v, jnp.take_along_axis(cols, sel, axis=-1))

        _, (vals, idx) = jax.lax.scan(level0, 0, starts)
        while vals.shape[0] > 1:
            if vals.shape[0] % 2:              # odd level: -inf ghost tile
                vals = jnp.concatenate(
                    [vals, jnp.full_like(vals[:1], -jnp.inf)], axis=0)
                idx = jnp.concatenate([idx, jnp.zeros_like(idx[:1])], axis=0)
            cat_v = jnp.concatenate([vals[0::2], vals[1::2]], axis=-1)
            cat_i = jnp.concatenate([idx[0::2], idx[1::2]], axis=-1)
            vals, sel = jax.lax.top_k(cat_v, m)
            idx = jnp.take_along_axis(cat_i, sel, axis=-1)
        vals, idx = vals[0], idx[0]
    else:
        def body(carry, start):
            vals, idx = carry
            neg, cols = tile_neg(start)
            return _merge_topm(vals, idx, neg, cols, m), None

        init = (jnp.full((b, m), -jnp.inf, jnp.float32),
                jnp.zeros((b, m), jnp.int32))
        (vals, idx), _ = jax.lax.scan(body, init, starts)
    return jnp.minimum(idx, max(n - 1, 0)), -vals


# -- streaming full-scan LSE (XLA twin of the golden_aggregate kernel) --------

@functools.partial(jax.jit, static_argnames=("sigma2", "tile"))
def full_scan_partial_stream(q: jnp.ndarray, x: jnp.ndarray, sigma2: float,
                             x_norms: jnp.ndarray | None = None,
                             tile: int = DEFAULT_TILE):
    """Unnormalized softmax state of the FULL store, one tiled pass.

    Returns ``(acc [B, D], m [B], l [B])`` with the same clamped-logit
    (``NEG_INF`` floor) semantics as ``ops.golden_partial_aggregate``'s
    dense full-scan case, so the states LSE-merge exactly across shards
    (``sharding.lse_merge_mean``).  Peak live memory O(B * tile + B * D)
    — the [B, N] logits matrix of the dense form is never built, and
    (like :func:`screen_topm_scan`) a ragged final tile overlaps
    backwards with the re-seen columns masked to exactly zero weight
    instead of padding the store.  ``x`` is the store rows ``[N, 1, D]``;
    each tile is contracted on its last axis, not reshaped.
    """
    check_rows(x)
    n, d = x.shape[0], x.shape[-1]
    b = q.shape[0]
    q32 = q.astype(jnp.float32)
    qn = jnp.sum(q32 ** 2, -1)[:, None]
    if x_norms is None:
        x_norms = row_sq_norms(x)
    x_norms = x_norms.astype(jnp.float32)
    tile = min(tile, max(n, 1))
    # finite inverse temperature: degenerate sigma2 clamps every logit
    # at NEG_INF (uniform weights -> data mean) instead of the silent
    # 0 * inf NaN / ZeroDivisionError of an unguarded 1 / (2 sigma2)
    inv = ref.finite_inv_two_sigma2(sigma2)

    def body(carry, start):
        m_run, l_run, acc = carry
        eff = jnp.minimum(start, n - tile)     # ragged tail: overlap back
        xt = jax.lax.dynamic_slice_in_dim(x, eff, tile).astype(jnp.float32)
        xnt = jax.lax.dynamic_slice_in_dim(x_norms, eff, tile)
        dot = dot_rows(q32, xt)
        d2 = jnp.maximum(qn + xnt[None, :] - 2.0 * dot, 0.0)
        # +inf-norm (padded) rows clamp to the finite NEG_INF sentinel —
        # exp(NEG_INF - m) underflows to exactly 0 for any real logit,
        # matching the dense partial; re-seen overlap columns get a hard
        # -inf so they are zero even in the all-NEG_INF degenerate case
        lg = jnp.maximum(-d2 * inv, NEG_INF)
        cols = eff + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
        lg = jnp.where(cols >= start, lg, -jnp.inf)
        m_new = jnp.maximum(m_run, jnp.max(lg, -1))
        scale = jnp.exp(m_run - m_new)
        p = jnp.exp(lg - m_new[:, None])
        l_new = l_run * scale + jnp.sum(p, -1)
        acc_new = acc * scale[:, None] + weigh_rows(p, xt)
        return (m_new, l_new, acc_new), None

    init = (jnp.full((b,), NEG_INF, jnp.float32),
            jnp.zeros((b,), jnp.float32),
            jnp.zeros((b, d), jnp.float32))
    (m_run, l_run, acc), _ = jax.lax.scan(
        body, init,
        jnp.arange(0, -(-n // tile) * tile, tile, dtype=jnp.int32))
    return acc, m_run, l_run


def full_scan_stream(q: jnp.ndarray, x: jnp.ndarray, sigma2: float,
                     x_norms: jnp.ndarray | None = None,
                     tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Streaming exact posterior mean (Eq. 2); [B, D] in q.dtype."""
    acc, _, l = full_scan_partial_stream(q, x, float(sigma2),
                                         x_norms=x_norms, tile=tile)
    return (acc / jnp.maximum(l, 1e-30)[:, None]).astype(q.dtype)
