"""Jit'd public wrappers over the Pallas kernels with backend dispatch.

This module is the single entry point the core library uses for the
GoldDiff hot path — coarse screening (``screen_topm``: fused tiled
pdist + running top-m, or the materialized ``pdist`` form below the
crossover), exact re-ranking (``golden_rerank``), and golden
aggregation (``golden_support_aggregate`` for supports,
``golden_aggregate`` for full scans, streamable) — plus the attention
kernels.  ``repro.core.engine.GoldDiffEngine`` routes every stage
through these wrappers so the same code path serves CPU tests, the
multi-pod dry-run, and real TPUs.

``backend``:
  * "pallas"            — the TPU implementation of every stage: the
                          compiled Pallas kernels (``pdist``,
                          ``centroid_scan``, the re-rank and aggregate
                          kernels) plus the ``lax.scan`` forms of the
                          streamed screen and the fused candidate pass,
                          whose running top-m merge Mosaic cannot lower
                          (it has no ``top_k``)
  * "pallas_interpret"  — the same stage choices with the kernel bodies
                          executed in Python on the CPU (correctness
                          validation; reached only by naming it)
  * "xla"               — pure-jnp reference math
  * ``None``            — :func:`platform_backend`: "pallas" on a TPU,
                          "xla" everywhere else.  The engine, the
                          denoisers and every op here resolve a missing
                          backend by this one rule.

Strategy note (measured on XLA:CPU): row gathers run ~50x slower per
element than GEMM, so by default the "xla" backend computes re-rank
distances in the *dense* form (one [B, N] GEMM + O(B m) scalar lookups)
and aggregates by scattering the k softmax weights into [B, N] and
doing a second GEMM — ~10x faster end-to-end than gathering [B, m, D]
rows on CPU *when m is a sizable fraction of N*.  The crossover flips
once the touched rows drop below ~10% of N on CPU (much higher on
GPU/TPU), which is exactly the regime the Golden Index creates, so
``support_distances`` / ``golden_support_aggregate`` accept an explicit
``strategy`` ("dense" | "gather") that ``GoldDiffEngine`` selects per
platform at build time instead of hard-coding by backend.  The Pallas
backends always use the row-fetch kernels, the right shape for TPU: each
candidate row is DMA'd from the ``[N, 1, D]`` store into a VMEM tile
(``kernels.common.fetch_tile``).  Store operands are the rows
``[N, 1, D]`` (``kernels.common`` states the layout rule; ``[N, D]`` is
refused, not reshaped); only ``support_distances`` / ``golden_rerank``
also take a 2-D table, the proxy of the indexed screen.  XLA forms
contract the rows' last axis and squeeze gathered rows.  All paths
compute the same math with fp32 accumulation (``HIGHEST`` matmul
precision or fp32 VPU sums, ``kernels.common``); parity is asserted in
``tests/test_engine.py`` / ``tests/test_index.py``.

``ivf_screen`` + ``centroid_scan`` are the indexed (sublinear) coarse
stage over a ``repro.index.GoldenIndex`` layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.centroid_scan import centroid_scan as _cscan
from repro.kernels.common import (check_rows, gather_rows, row_sq_norms,
                                  weigh_rows)
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.fused_step import fused_candidates_scan, fused_posterior
from repro.kernels.golden_aggregate import golden_aggregate as _agg
from repro.kernels.golden_attention import (golden_attention_decode as _gattn,
                                            select_golden_blocks)
from repro.kernels.golden_rerank import support_sqdist as _sqd
from repro.kernels.golden_support_aggregate import (
    golden_support_aggregate as _sagg)
from repro.kernels.pdist import pdist as _pdist
from repro.kernels.screen import (DEFAULT_TILE, SCAN_TILE,
                                  full_scan_partial_stream,
                                  full_scan_stream, screen_topm_scan)

BACKENDS = ("pallas", "pallas_interpret", "xla")


def platform_backend() -> str:
    """The backend for the platform JAX runs on: the compiled kernels
    ("pallas") on a TPU, the XLA reference math ("xla") elsewhere.
    Interpret mode is never a default."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(backend: str | None) -> str:
    return platform_backend() if backend is None else backend


# -- fault-injection dispatch seam -------------------------------------------
# The engine's compiled-program cache (``GoldDiffEngine.program``)
# consults this module-level hook on every lookup.  With no hook
# installed (the production default) the cache returns its raw
# callables — identity, zero overhead, zero recompiles (guarded by the
# CI recompile job).  ``repro.launch.faults`` installs a deterministic
# injector here for chaos tests and the resilience benchmark; nothing
# else should ever set it.
_DISPATCH_HOOK = None


def set_dispatch_hook(hook):
    """Install (or clear, with ``None``) the dispatch fault hook.

    A hook object must provide ``on_program(engine, key)`` (called on
    every cache lookup, before the hit/miss check — it may evict) and
    ``wrap(key, fn) -> fn`` (called on every dispatch — it may return
    ``fn`` unchanged or a fault-wrapped callable).  Returns the
    previously installed hook so callers can restore it.
    """
    global _DISPATCH_HOOK
    prev = _DISPATCH_HOOK
    _DISPATCH_HOOK = hook
    return prev


def dispatch_hook():
    """The currently installed dispatch fault hook (``None`` = off)."""
    return _DISPATCH_HOOK


def pdist(q, x, q_norms=None, x_norms=None, backend: str | None = None,
          **kw):
    """Pairwise squared distances [B, N] (tiled matmul form, fp32)."""
    backend = _resolve(backend)
    if backend == "xla":
        return ref.pdist_ref(q, x, q_norms, x_norms)
    return _pdist(q, x, q_norms, x_norms,
                  interpret=(backend == "pallas_interpret"), **kw)


def screen_topm(q, x, m: int, q_norms=None, x_norms=None,
                tile: int | None = None, stream: bool = True,
                backend: str | None = None):
    """Exact top-m rows of x by squared distance, read exactly once.

    The streaming coarse screen (``kernels.screen``): tiled matmul-form
    distances + a running top-m carry, peak memory O(B * (m + tile))
    instead of the materialized O(B * N).  Returns ``(idx, d2)``
    [B, m] with ``d2`` ascending; ``m > N`` surplus slots carry
    ``d2 = +inf`` and clamped in-range indices.  The result equals
    ``lax.top_k(-pdist(q, x), m)`` including tie order.

    ``stream=False`` keeps the materialized form — the full [B, N]
    distance matrix (tiled ``pdist`` kernel on pallas backends) plus
    one wide ``lax.top_k`` — which is the right shape below the
    engine's streamed-vs-materialized crossover, where one big GEMM
    beats the scan's per-tile merge overhead (measured ~1.6x on
    XLA:CPU at the scan-path default tile; see
    ``benchmarks/screen_speedup.py``).  The streamed form is
    ``screen_topm_scan`` on every backend (Mosaic cannot lower its
    top-m merge); ``tile=None`` picks its ``SCAN_TILE``.
    """
    backend = _resolve(backend)
    if not stream:
        if backend == "xla":
            return ref.screen_topm_ref(q, x, m, q_norms, x_norms)
        return ref.materialized_topm(
            pdist(q, x, q_norms, x_norms, backend=backend), m)
    return screen_topm_scan(q, x, m, q_norms, x_norms, tile=tile)


def support_distances(q, x, idx, x_norms=None,
                      backend: str | None = None,
                      strategy: str | None = None, **kw):
    """Exact distances q -> x[idx] with no [B, m, D] subtract temporaries.

    ``strategy`` picks the candidate-math form on the xla backend:
    "dense" (one [B, N] GEMM + scalar lookup — no row gathers) or
    "gather" ([B, m, D] row gather + matmul-form distances, sublinear in
    N).  ``None`` keeps the historical per-backend default ("dense" on
    xla).  The pallas backends always take the row-fetch kernel, the
    TPU shape regardless.  ``x`` is the store rows ``[N, 1, D]`` or a
    2-D table (the proxy of the indexed screen), whose rows the kernel
    reads through an XLA gather.
    """
    backend = _resolve(backend)
    if x_norms is None:
        x_norms = row_sq_norms(x)
    if backend == "xla":
        if (strategy or "dense") == "dense":
            d2_all = ref.pdist_ref(q, x, x_norms=x_norms)
            return jnp.take_along_axis(d2_all, idx, axis=-1)
        return ref.support_sqdist_ref(q, gather_rows(x, idx), x_norms[idx])
    return _sqd(q, x, idx, x_norms,
                interpret=(backend == "pallas_interpret"), **kw)


def golden_rerank(q, x, cand, k: int, x_norms=None,
                  backend: str | None = None,
                  strategy: str | None = None, valid=None, **kw):
    """Exact re-rank inside the candidate set (paper Eq. 5).

    Returns ``(idx, d2)``: top-k dataset indices [B, k] AND their exact
    squared distances [B, k] (sorted ascending), so the caller reuses
    selection distances for the aggregation softmax instead of
    recomputing them.  ``valid`` (bool [B, m], optional) masks padded
    candidate slots (e.g. clipped rows from a capacity-padded
    ``ivf_screen``) to +inf so they are selected last and weightless.
    """
    d2 = support_distances(q, x, cand, x_norms, backend=backend,
                           strategy=strategy, **kw)
    if valid is not None:
        d2 = jnp.where(valid, d2, jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k)
    return jnp.take_along_axis(cand, pos, axis=-1), -neg


def fused_step(q, qp, x, proxy, m: int, k: int, sigma2,
               x_norms=None, proxy_norms=None,
               backend: str | None = None, strategy: str | None = None,
               stream: bool = True, tile: int | None = None,
               m_t=None, k_t=None):
    """One fused GoldDiff denoise step: posterior mean in a single pass.

    Coarse screen + exact re-rank + softmax aggregation fused
    (``kernels.fused_step``): store tiles stream through once carrying
    a running proxy top-m with the exact distances threaded along, and
    the epilogue aggregates only the k selected golden rows — no
    [B, N] re-rank matrix, no [B, m, D] candidate materialization, no
    second read of the store.  ``q`` [B, D] are rescaled queries
    (``x_t / a``), ``qp`` [B, dp] their proxy projections; returns the
    posterior mean [B, D] fp32.

    ``strategy`` picks the epilogue's aggregation form (and, with
    ``stream=False``, the re-rank form) exactly as in the staged ops:
    "gather" keeps everything sublinear in N (the streaming story);
    "dense" keeps the scatter + GEMM aggregate dense-strategy engines
    already use — the identical op the staged body runs, so fused and
    staged stay op-compatible per strategy.  ``stream=False`` keeps the
    materialized candidate form below the streamed-screen byte
    crossover.  The streamed candidate pass is ``fused_candidates_scan``
    on every backend (Mosaic cannot lower its top-m merge); on the
    pallas backends the epilogue aggregates with the support-aggregate
    kernel.  ``sigma2`` may be traced;
    ``m_t`` / ``k_t`` (optional traced scalars) mask scheduled sizes
    for the caps-aware masked path.  Fused-vs-staged outputs agree at
    fp32 reduction order (~1e-7 relative; the candidate *sets* are
    bit-identical, see the kernel module docstring).
    """
    backend = _resolve(backend)
    check_rows(x)
    if stream:
        idx, d2 = fused_candidates_scan(qp, q, proxy, x, m,
                                        proxy_norms, x_norms, tile=tile)
    else:
        idx, pd2 = screen_topm(qp, proxy, m, x_norms=proxy_norms,
                               stream=False, backend=backend)
        d2 = support_distances(q, x, idx, x_norms, backend=backend,
                               strategy=strategy)
        # surplus slots (m > N) alias clamped rows with finite dense
        # distances; propagate the screen's +inf marker so they stay
        # weightless, matching the streaming forms
        d2 = jnp.where(jnp.isinf(pd2), jnp.inf, d2)
    return fused_posterior(x, idx, d2, k, sigma2, backend=backend,
                           m_t=m_t, k_t=k_t,
                           interpret=(backend == "pallas_interpret"),
                           strategy=strategy)


def golden_support_aggregate(x, idx, logits, backend: str | None = None,
                             strategy: str | None = None, **kw):
    """softmax(logits)-weighted mean of x[idx] per query -> [B, D] fp32.

    ``logits`` come from re-ranking distances (masking is the caller's
    job: NEG_INF entries get zero weight).  xla: scatter + GEMM
    (``strategy="dense"``, the default) or row gather + einsum
    (``strategy="gather"``, sublinear in N); pallas*: row-fetch +
    streaming online-softmax kernel.
    """
    backend = _resolve(backend)
    check_rows(x)
    if backend == "xla":
        if (strategy or "dense") == "dense":
            return ref.scatter_aggregate_ref(x, idx, logits)
        return ref.golden_support_aggregate_ref(gather_rows(x, idx), logits)
    return _sagg(x, idx, logits, interpret=(backend == "pallas_interpret"),
                 **kw)


def golden_partial_aggregate(x, idx, logits, strategy: str | None = None):
    """Unnormalized softmax partial state of x[idx] per query.

    Returns ``(acc [B, D], m [B], l [B])`` — the shard-local half of the
    golden aggregation: partial states from different dataset shards
    combine exactly with ``repro.distributed.sharding.lse_merge_mean``
    (streaming.merge semantics), which is how the sharded
    ``GoldDiffEngine`` and ``distributed_golden_denoise`` produce a
    posterior mean bit-comparable to the single-host softmax.

    ``idx`` indexes rows of the *local* shard ``x``; ``strategy``
    mirrors :func:`golden_support_aggregate` ("dense": scatter + GEMM,
    the XLA:CPU shape; "gather": row gather + einsum, sublinear in the
    shard size).  Pass ``idx=None`` with dense [B, n_loc] logits for
    the full-scan (every-local-row) case.  The body is plain jnp on
    every backend: it runs inside ``shard_map``, where it compiles for
    whatever platform the mesh lives on (the same rationale as the
    standalone distributed path).
    """
    check_rows(x)
    if idx is None:
        lg = logits.astype(jnp.float32)
        m = jnp.max(lg, axis=-1)
        p = jnp.exp(lg - m[:, None])
        return weigh_rows(p, x), m, jnp.sum(p, axis=-1)
    if (strategy or "gather") == "dense":
        return ref.scatter_partial_aggregate_ref(x, idx, logits)
    return ref.partial_aggregate_ref(gather_rows(x, idx), logits)


def ivf_screen_local(qp, offsets_loc, centroids, centroid_norms, w_lo, w_hi,
                     nprobe_max: int, max_cluster: int, w_cap: int,
                     n_loc: int, nprobe=None,
                     backend: str | None = None):
    """Shard-local lanes of a *globally probed* Golden Index.

    The sharded engine partitions one global ``GoldenIndex`` across
    devices at CSR *window* boundaries (``repro.index.shard``): each
    shard owns the contiguous window ids ``[w_lo, w_hi)`` and their
    cluster-sorted rows.  Every shard runs the identical (replicated,
    O(C d)) centroid scan and top-``nprobe_max`` probe selection — same
    input, same op, so the probe list agrees across shards bit-for-bit
    — then keeps only *its own* probed windows, compacted best-first
    into ``w_cap = min(nprobe_max, windows per shard)`` slots via a
    masked top-k.  The union of lanes across shards is exactly the
    single-host probe set, each lane owned by one shard: this is what
    makes sharded-vs-single-host indexed screening an equality test,
    not a recall bound.

    Capacity mode only (the engine's IVF-Flat convention: every probed
    row feeds the exact re-rank).  Returns ``(pos, d2)``: [B, w_cap *
    max_cluster] positions into the shard's sorted rows, and validity
    markers (0 real, +inf capacity padding / foreign windows).
    ``nprobe`` (defaults to ``nprobe_max``) may be traced — probes
    beyond it are masked, for the scan/pjit-compatible masked path.
    """
    cd2 = centroid_scan(qp, centroids, centroid_norms, backend=backend)
    cneg, probe = jax.lax.top_k(-cd2, nprobe_max)          # [B, P], global
    mine = (probe >= w_lo) & (probe < w_hi)
    if nprobe is not None:
        mine = mine & (jnp.arange(nprobe_max) < nprobe)[None, :]
    score = jnp.where(mine, cneg, -jnp.inf)
    svals, spos = jax.lax.top_k(score, w_cap)              # my probed windows
    win = jnp.take_along_axis(probe, spos, axis=-1)
    wvalid = svals > -jnp.inf
    lw = jnp.clip(win - w_lo, 0, offsets_loc.shape[0] - 2)
    starts = offsets_loc[lw]                               # [B, Wc]
    ends = offsets_loc[lw + 1]
    lane = jnp.arange(max_cluster, dtype=starts.dtype)
    pos = starts[..., None] + lane[None, None, :]          # [B, Wc, L]
    valid = (pos < ends[..., None]) & wvalid[..., None]
    b = qp.shape[0]
    pos = jnp.minimum(pos, n_loc - 1).reshape(b, -1)
    valid = valid.reshape(b, -1)
    return pos, jnp.where(valid, 0.0, jnp.inf)


def centroid_scan(q, centroids, c_norms=None, backend: str | None = None,
                  **kw):
    """Query -> k-means-centroid distances [B, C] (IVF level 1, fp32)."""
    backend = _resolve(backend)
    if backend == "xla":
        return ref.pdist_ref(q, centroids, x_norms=c_norms)
    return _cscan(q, centroids, c_norms,
                  interpret=(backend == "pallas_interpret"), **kw)


def ivf_screen(qp, proxy_sorted, proxy_norms_sorted, offsets, centroids,
               centroid_norms, m: int, nprobe_max: int, max_cluster: int,
               nprobe=None, backend: str | None = None):
    """Two-level indexed coarse screening (GoldenIndex layout).

    Level 1: tiled centroid scan + top-``nprobe_max`` probe selection.
    Level 2: gather ONLY the probed clusters' rows (CSR windows padded
    to the static ``max_cluster`` width L) and compute matmul-form
    proxy distances over those ``nprobe_max * L`` rows — O(C d +
    nprobe L d) per query instead of the dense O(N d) scan.

    ``nprobe`` (defaults to ``nprobe_max``) may be a *traced* scalar:
    probes beyond it are masked, which is how the scan/pjit-compatible
    masked engine path varies the probe width inside one program.

    Returns ``(pos, d2)``: candidate rows as positions **in
    cluster-sorted row space** [B, m] plus their proxy distances (slots
    beyond the probed clusters' true rows carry +inf).  Callers map
    positions to dataset ids via ``index.perm``.  When ``m`` equals the
    probed capacity ``nprobe_max * max_cluster`` — the IVF-Flat
    convention of re-ranking *everything probed*, and the engine's
    default — no per-row screening decision remains, so the gather +
    proxy-distance pass AND the top-m select (the two dominant costs of
    the indexed path) are skipped entirely: the returned ``d2`` are
    validity markers (0 for real rows, +inf for capacity padding, which
    is all downstream consumers use them for), the rows come back in
    CSR order, and the coarse stage costs O(C d + nprobe L) — the
    proxy-dim factor moves wholly into the exact re-rank.
    """
    n = proxy_sorted.shape[0]
    cd2 = centroid_scan(qp, centroids, centroid_norms, backend=backend)
    probe = jax.lax.top_k(-cd2, nprobe_max)[1]              # [B, P]
    starts = offsets[probe]                                 # [B, P]
    ends = offsets[probe + 1]
    lane = jnp.arange(max_cluster, dtype=starts.dtype)
    pos = starts[..., None] + lane[None, None, :]           # [B, P, L]
    valid = pos < ends[..., None]
    if nprobe is not None:
        probe_live = jnp.arange(nprobe_max) < nprobe        # [P]
        valid = valid & probe_live[None, :, None]
    b = qp.shape[0]
    pos = jnp.minimum(pos, n - 1).reshape(b, -1)            # [B, R]
    valid = valid.reshape(b, -1)
    if m >= nprobe_max * max_cluster:
        return pos, jnp.where(valid, 0.0, jnp.inf)
    d2 = support_distances(qp, proxy_sorted, pos, proxy_norms_sorted,
                           backend=backend, strategy="gather")
    d2 = jnp.where(valid, d2, jnp.inf)
    neg, sel = jax.lax.top_k(-d2, m)
    return jnp.take_along_axis(pos, sel, axis=-1), -neg


def golden_aggregate(q, x, sigma2: float, x_norms=None,
                     backend: str | None = None, stream: bool = False,
                     tile: int | None = None, **kw):
    """Full-scan posterior mean (Eq. 2) via streaming softmax.

    The pallas backends always stream (online-softmax carry in VMEM
    scratch).  On xla, ``stream=True`` switches from the dense [B, N]
    logits form to the tiled ``lax.scan`` LSE
    (``kernels.screen.full_scan_stream``), which makes full-scan
    baselines runnable at N where the dense matrix cannot be allocated.
    """
    backend = _resolve(backend)
    check_rows(x)
    if backend == "xla":
        if stream:
            return full_scan_stream(q, x, float(sigma2), x_norms=x_norms,
                                    tile=DEFAULT_TILE if tile is None
                                    else tile)
        return ref.golden_aggregate_ref(q, x, sigma2, x_norms)
    return _agg(q, x, float(sigma2), x_norms=x_norms,
                interpret=(backend == "pallas_interpret"), **kw)


def golden_full_partial(q, x, sigma2: float, x_norms=None,
                        stream: bool = False, tile: int | None = None):
    """Unnormalized softmax state of the FULL local store; (acc, m, l).

    The shard-local half of a full scan: states LSE-merge exactly
    across shards (``sharding.lse_merge_mean``).  ``stream=True`` tiles
    the pass (O(B * tile) live logits) instead of materializing the
    dense [B, n_loc] matrix; both forms clamp logits at the finite
    ``NEG_INF`` sentinel so all-padding rows merge to zero weight, and
    they agree to fp32 reduction order.  Plain jnp on every backend —
    it runs inside ``shard_map``, where it compiles for whatever
    platform the mesh lives on.
    """
    check_rows(x)
    if stream:
        return full_scan_partial_stream(q, x, float(sigma2),
                                        x_norms=x_norms,
                                        tile=DEFAULT_TILE if tile is None
                                        else tile)
    d2 = ref.pdist_ref(q, x, x_norms=x_norms)
    lg = jnp.maximum(-d2 * ref.finite_inv_two_sigma2(sigma2), ref.NEG_INF)
    return golden_partial_aggregate(x, None, lg)


def golden_attention_decode(q, k, v, block_idx, valid, block_size: int = 128,
                            backend: str | None = None):
    backend = _resolve(backend)
    if backend == "xla":
        return ref.golden_attention_decode_ref(q, k, v, block_idx, valid,
                                               block_size)
    return _gattn(q, k, v, block_idx, valid, block_size=block_size,
                  interpret=(backend == "pallas_interpret"))


def flash_attention(q, k, v, causal: bool = True,
                    backend: str | None = None, **kw):
    backend = _resolve(backend)
    if backend == "xla":
        return ref.flash_attention_ref(q, k, v, causal)
    return _flash(q, k, v, causal=causal,
                  interpret=(backend == "pallas_interpret"), **kw)


__all__ = ["pdist", "screen_topm", "support_distances",
           "golden_rerank", "fused_step",
           "golden_support_aggregate",
           "golden_partial_aggregate", "golden_full_partial",
           "golden_aggregate", "centroid_scan", "ivf_screen",
           "ivf_screen_local", "golden_attention_decode",
           "select_golden_blocks", "flash_attention", "platform_backend",
           "BACKENDS", "DEFAULT_TILE", "SCAN_TILE", "set_dispatch_hook",
           "dispatch_hook"]
