"""Distributed golden retrieval over a dataset sharded on the `data` axis.

The GoldDiff selection + aggregation pipeline, shard-parallel (DESIGN §3):

  1. every shard screens its local dataset rows with the proxy distance
     (exact matmul-form ``ops.pdist``, or ``ops.ivf_screen_local`` over
     its slice of a globally partitioned Golden Index) and a cross-shard
     top-m threshold restricts the union to exactly the global
     candidate set;
  2. each shard re-ranks its candidates exactly and local top-k
     (index, distance) pairs are all-gathered — k floats+ints per
     shard, NOT data rows;
  3. the golden set = global top-k over the gathered candidates
     (``sharding.crossshard_kth``);
  4. each shard aggregates its *owned* golden members into an
     unnormalized softmax partial state (``ops.golden_partial_aggregate``)
     and partial states merge exactly with a log-sum-exp ``psum``
     (``sharding.lse_merge_mean``, streaming.merge semantics), so the
     distributed estimate is bit-comparable to the single-host one.

Since PR 3 the shard-local screening math AND the cross-shard merge are
the same primitives the sharded ``GoldDiffEngine`` executes
(``core/engine.py``) — this module composes them for callers that want
raw (sigma2, m, k) control without a schedule; there is exactly one
implementation of the two-stage top-k + LSE merge in the repo
(``distributed/sharding.py``), pinned against a global top-k + softmax
in ``tests/test_sharded_engine.py``.

The shard-local distance math goes through the kernel ops layer
(``repro.kernels.ops``; ``backend=None`` takes the platform's — the
compiled kernels on a TPU mesh, the reference math elsewhere), so the
matmul-form distances here are the exact same code the single-host
GoldDiffEngine runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dataset import DatasetStore, downsample_proxy
from repro.distributed.sharding import (crossshard_kth, kth_from_gathered,
                                        lse_merge_mean, shard_map_compat)
from repro.index.shard import ShardedLayout, shard_layout
from repro.index.store import build_index
from repro.kernels import ops

Array = jnp.ndarray
NEG_INF = -1e30


def shard_store(store: DatasetStore, mesh: Mesh, axis: str = "data"
                ) -> DatasetStore:
    """Place the dataset rows sharded over ``axis`` (pads N to divisor)."""
    n_sh = mesh.shape[axis]
    n = store.n
    pad = (-n) % n_sh
    def pad_rows(x, fill=0.0):
        if pad == 0:
            return x
        cfg = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, cfg, constant_values=fill)
    sh = NamedSharding(mesh, P(axis))
    return DatasetStore(
        X=jax.device_put(pad_rows(store.X), sh),
        proxy=jax.device_put(pad_rows(store.proxy), sh),
        # +inf norms on padded rows exclude them from every top-k
        x_norms=jax.device_put(pad_rows(store.x_norms, jnp.inf), sh),
        proxy_norms=jax.device_put(pad_rows(store.proxy_norms, jnp.inf), sh),
        image_shape=store.image_shape,
        labels=None if store.labels is None
        else jax.device_put(pad_rows(store.labels, -1), sh),
    )


def build_shard_indexes(store: DatasetStore, mesh: Mesh, axis: str = "data",
                        num_clusters: int | None = None,
                        key: Array | None = None, iters: int = 25
                        ) -> ShardedLayout:
    """One *global* Golden Index, partitioned across the mesh axis.

    Builds ``repro.index.build_index`` over the full proxy embedding and
    lays it out per shard at CSR window boundaries
    (``repro.index.shard.shard_layout``) — the same layout the sharded
    ``GoldDiffEngine`` uses, so shard-local probing reproduces the
    single-host probe set exactly instead of approximating it with
    per-shard clusterings.
    """
    index = build_index(store, num_clusters=num_clusters, key=key,
                        iters=iters)
    return shard_layout(store, mesh, axis, index=index)


# -- shard-local pipeline stages (shard_map bodies; engine-callable) ---------

def local_coarse_exact(qp, proxy_loc, pnorms_loc, m_cap: int, m_sort: int,
                       m, axis: str, backend: str | None = None,
                       stream: bool = False, tile: int | None = None):
    """Shard-local exact proxy screening + cross-shard top-m threshold.

    Local top-``m_cap`` by matmul-form proxy distance, then a global
    m-th-distance cut so the surviving candidates across all shards are
    exactly the single-host top-m set (not the union of per-shard
    top-m/S approximations).  ``m`` may be traced (masked path);
    ``m_sort`` is its static bound.  Returns ``(cand, valid)``:
    [B, m_cap] local row ids + validity.

    The local screen goes through ``ops.screen_topm``: ``stream=True``
    tiles the shard's rows with a running top-m carry (O(B * (m_cap +
    tile)) live memory, the engine's streamed mode applied per shard)
    instead of materializing the [B, n_loc] distance matrix.
    """
    cand, d2p = ops.screen_topm(qp, proxy_loc, m_cap, x_norms=pnorms_loc,
                                tile=tile, stream=stream, backend=backend)
    negp = -d2p
    mth = crossshard_kth(negp, m_sort, m, axis)
    return cand, negp >= mth[:, None]


def golden_local_topk(X_loc, xn_loc, q, cand, cand_valid, k_cap: int,
                      k_sort: int, k, axis: str, backend: str | None = None,
                      strategy: str = "gather"):
    """Exact shard-local re-rank + stage-two global top-k threshold.

    Returns ``(idx, neg, kth)``: local top-``k_cap`` candidate row ids,
    their negated exact distances, and the global k-th threshold —
    ``neg >= kth[:, None]`` marks this shard's golden members.
    """
    d2 = ops.support_distances(q, X_loc, cand, x_norms=xn_loc,
                               backend=backend, strategy=strategy)
    d2 = jnp.where(cand_valid, d2, jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k_cap)
    idx = jnp.take_along_axis(cand, pos, axis=-1)
    kth = crossshard_kth(neg, k_sort, k, axis)
    return idx, neg, kth


def merged_golden_mean(X_loc, idx, neg, kth, sig2, axis: str,
                       strategy: str = "gather") -> Array:
    """Aggregate owned golden members and LSE-merge across shards."""
    lg = jnp.where(neg >= kth[:, None],
                   jnp.maximum(neg / (2.0 * sig2), NEG_INF), NEG_INF)
    acc, m_l, l_l = ops.golden_partial_aggregate(X_loc, idx, lg,
                                                 strategy=strategy)
    return lse_merge_mean(acc, m_l, l_l, axis)


def fused_local_step(X_loc, xn_loc, q, qp, proxy_loc, pnorms_loc,
                     m_cap: int, m_sort: int, m, k_cap: int, k_sort: int, k,
                     sig2, axis: str, backend: str | None = None,
                     strategy: str = "gather", stream: bool = False,
                     tile: int | None = None) -> Array:
    """One fused shard-local GoldDiff step with collective-compute overlap.

    Runs the same screen -> re-rank -> aggregate math as
    :func:`local_coarse_exact` + :func:`golden_local_topk` +
    :func:`merged_golden_mean` — the same kernel ops in the same order,
    so the result is **bitwise identical** to the staged sharded path —
    but restructures the dataflow so each cross-shard collective is
    issued *before* the shard-local compute it has no dependency on:

    * the m-threshold ``all_gather`` (k floats per shard) starts before
      the exact re-rank GEMM — the threshold is only consumed by the
      post-GEMM validity mask, so the collective hides behind the
      heaviest local stage;
    * the k-threshold ``all_gather`` starts before the golden-row
      gather feeding the partial aggregate — the rows depend on ``idx``
      alone, so the prefetch overlaps the second collective.

    XLA's latency-hiding scheduler can only overlap what the dataflow
    permits; this ordering makes the independence explicit instead of
    hoping the staged graph gets rescheduled.  ``m`` / ``k`` may be
    traced (masked path); ``m_sort`` / ``k_sort`` are their static
    bounds.
    """
    cand, d2p = ops.screen_topm(qp, proxy_loc, m_cap, x_norms=pnorms_loc,
                                tile=tile, stream=stream, backend=backend)
    negp = -d2p
    # collective in flight ...
    g_m = jax.lax.all_gather(negp, axis, axis=1)
    # ... while the shard-local exact re-rank runs
    d2 = ops.support_distances(q, X_loc, cand, x_norms=xn_loc,
                               backend=backend, strategy=strategy)
    mth = kth_from_gathered(g_m, m_sort, m)
    d2 = jnp.where(negp >= mth[:, None], d2, jnp.inf)
    neg, pos = jax.lax.top_k(-d2, k_cap)
    idx = jnp.take_along_axis(cand, pos, axis=-1)
    # second collective in flight while the aggregate's golden-row
    # gather (inside golden_partial_aggregate) proceeds
    g_k = jax.lax.all_gather(neg, axis, axis=1)
    kth = kth_from_gathered(g_k, k_sort, k)
    lg = jnp.where(neg >= kth[:, None],
                   jnp.maximum(neg / (2.0 * sig2), NEG_INF), NEG_INF)
    acc, m_l, l_l = ops.golden_partial_aggregate(X_loc, idx, lg,
                                                 strategy=strategy)
    return lse_merge_mean(acc, m_l, l_l, axis)


def distributed_golden_denoise(store: DatasetStore, mesh: Mesh, q: Array,
                               sigma2: float, m: int, k: int,
                               proxy_factor: int = 4, axis: str = "data",
                               index: ShardedLayout | None = None,
                               nprobe: int | None = None) -> Array:
    """Full GoldDiff step, shard-parallel.  q: [B, D] (rescaled query).

    ``store`` must be placed with :func:`shard_store`.  With ``index``
    (from :func:`build_shard_indexes`), the coarse screen probes
    ``nprobe`` windows of the *global* index (defaults to a quarter of
    them) and every probed row feeds the exact re-rank (IVF-Flat
    capacity mode); the store rows then come from the layout's
    cluster-sorted copies, not from ``store``.
    """
    n_sh = int(mesh.shape[axis])
    if index is not None:
        c = index.centroids.shape[0]
        nprobe = min(nprobe or max(1, -(-c // 4)), c)
        w_cap = min(nprobe, index.w_max)
        cap = w_cap * index.max_cluster
        k_cap = max(1, min(k, cap))

        def local(X, xn, offs, wr, ids, q_rep, cents, cnorms):
            X, xn, offs, wr = (z[0] for z in (X, xn, offs, wr))
            del ids
            q_img = q_rep.reshape(q_rep.shape[:-1]
                                  + tuple(store.image_shape))
            qp = downsample_proxy(q_img, proxy_factor)
            cand, pd2 = ops.ivf_screen_local(
                qp, offs, cents, cnorms, wr[0], wr[1], nprobe,
                index.max_cluster, w_cap, index.n_loc)
            idx, neg, kth = golden_local_topk(X, xn, q_rep, cand,
                                              jnp.isfinite(pd2), k_cap,
                                              k, k, axis)
            return merged_golden_mean(X, idx, neg, kth, sigma2, axis)

        sp = P(axis)
        mapped = shard_map_compat(
            local, mesh,
            in_specs=(sp, sp, sp, sp, sp, P(), P(), P()), out_specs=P())
        return mapped(index.X, index.x_norms, index.offsets, index.wrange,
                      index.ids, q, index.centroids, index.centroid_norms)

    n_loc = store.X.shape[0] // n_sh
    m_cap = min(m, n_loc)
    k_cap = max(1, min(k, m_cap))

    def local(x_sh, xn_sh, proxy_sh, pn_sh, q_rep):
        q_img = q_rep.reshape(q_rep.shape[:-1] + tuple(store.image_shape))
        qp = downsample_proxy(q_img, proxy_factor)
        cand, valid = local_coarse_exact(qp, proxy_sh, pn_sh, m_cap, m, m,
                                         axis)
        idx, neg, kth = golden_local_topk(x_sh, xn_sh, q_rep, cand, valid,
                                          k_cap, k, k, axis)
        return merged_golden_mean(x_sh, idx, neg, kth, sigma2, axis)

    sp = P(axis)
    mapped = shard_map_compat(local, mesh, in_specs=(sp, sp, sp, sp, P()),
                              out_specs=P())
    return mapped(store.X, store.x_norms, store.proxy, store.proxy_norms, q)
