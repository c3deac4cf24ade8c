"""Per-shard layout of the golden store (and its index) over a mesh axis.

The sharded ``GoldDiffEngine`` partitions ONE dataset — and, when
indexed, ONE global ``GoldenIndex`` — across the devices of a mesh
axis, so multi-device screening is an *equality-preserving* re-layout
of the single-host pipeline rather than an approximation:

* **exact mode** (no index): rows are chunked contiguously in dataset
  order; padded tail rows carry +inf norms and are never screened in.
* **indexed mode**: the global index's cluster-sorted rows are
  partitioned at CSR *window* boundaries, balanced by row count.  Each
  shard holds the contiguous window-id range ``wrange = [w_lo, w_hi)``,
  those windows' rows (proxy AND the [n_loc, 1, D] store rows, both in
  cluster-sorted order), and window offsets rebased to shard-local row
  positions.  The (small) centroid table is replicated so every shard
  can run the identical global probe selection
  (``ops.ivf_screen_local``); a probed window then belongs to exactly
  one shard, so the union of shard-local candidate lanes equals the
  single-host probe set row-for-row.

All per-shard arrays are stacked on a leading shard axis and placed
with ``NamedSharding(mesh, P(axis))``: inside ``shard_map`` each shard
sees exactly its own slab (leading dim 1, squeezed by the caller).
``ids`` maps shard-local row positions back to dataset row ids, which
is how ``select()`` keeps returning ordinary dataset indices.
"""
from __future__ import annotations

from typing import TYPE_CHECKING, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

if TYPE_CHECKING:  # annotation-only: importing repro.core here would
    from repro.core.dataset import DatasetStore      # cycle via engine
    from repro.index.store import GoldenIndex

Array = jnp.ndarray


class ShardedLayout(NamedTuple):
    """Stacked per-shard golden store (+ optional index routing)."""

    X: Array                   # [S, n_loc, 1, D] store rows (sorted if indexed)
    x_norms: Array             # [S, n_loc] fp32 (+inf on padding)
    proxy: Array               # [S, n_loc, dp] (cluster-sorted if indexed)
    proxy_norms: Array         # [S, n_loc] fp32 (+inf on padding)
    ids: Array                 # [S, n_loc] int32 dataset row ids (0 on pad)
    offsets: Array | None      # [S, W + 1] int32 local window offsets
    wrange: Array | None       # [S, 2] int32 owned window ids [w_lo, w_hi)
    centroids: Array | None    # [C, dp] replicated global window centroids
    centroid_norms: Array | None  # [C] replicated
    n_loc: int                 # static per-shard row count (padded)
    w_max: int                 # static max windows owned by any shard
    max_cluster: int           # L: padded per-window row count
    n_shards: int

    @property
    def indexed(self) -> bool:
        return self.offsets is not None


def partition_windows(offsets: np.ndarray, n_shards: int) -> np.ndarray:
    """Cut points (window ids, length S+1) balancing rows per shard.

    Greedy: shard s takes the windows up to the first boundary at or
    past ``(s + 1) / S`` of the rows.  Monotone by construction; shards
    past the last window come out empty (valid, just idle) when there
    are fewer windows than shards.
    """
    n = int(offsets[-1])
    cuts = [0]
    for s in range(1, n_shards):
        target = round(n * s / n_shards)
        w = int(np.searchsorted(offsets, target, side="left"))
        cuts.append(int(np.clip(w, cuts[-1], len(offsets) - 1)))
    cuts.append(len(offsets) - 1)
    return np.asarray(cuts, np.int64)


def shard_layout(store: DatasetStore, mesh: Mesh, axis: str = "data",
                 index: GoldenIndex | None = None,
                 storage_dtype=None) -> ShardedLayout:
    """Build the stacked per-shard layout (host-side, at engine build)."""
    n_sh = int(mesh.shape[axis])
    n = store.n
    X = store.X
    proxy = np.asarray(store.proxy)
    xn = np.asarray(store.x_norms, np.float32)
    pn = np.asarray(store.proxy_norms, np.float32)

    if index is None:
        order = np.arange(n)
        n_loc = -(-n // n_sh)
        row_cuts = np.minimum(np.arange(n_sh + 1) * n_loc, n)
        w_max = 0
        offs_parts = wrange = None
    else:
        if index.n != n:
            raise ValueError(f"index built for N={index.n}, store N={n}")
        order = np.asarray(index.perm)
        offsets = np.asarray(index.offsets, np.int64)
        cuts = partition_windows(offsets, n_sh)
        row_cuts = offsets[cuts]
        w_max = int(np.max(np.diff(cuts)))
        n_loc = int(np.max(np.diff(row_cuts)))
        offs_parts = []
        for s in range(n_sh):
            o = offsets[cuts[s]: cuts[s + 1] + 1] - offsets[cuts[s]]
            offs_parts.append(np.pad(o, (0, w_max + 1 - len(o)),
                                     mode="edge" if len(o) else "constant"))
        wrange = np.stack([cuts[:-1], cuts[1:]], axis=1).astype(np.int32)

    def stack_rows(a, fill=0.0):
        out = np.full((n_sh, n_loc) + a.shape[1:], fill, a.dtype)
        for s in range(n_sh):
            rows = order[row_cuts[s]: row_cuts[s + 1]]
            out[s, : len(rows)] = a[rows]
        return out

    ids = np.zeros((n_sh, n_loc), np.int32)
    for s in range(n_sh):
        rows = order[row_cuts[s]: row_cuts[s + 1]]
        ids[s, : len(rows)] = rows

    Xs, ps = stack_rows(X)[:, :, None, :], stack_rows(proxy)
    if storage_dtype is not None:
        Xs = Xs.astype(storage_dtype)
        ps = ps.astype(storage_dtype)
    sh = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    put = lambda a: jax.device_put(jnp.asarray(a), sh)
    return ShardedLayout(
        X=put(Xs),
        x_norms=put(stack_rows(xn, fill=np.inf)),
        proxy=put(ps),
        proxy_norms=put(stack_rows(pn, fill=np.inf)),
        ids=put(ids),
        offsets=None if index is None else put(
            np.stack(offs_parts).astype(np.int32)),
        wrange=None if index is None else put(wrange),
        centroids=None if index is None else jax.device_put(
            index.centroids, rep),
        centroid_norms=None if index is None else jax.device_put(
            index.centroid_norms, rep),
        n_loc=int(n_loc),
        w_max=w_max,
        max_cluster=0 if index is None else index.max_cluster,
        n_shards=n_sh,
    )
