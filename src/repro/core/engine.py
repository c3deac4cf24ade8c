"""Backend-dispatched GoldDiff execution engine.

``GoldDiffEngine`` owns the entire coarse -> fine -> aggregate pipeline
(paper Sec. 3.4) and routes every stage through the kernel layer
(``repro.kernels.ops``), replacing the seed's ad-hoc per-class
``_programs`` dicts and inline jnp hot loops:

* **coarse screening** — proxy distances via ``ops.pdist`` (tiled
  matmul form with precomputed norms) instead of an inline broadcast
  expression;
* **precision re-ranking** — ``ops.golden_rerank`` returns top-k
  indices *and* their exact distances, so the aggregation softmax
  reuses selection distances (the seed recomputed them — and regathered
  the rows — a second time);
* **aggregation** — ``ops.golden_support_aggregate`` (streaming online
  softmax on Pallas backends; scatter + GEMM on the XLA backend) and
  ``ops.golden_aggregate`` for full scans.

Engine features:

* **program cache** — compiled programs keyed on
  ``(kind, t, shape, dtype, backend)``; each timestep has static
  (m_t, k_t) so one XLA program per step (true FLOP savings, the
  paper's complexity table), while ``denoise_masked`` is a
  scan/pjit-compatible program padded to (m_max, k_max) — or, given a
  trajectory-plan bucket's ``caps`` (``repro.core.plan``), padded only
  to that bucket's (m_cap, k_cap, nprobe_cap), which is how
  ``sampler.sample_plan`` serves a whole trajectory with 3-4 compiled
  programs at near-static FLOPs.
* **per-timestep schedule constants** — a_t, sigma_t^2, (m_t, k_t)
  precomputed host-side once per t.
* **bf16 storage with fp32 accumulation** — ``storage_dtype=bfloat16``
  keeps the dataset (and proxy) operands in bf16 for bandwidth while
  row norms stay fp32 (computed from the fp32 master copy) and every
  distance/softmax/accumulation runs in fp32.
* **uniform backends** — ``xla`` (the reference math; the default off
  TPU), ``pallas_interpret`` (kernel-body validation on CPU, only when
  named), and ``pallas`` (the default on TPU) all execute the same
  pipeline; ``backend=None`` takes ``ops.platform_backend()``.  Parity
  is asserted in ``tests/test_engine.py``.

Backend/strategy matrix::

    backend           re-rank distances        aggregation
    ----------------  -----------------------  --------------------------
    xla + dense       dense GEMM + lookup      scatter + GEMM
    xla + gather      row gather + einsum      row gather + einsum
    pallas_interpret  row-fetch tiled kernel   row-fetch streaming kernel
    pallas            row-fetch tiled kernel   row-fetch streaming kernel

On the pallas backends the streamed screen and the fused candidate pass
run as ``lax.scan`` programs (Mosaic cannot lower their top-m merge);
every other stage is a compiled Pallas kernel.

The xla *strategy* (gather vs dense) is selected per platform at engine
build time: XLA:CPU row gathers run ~50x slower per element than GEMM,
so dense wins whenever the touched rows are a sizable fraction of N,
but the gather form wins below the platform's crossover fraction
(``GATHER_CROSSOVER_FRAC``, measured ~10% of N on CPU; pass
``strategy="measure"`` to probe the live device instead of using the
table).  On TPU the row-fetch kernels always read only the candidates.

**Streamed exact screening** (``screen=``): the exact coarse stage and
the full scan route through ``ops.screen_topm`` / the streaming LSE
(``kernels/screen.py``) — a fused tiled pdist with a running top-m
(or online-softmax) carry that reads the store exactly once at
O(B * (m + tile)) peak memory instead of materializing [B, N].
``screen="auto"`` keeps the materialized form while the [B, N] buffer
fits the platform budget (``SCREEN_MATERIALIZE_BYTES``; on CPU the one
big GEMM + top_k is ~1.6x faster when it fits) and streams beyond it,
which makes screening and full-scan baselines runnable at N where the
dense matrix cannot be allocated at all.  ``screen_tile`` is part of
every streamed program's cache key.  The same policy applies per shard
inside the sharded entry points (the local [B, n_loc] screen streams
by the same rule).

**Golden Index** (``index=...``): coarse screening routes through the
IVF-clustered ``repro.index.GoldenIndex`` — a tiled centroid scan plus
a gather of only the probed clusters' rows (``ops.ivf_screen``) — with
the probe count nprobe_t driven by the time-aware
``repro.index.ProbeSchedule`` (wide at low SNR, a handful of clusters
at high SNR) plus an occupancy floor (probed windows always hold
>= k_t real rows).  Only the proxy side lives in cluster-sorted order
(reusing the index's own arrays); candidates map through
``index.perm`` into ordinary dataset ids before the re-rank, so the
[N, D] store is never duplicated.  Per-timestep, the engine falls back
to exact dense screening when the scheduled probes would touch more
rows than the platform's gather/GEMM crossover (``index_mode="auto"``;
``"always"`` forces the index, e.g. for recall tests).  Program-cache
keys extend with (nprobe_t, padded candidate count) so indexed and
exact programs never collide.

**Epoch hot-swap** (``install_epoch`` / ``set_serving_epoch`` /
``at_epoch``): every compiled body takes the store/index device arrays
as a real jit argument (:class:`StoreOperands`, threaded by
:meth:`GoldDiffEngine.jitter`) instead of closing over them, so the
operands are *data*, not baked executable constants.  Installing a new
epoch with the same shapes — what the appendable store lifecycle
(``repro.index.ingest``) guarantees across appends — reuses every
compiled program unchanged: a live service grows its golden store with
**zero post-warmup compiles**.  ``at_epoch`` pins a thread's dispatches
to one epoch, which is how the serving runtime lets in-flight waves
finish on the epoch they were admitted under while new waves start on
the swapped one.  Shapes that do change (a capacity rebuild) need a
fresh engine, warmed before cutover (``swap_compat`` names the
mismatch).

**Sharded execution** (``mesh=..., shard_axis=...``): the golden store
— and, when indexed, the global index's cluster-sorted rows, split at
CSR window boundaries (``repro.index.shard``) — is data-sharded across
the devices of one mesh axis, and every public entry point
(``denoise``, ``denoise_masked``, ``select``, ``full_scan``) runs the
same coarse -> fine -> aggregate pipeline under ``jax.jit`` +
``shard_map``:

* shard-local coarse screening (exact ``ops.pdist`` over local rows, or
  ``ops.ivf_screen_local`` over the shard's windows of the *globally
  probed* index), with a cross-shard top-m threshold restricting the
  union of candidates to exactly the single-host candidate set;
* shard-local exact re-rank (``ops.support_distances``, the same
  gather/dense strategy machinery as single-host);
* a cross-shard **two-stage top-k**: local top-k (index, distance)
  pairs are all-gathered — k floats+ints per shard, never data rows —
  and the global k-th distance thresholds each shard's golden members
  (``sharding.crossshard_kth``);
* shard-local unnormalized softmax partials
  (``ops.golden_partial_aggregate``) merged exactly with a log-sum-exp
  ``psum`` (``sharding.lse_merge_mean``) into one
  golden-support aggregate.

Because the candidate partition equals the single-host candidate set
row-for-row (both exact and indexed modes), sharded outputs match the
single-host engine to fp32 reduction order — asserted on emulated
8-device CPU meshes in ``tests/test_sharded_engine.py``.  Program-cache
keys extend with the (shard_axis, n_shards) mesh shape.  The standalone
``distributed_golden_denoise`` composes the same primitives, so there
is one screening implementation in the repo.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.dataset import DatasetStore, downsample_proxy
from repro.core.schedules import Schedule
from repro.distributed.sharding import (gather_global_topk, lse_merge_mean,
                                        shard_map_compat)
from repro.index.schedule import ProbeSchedule
from repro.index.shard import shard_layout
from repro.index.store import GoldenIndex
from repro.kernels import ops, ref
from repro.kernels.common import gather_rows
from repro.obs import trace as obs_trace

Array = jnp.ndarray
NEG_INF = -1e30

# Gather/GEMM crossover: the gather-form candidate math beats the dense
# [B, N] GEMM once the touched rows drop below this fraction of N
# (measured on XLA:CPU in PR 2; GPU/TPU entries are conservative tables
# to be refined on real hardware — pass strategy="measure" to probe).
GATHER_CROSSOVER_FRAC = {"cpu": 0.10, "gpu": 0.35, "tpu": 0.50}

# Streamed-vs-materialized screening crossover: the one-pass tiled
# screen (``ops.screen_topm`` / the streaming full-scan LSE) caps peak
# live memory at O(B * (m + tile)), but its running-merge scan
# serializes work that the materialized form hands XLA as one big GEMM
# + top_k.  Re-measured at the PR-10 scan tile (SCAN_TILE=16384;
# N=65536, B=32): streamed 33/64/204 ms at m=512/1638/6553 vs
# materialized 20/40/130 ms — a ~1.6x gap (down from ~2-3x at
# tile=4096), still ~13x less temp memory (benchmarks/
# screen_speedup.py).  A two-level hierarchical merge (per-tile top-m
# + tree reduce, ``screen_topm_scan(hier=True)``) measured ~3-6x
# SLOWER than the carry on XLA:CPU — its TopK custom call fast-paths
# the carry's sorted-prefix input — so the crossover below is
# unchanged: materialize while the [B, N] buffer fits.
# ``screen="auto"`` therefore streams only once the [B, N] fp32 buffer
# would cross this per-platform budget (i.e. exactly when the dense
# path stops being allocatable/cheap); "streamed"/"materialized" force
# either form.  GPU/TPU budgets are conservative HBM-headroom guesses
# to refine on real hardware.
SCREEN_MATERIALIZE_BYTES = {"cpu": 1 << 31, "gpu": 1 << 30, "tpu": 1 << 28}


class StoreOperands(NamedTuple):
    """The engine's device operands for ONE store/index epoch.

    Every compiled body receives this pytree as a real jit *argument*
    (threaded by :meth:`GoldDiffEngine.jitter`) instead of closing over
    engine attributes — closure constants get baked into the XLA
    executable, which is exactly what hot-swapping a grown golden store
    must avoid.  Because the appendable store lifecycle
    (``repro.index.ingest``) keeps shapes static across appends, a new
    epoch with the same shapes reuses every compiled program as-is:
    zero post-warmup compiles on an epoch swap.

    Index fields are ``None`` on unindexed engines (None is empty pytree
    structure, so indexed/unindexed programs cannot collide).

    Store layout (the rule is stated in ``kernels/common.py``): ``X`` is
    the store's own ``[N, 1, D]`` rows (``DatasetStore.rows``, cast to
    the storage dtype here, once), on every backend.  In fp32 the Pallas
    re-rank and support-aggregate kernels DMA single candidate rows
    straight out of it (``kernels/common.fetch_tile``) and the full-scan
    kernel tiles it in ``(rows, 1, D)`` blocks; a 16-bit store, which
    XLA lays out as ``[N, D]`` tiles, is read through that bitcast view
    and an XLA row gather.  XLA math contracts the rows' last axis and
    squeezes gathered rows.  The store is never reshaped inside a step
    program (on a TPU that copies all of it), so the device holds one
    copy of the rows.
    """

    X: Array                        # [N, 1, D] dataset rows (storage dtype)
    proxy: Array                    # [N, dp] proxy rows (storage dtype)
    x_norms: Array                  # [N] fp32 ||x||^2
    proxy_norms: Array              # [N] fp32 ||proxy||^2
    proxy_sorted: Array | None = None        # [N, dp] cluster-sorted
    proxy_norms_sorted: Array | None = None  # [N] (+inf marks pad slots)
    perm: Array | None = None       # [N] sorted row -> dataset id
    offsets: Array | None = None    # [C+1] CSR window boundaries
    centroids: Array | None = None  # [C, dp]
    centroid_norms: Array | None = None      # [C] (+inf on spare windows)


def measure_crossover(x: Array, x_norms: Array, batch: int = 8,
                      rows: int = 2048, repeats: int = 3) -> float:
    """Probe the live device for the gather/GEMM crossover fraction.

    Times the dense [B, N] GEMM + lookup form against the gather +
    einsum form for ``rows`` touched rows, and extrapolates the touched
    fraction at which they break even (gather cost is ~linear in rows,
    dense cost ~constant).  A coarse estimate is fine here: it only
    picks a strategy, both of which are exact.  ``x``: the store rows
    ``[N, 1, D]``.
    """
    n = x.shape[0]
    rows = min(rows, n)
    q = jnp.zeros((batch, x.shape[-1]), x.dtype)
    idx = jnp.tile((jnp.arange(rows) * 997) % n, (batch, 1))
    dense = jax.jit(lambda q, i: jnp.take_along_axis(
        ref.pdist_ref(q, x, x_norms=x_norms), i, -1))
    gather = jax.jit(lambda q, i: ref.support_sqdist_ref(
        q, gather_rows(x, i), x_norms[i]))

    def best(fn):
        jax.block_until_ready(fn(q, idx))
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(q, idx))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    t_dense, t_gather = best(dense), best(gather)
    return float(np.clip((t_dense / t_gather) * (rows / n), 1e-3, 1.0))


@dataclasses.dataclass(frozen=True)
class GoldDiffConfig:
    """Subset-size schedules as fractions of N (paper defaults, Sec. 4.1)."""

    m_min_frac: float = 1 / 10   # = k_max (paper: random N/10 matches full)
    m_max_frac: float = 1 / 4
    k_min_frac: float = 1 / 20
    k_max_frac: float = 1 / 10
    proxy_factor: int = 4

    def sizes(self, n: int) -> tuple[int, int, int, int]:
        m_min = max(1, int(n * self.m_min_frac))
        m_max = max(m_min, int(n * self.m_max_frac))
        k_min = max(1, int(n * self.k_min_frac))
        k_max = max(k_min, int(n * self.k_max_frac))
        k_max = min(k_max, m_min)  # golden set always fits the candidate set
        return m_min, m_max, k_min, k_max


def schedule_sizes(cfg: GoldDiffConfig, schedule: Schedule, t: int,
                   n: int) -> tuple[int, int]:
    """(m_t, k_t) for integer timestep t (static mode; Eqs. 4/6)."""
    g = schedule.g_np(t)
    m_min, m_max, k_min, k_max = cfg.sizes(n)
    m_t = int(math.floor(m_min + (m_max - m_min) * (1.0 - g)))
    k_t = int(math.floor(k_min + (k_max - k_min) * g))
    return max(1, min(m_t, n)), max(1, min(k_t, m_t, n))


class GoldDiffEngine:
    """Compiled-program cache + kernel routing for the GoldDiff pipeline."""

    def __init__(self, store: DatasetStore, schedule: Schedule,
                 cfg: GoldDiffConfig | None = None,
                 backend: str | None = None,
                 storage_dtype=None, index: GoldenIndex | None = None,
                 probe_schedule: ProbeSchedule | None = None,
                 strategy: str = "auto", index_mode: str = "auto",
                 mesh=None, shard_axis: str = "data",
                 screen: str = "auto", screen_tile: int | None = None,
                 fused: str | bool = "auto", batch_axis: str | None = None):
        if backend is None:
            backend = ops.platform_backend()
        if backend not in ops.BACKENDS:
            raise ValueError(f"unknown backend {backend!r}; "
                             f"expected one of {ops.BACKENDS}")
        if strategy not in ("auto", "measure", "gather", "dense"):
            raise ValueError(f"unknown strategy {strategy!r}")
        if screen not in ("auto", "streamed", "materialized"):
            raise ValueError(f"unknown screen mode {screen!r}")
        if index_mode not in ("auto", "always"):
            raise ValueError(f"unknown index_mode {index_mode!r}")
        if fused not in ("auto", True, False):
            raise ValueError(f"unknown fused mode {fused!r}; expected "
                             f"'auto', True or False")
        if mesh is not None and shard_axis not in mesh.axis_names:
            raise ValueError(f"shard_axis {shard_axis!r} not in mesh axes "
                             f"{mesh.axis_names}")
        if batch_axis is not None:
            if mesh is None:
                raise ValueError("batch_axis requires a mesh")
            if batch_axis not in mesh.axis_names:
                raise ValueError(f"batch_axis {batch_axis!r} not in mesh "
                                 f"axes {mesh.axis_names}")
            if batch_axis == shard_axis:
                raise ValueError("batch_axis must differ from shard_axis "
                                 f"({shard_axis!r})")
        self.store = store
        self.schedule = schedule
        self.cfg = cfg or GoldDiffConfig()
        self.backend = backend
        self.storage_dtype = storage_dtype
        n = store.n
        # -- Golden Index (clustered, time-aware coarse screening)
        if index is not None and index.n != n:
            raise ValueError(f"index built for N={index.n}, store has N={n}")
        self.index = index
        self.index_mode = index_mode
        self.probe_schedule = probe_schedule or ProbeSchedule()
        if index is not None:
            # ascending-occupancy cumsum: worst-case row count held by
            # any P probed windows (the nprobe occupancy floor).  Host
            # constant — ``install_epoch`` requires identical offsets,
            # so it stays valid across epoch swaps.
            self._occ_cum = np.cumsum(np.sort(np.diff(
                np.asarray(index.offsets))))
        self._nprobe: dict[int, int] = {}
        # -- epoch-swappable store operands (see StoreOperands): the
        # construction store/index become epoch 0.  ``self.X`` etc. are
        # *properties* resolving through the current epoch (or, inside a
        # traced body, through the operands ``jitter`` threaded in).
        self._tls = threading.local()
        self._epochs: dict[int, StoreOperands] = {
            0: self._make_operands(store, index)}
        self._serving_epoch = 0
        # -- streamed-vs-materialized exact screening (build-time policy)
        self.screen = screen
        # None -> per-path default (SCAN_TILE for lax.scan, the VMEM
        # block for Pallas); an explicit int forces both
        self.screen_tile = None if screen_tile is None else int(screen_tile)
        # -- per-platform gather-vs-dense strategy (build-time selection)
        platform = jax.default_backend()
        self._screen_budget = SCREEN_MATERIALIZE_BYTES.get(platform, 1 << 31)
        if strategy == "measure":
            self.crossover_frac = measure_crossover(store.rows, self.x_norms)
        else:
            self.crossover_frac = GATHER_CROSSOVER_FRAC.get(platform, 0.10)
        if strategy in ("gather", "dense"):
            self.strategy = strategy
        else:
            # the fine stage touches m_t <= m_max rows per query
            m_max_frac = self.cfg.sizes(n)[1] / n
            self.strategy = ("gather" if m_max_frac <= self.crossover_frac
                             else "dense")
        # -- fused single-pass step (kernels/fused_step.py) policy
        self.fused = fused
        # -- sharded execution (data-sharded store over one mesh axis;
        # optionally batch-sharded queries over a second axis)
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.batch_axis = batch_axis
        if mesh is not None:
            self.n_shards = int(mesh.shape[shard_axis])
            self.batch_shards = (1 if batch_axis is None
                                 else int(mesh.shape[batch_axis]))
            self._layout = shard_layout(store, mesh, shard_axis, index=index,
                                        storage_dtype=storage_dtype)
        else:
            self.n_shards = 1
            self.batch_shards = 1
            self._layout = None
        # Per-timestep schedule constants, computed host-side exactly once.
        self._consts: dict[int, tuple[float, float]] = {}
        self._sizes: dict[int, tuple[int, int]] = {}
        self._programs: dict = {}
        # monotonic build counter: the serving runtime diffs it across a
        # segment dispatch to detect post-warmup compiles (a cache-size
        # delta misses evict-then-rebuild recompile storms)
        self._builds = 0
        # AOT compiles in flight inside ``parallel_compiles``
        self._compile_pool: ThreadPoolExecutor | None = None
        self._compile_futures: list[Future] = []

    # -- epoch-swappable store operands ---------------------------------------
    def _make_operands(self, store: DatasetStore,
                       index: GoldenIndex | None) -> StoreOperands:
        """Device operands for one (store, index) epoch.

        Dataset-side operands optionally drop to low-precision storage;
        norms always stay fp32, computed from the master copy (exact
        even under bf16).  Only the PROXY side lives in cluster-sorted
        order (the index already materializes it); X is addressed
        through ``perm`` — one [B, R] int gather — instead of
        duplicating the whole store in sorted order.
        """
        sd = self.storage_dtype
        X, proxy = store.rows, store.proxy
        if sd is not None and X.dtype != sd:
            X = X.astype(sd)
            proxy = proxy.astype(sd)
        kw = {}
        if index is not None:
            ps = index.proxy_sorted
            if sd is not None and ps.dtype != sd:
                ps = ps.astype(sd)
            kw = dict(proxy_sorted=ps,
                      proxy_norms_sorted=index.proxy_norms_sorted
                      .astype(jnp.float32),
                      perm=index.perm, offsets=index.offsets,
                      centroids=index.centroids,
                      centroid_norms=index.centroid_norms)
        return StoreOperands(X=X, proxy=proxy,
                             x_norms=store.x_norms.astype(jnp.float32),
                             proxy_norms=store.proxy_norms
                             .astype(jnp.float32), **kw)

    def _operands(self) -> StoreOperands:
        """Operand resolution order: the pytree bound by an in-flight
        ``jitter`` trace (tracers), else the pinned/serving epoch."""
        bound = getattr(self._tls, "bound", None)
        if bound is not None:
            return bound
        return self._epochs[self.call_epoch]

    @property
    def call_epoch(self) -> int:
        """Epoch the *next* dispatch resolves operands from: the epoch
        pinned by an enclosing :meth:`at_epoch` (how in-flight serving
        waves finish on the epoch they were admitted under), else the
        serving epoch."""
        pinned = getattr(self._tls, "pinned", None)
        return self._serving_epoch if pinned is None else pinned

    @property
    def serving_epoch(self) -> int:
        return self._serving_epoch

    # operand views (read-only; resolve per-epoch, or to tracers inside
    # a jitter-traced body)
    @property
    def X(self) -> Array:
        return self._operands().X

    @property
    def proxy(self) -> Array:
        return self._operands().proxy

    @property
    def x_norms(self) -> Array:
        return self._operands().x_norms

    @property
    def proxy_norms(self) -> Array:
        return self._operands().proxy_norms

    @property
    def proxy_sorted(self) -> Array:
        return self._operands().proxy_sorted

    @property
    def proxy_norms_sorted(self) -> Array:
        return self._operands().proxy_norms_sorted

    @property
    def index_perm(self) -> Array:
        return self._operands().perm

    def swap_compat(self, store: DatasetStore,
                    index: GoldenIndex | None) -> str | None:
        """Can ``(store, index)`` hot-swap into this engine's compiled
        programs?  Returns None when compatible, else a human-readable
        reason.

        Compatibility = every *static* ingredient of a compiled program
        (and of the host-side per-timestep constants) is unchanged:
        array shapes, indexed-ness, cluster count, padded probe width,
        and the CSR offsets themselves (they feed the static nprobe
        occupancy floor).  The appendable store lifecycle
        (``repro.index.ingest``) is built to preserve all of these
        across appends; a capacity rebuild changes them and needs a
        fresh engine (warmed before cutover by the caller).
        """
        if self.mesh is not None:
            return ("sharded engines do not hot-swap (the mesh layout "
                    "bakes per-shard arrays; rebuild the engine)")
        if (store.n, store.dim) != (self.store.n, self.store.dim):
            return (f"store shape ({store.n}, {store.dim}) != engine's "
                    f"({self.store.n}, {self.store.dim})")
        if (index is None) != (self.index is None):
            return "indexed-ness differs from the engine's"
        if index is not None:
            if index.num_clusters != self.index.num_clusters:
                return (f"num_clusters {index.num_clusters} != "
                        f"{self.index.num_clusters}")
            if index.max_cluster != self.index.max_cluster:
                return (f"max_cluster {index.max_cluster} != "
                        f"{self.index.max_cluster}")
            if not np.array_equal(np.asarray(index.offsets),
                                  np.asarray(self.index.offsets)):
                return ("CSR offsets differ (the static nprobe "
                        "occupancy floor depends on them)")
        return None

    def install_epoch(self, epoch: int, store: DatasetStore,
                      index: GoldenIndex | None = None) -> None:
        """Install ``(store, index)`` as a standby epoch.

        Shapes must match the construction epoch (``swap_compat``) —
        same shapes means every already-compiled program serves the new
        operands unmodified, so the swap costs zero compiles.  The
        serving epoch is unchanged until :meth:`set_serving_epoch`.
        """
        reason = self.swap_compat(store, index)
        if reason is not None:
            raise ValueError(f"epoch {epoch} cannot hot-swap: {reason}")
        self._epochs[int(epoch)] = self._make_operands(store, index)

    def set_serving_epoch(self, epoch: int) -> None:
        if int(epoch) not in self._epochs:
            raise KeyError(f"epoch {epoch} is not installed "
                           f"(have {sorted(self._epochs)})")
        self._serving_epoch = int(epoch)

    def retire_epoch(self, epoch: int) -> None:
        """Drop a standby epoch's operands (frees device memory)."""
        if int(epoch) == self._serving_epoch:
            raise ValueError(f"cannot retire the serving epoch {epoch}")
        self._epochs.pop(int(epoch), None)

    @contextlib.contextmanager
    def at_epoch(self, epoch: int):
        """Pin dispatches in this thread to ``epoch``'s operands (the
        serving runtime wraps each wave's segment in this, so in-flight
        waves finish on the epoch they were admitted under)."""
        prev = getattr(self._tls, "pinned", None)
        self._tls.pinned = int(epoch)
        try:
            yield
        finally:
            self._tls.pinned = prev

    def current_operands(self) -> StoreOperands:
        return self._epochs[self.call_epoch]

    @staticmethod
    def _ops_sig(ops_: StoreOperands) -> tuple:
        return tuple(None if a is None else (tuple(a.shape), str(a.dtype))
                     for a in ops_)

    def _bind(self, fn):
        """``fn`` with the store operands as a leading argument: inside
        the call the operand properties resolve to that argument."""
        def traced(ops_, *args):
            self._tls.bound = ops_
            try:
                return fn(*args)
            finally:
                self._tls.bound = None
        return traced

    def lower(self, fn, *specs):
        """``fn`` lowered for ``specs`` with the store operands threaded
        as arguments, exactly as :meth:`jitter` compiles it — for
        reading a program's text (which kernels it calls) without
        compiling or running it.  Single-host engines only."""
        if self.mesh is not None:
            raise ValueError("lower() reads single-host programs; a "
                             "sharded program bakes its mesh layout")
        return jax.jit(self._bind(fn)).lower(self.current_operands(),
                                             *specs)

    def jitter(self, fn, aot_specs: tuple | None = None):
        """Epoch-aware ``jax.jit``: compile ``fn`` with the store
        operands threaded as real arguments, not baked constants.

        The returned callable has ``fn``'s own signature; at each call
        it resolves the current (or ``at_epoch``-pinned) epoch's
        operands and passes them positionally, so one compiled
        executable serves every installed epoch with the same shapes.
        Inside the traced body the engine's operand properties resolve
        to the threaded tracers (thread-local bind), which is why the
        pipeline-stage methods need no signature changes.

        ``aot_specs`` (a tuple of ``ShapeDtypeStruct``) AOT-lowers for
        those input avals immediately — the serving warmup path (inside
        :meth:`parallel_compiles` the compile runs on its thread pool
        and the first call waits for it).  AOT executables are cached
        per operand-shape signature; an epoch whose shapes were never
        lowered falls back to a fresh compile, counted in ``_builds`` so
        the post-warmup recompile guard stays honest.  Sharded engines
        return plain ``jax.jit`` (their operands live in the mesh
        layout; they do not hot-swap).
        """
        if self.mesh is not None:
            return jax.jit(fn)
        jf = jax.jit(self._bind(fn))
        if aot_specs is None:
            return lambda *args: jf(self.current_operands(), *args)

        def compile_for(ops_):
            return jf.lower(ops_, *aot_specs).compile()

        ops0 = self.current_operands()
        if self._compile_pool is None:
            first = compile_for(ops0)
        else:
            first = self._compile_pool.submit(compile_for, ops0)
            self._compile_futures.append(first)
        execs = {self._ops_sig(ops0): first}

        def call(*args):
            ops_ = self.current_operands()
            sig = self._ops_sig(ops_)
            compiled = execs.get(sig)
            if compiled is None:         # changed-shape epoch: honest
                self._builds += 1        # post-warmup compile accounting
                compiled = compile_for(ops_)
                execs[sig] = compiled
            elif isinstance(compiled, Future):
                compiled = execs[sig] = compiled.result()
            return compiled(ops_, *args)

        return call

    @contextlib.contextmanager
    def parallel_compiles(self):
        """Run the AOT compiles started inside this block on a thread
        pool (half the host's cores, at most 8); leave it only when all
        have finished (re-raising the first failure).

        XLA compiles outside the GIL, and a serving warmup is a few
        dozen independent programs — on a TPU each takes ~20 s, most of
        it in the large-k ``top_k`` of the screen and re-rank — so
        compiling them side by side is what bounds cold start.  Nested
        blocks share the outer pool.
        """
        if self._compile_pool is not None:
            yield
            return
        self._compile_pool = ThreadPoolExecutor(
            max(1, min(8, (os.cpu_count() or 2) // 2)))
        try:
            yield
            for f in self._compile_futures:
                f.result()
        finally:
            self._compile_pool.shutdown(wait=True)
            self._compile_pool = None
            self._compile_futures = []

    # -- precomputed per-timestep constants ----------------------------------
    def sizes(self, t: int) -> tuple[int, int]:
        if t not in self._sizes:
            self._sizes[t] = schedule_sizes(self.cfg, self.schedule, t,
                                            self.store.n)
        return self._sizes[t]

    def constants(self, t: int) -> tuple[float, float]:
        """(a_t, sigma_t^2) as host floats for static-t programs."""
        if t not in self._consts:
            a = float(self.schedule.a[t])
            sig2 = float(self.schedule.sigma_np(t)) ** 2
            self._consts[t] = (a, sig2)
        return self._consts[t]

    def nprobe(self, t: int) -> int:
        """Scheduled probe count nprobe_t for a static timestep.

        Beyond the ProbeSchedule value, an **occupancy floor** is
        enforced: even the nprobe_t *smallest* windows must hold k_t
        real rows, so the golden support can always be filled with
        valid candidates and ``select()`` never returns padding ids.
        """
        if t not in self._nprobe:
            m_t, k_t = self.sizes(t)
            p = self.probe_schedule.nprobe(
                self.schedule.g_np(t), m_t, self.store.n,
                self.index.num_clusters)
            need = int(np.searchsorted(self._occ_cum, k_t) + 1)
            self._nprobe[t] = min(max(p, need), self.index.num_clusters)
        return self._nprobe[t]

    def padded_m(self, t: int) -> int:
        """Indexed candidate count: the probed capacity nprobe_t * L.

        IVF-Flat convention: *everything probed is re-ranked* — the
        time-aware candidate budget is nprobe_t itself (the capacity
        floor keeps it >= safety * m_t), and skipping the coarse top-m
        select over the gathered rows is what makes the indexed stage
        fast on every backend.
        """
        return self.nprobe(t) * self.index.max_cluster

    def use_index(self, t: int) -> bool:
        """Route coarse screening through the index at this timestep?

        ``auto`` falls back to the exact dense scan whenever the probed
        rows would exceed the platform's gather/GEMM crossover fraction
        of N — indexed screening degrades to exact screening, never to
        a slower program.
        """
        if self.index is None:
            return False
        if self.index_mode == "always":
            return True
        touched = self.nprobe(t) * self.index.max_cluster
        return touched <= self.crossover_frac * self.store.n

    def strategy_for(self, t: int) -> str:
        """Per-step candidate-math strategy.

        Indexed steps always gather: their candidate set is the probed
        capacity (small by the use_index rule), and the dense form's
        [B, N] GEMM would nullify the index's sublinear coarse stage.
        Exact steps keep the build-time platform selection (sized for
        the non-indexed m_max).
        """
        return "gather" if self.use_index(t) else self.strategy

    def use_fused(self, t: int) -> bool:
        """Route this static step through the fused single-pass kernel
        (``ops.fused_step``; program kind ``"fused_step"``)?

        Indexed steps never fuse — the IVF gather path's sublinear
        coarse stage is the whole point of the index, and the one-pass
        streaming kernel reads every store row.  ``True`` forces fusion
        on every exact step; ``auto`` fuses exactly where the staged
        pipeline pays for dense [B, N]-shaped work anyway: when the
        per-step strategy is "dense" (single-host), or on any exact
        sharded step (the fused sharded form additionally overlaps the
        cross-shard collectives with shard-local compute).  On
        gather-strategy steps (m_t far below the platform crossover)
        the staged re-rank touches only m_t rows, which a full-store
        streaming pass cannot beat, so ``auto`` leaves them staged.
        """
        if self.fused is False:
            return False
        if self.use_index(t):
            return False
        if self.fused is True:
            return True
        if self.mesh is not None:
            return True
        return self.strategy_for(t) == "dense"

    def _fused_masked(self, use_ix: bool) -> bool:
        """Masked-path fused decision.  The masked path is ONE program
        (per caps bucket), so the choice is global over the bucket —
        same rule as :meth:`use_fused` with the build-time strategy."""
        if self.fused is False or use_ix:
            return False
        if self.fused is True:
            return True
        if self.mesh is not None:
            return True
        return self.strategy == "dense"

    def use_stream(self, batch: int, n: int | None = None) -> bool:
        """Stream the exact screen / full scan at this (batch, store) size?

        ``auto`` streams exactly when the materialized [B, N] fp32
        distance/logits buffer would cross the platform's budget
        (``SCREEN_MATERIALIZE_BYTES``) — the streamed form is then the
        only one that allocates, at O(B * (m + tile)) live memory.  ``n``
        overrides the store size (the sharded bodies pass their local
        row count).
        """
        if self.screen != "auto":
            return self.screen == "streamed"
        n = self.store.n if n is None else n
        return 4 * int(batch) * int(n) > self._screen_budget

    # -- program cache -------------------------------------------------------
    def program(self, key, build):
        """Compiled-program cache keyed on (kind, t, shape, dtype,
        backend, strategy) (+ (nprobe_t, padded candidate count) when
        the step is indexed).

        This lookup is the engine's *dispatch seam*: when a fault hook
        is installed (``ops.set_dispatch_hook``, see
        ``repro.launch.faults``) it may evict cache entries before the
        hit/miss check (simulated recompile storms) and wrap the
        returned callable per dispatch (injected NaNs / latency /
        raised executor errors).  The cache itself always stores the
        unwrapped callable, and with no hook installed the raw cached
        object is returned — identity, zero overhead, zero recompiles
        (the CI recompile guard covers the warm path).
        """
        hook = ops.dispatch_hook()
        if hook is not None:
            hook.on_program(self, key)
        if key not in self._programs:
            self._programs[key] = build()
            self._builds += 1
        fn = self._programs[key]
        if hook is not None:
            return hook.wrap(key, fn)
        return fn

    def _index_sig(self, t: int) -> tuple:
        """(nprobe_t, padded candidate count) — keeps indexed and exact
        programs for the same (t, shape) from colliding in the cache."""
        if not self.use_index(t):
            return ()
        return (self.nprobe(t), self.padded_m(t))

    def _key(self, kind: str, t, x_t: Array, extra: tuple = ()):
        mesh_sig = () if self.mesh is None else \
            (("mesh", self.shard_axis, self.n_shards,
              self.batch_axis, self.batch_shards),)
        # streamed screening programs tile the store, so the tile size
        # is part of the compiled program's identity; sharded programs
        # stream by their LOCAL row count (what the shard bodies see)
        n_sig = None if self.mesh is None else self._layout.n_loc
        screen_sig = (("screen", "streamed", self.screen_tile)
                      if self.use_stream(x_t.shape[0], n_sig)
                      else ("screen", "materialized"),)
        return (kind, t, x_t.shape, str(x_t.dtype), self.backend,
                self.strategy_for(t)) + mesh_sig + screen_sig + tuple(extra)

    # -- pipeline stages (traceable bodies) ----------------------------------
    def _proxy_query(self, q: Array) -> Array:
        q_img = q.reshape(q.shape[:-1] + tuple(self.store.image_shape))
        qp = downsample_proxy(q_img, self.cfg.proxy_factor)
        if self.storage_dtype is not None:
            qp = qp.astype(self.storage_dtype)
        return qp

    def coarse(self, q: Array, m: int) -> Array:
        """Top-m candidates by exact proxy distance; [B, m].

        Routed through ``ops.screen_topm``: one pass over the proxy
        store either way, materializing the [B, N] distance matrix only
        below the streamed-vs-materialized crossover (``use_stream``).
        """
        return ops.screen_topm(self._proxy_query(q), self.proxy, m,
                               x_norms=self.proxy_norms,
                               tile=self.screen_tile,
                               stream=self.use_stream(q.shape[0]),
                               backend=self.backend)[0]

    def coarse_indexed(self, q: Array, m: int, nprobe_max: int,
                       nprobe=None) -> tuple[Array, Array]:
        """Candidates via the Golden Index; O(C d + nprobe L) in the
        capacity mode the engine uses (``m = nprobe_max * L``: every
        probed row feeds the exact re-rank, no proxy pass needed).

        Returns ``(pos, d2)`` with positions in **cluster-sorted** row
        space (+inf ``d2`` marks slots beyond the probed capacity).
        """
        o = self._operands()
        return ops.ivf_screen(self._proxy_query(q), o.proxy_sorted,
                              o.proxy_norms_sorted, o.offsets,
                              o.centroids, o.centroid_norms, m,
                              nprobe_max, self.index.max_cluster,
                              nprobe=nprobe, backend=self.backend)

    def _select_body(self, q: Array, t: int) -> tuple[Array, Array]:
        """(idx, d2) of the golden support for a rescaled query (static
        t).  ``idx`` are dataset row ids on both paths (indexed
        candidates map through ``index.perm`` before the re-rank)."""
        m_t, k_t = self.sizes(t)
        if self.use_index(t):
            mp = self.padded_m(t)
            with jax.named_scope("stage.ivf_screen"):
                pos, pd2 = self.coarse_indexed(q, mp, self.nprobe(t))
                cand = self.index_perm[pos]
            with jax.named_scope("stage.rerank"):
                return ops.golden_rerank(q, self.X, cand, min(k_t, mp),
                                         x_norms=self.x_norms,
                                         backend=self.backend,
                                         strategy="gather",
                                         valid=jnp.isfinite(pd2))
        with jax.named_scope("stage.screen"):
            cand = self.coarse(q, m_t)
        with jax.named_scope("stage.rerank"):
            return ops.golden_rerank(q, self.X, cand, k_t,
                                     x_norms=self.x_norms,
                                     backend=self.backend,
                                     strategy=self.strategy)

    def _select_ids_body(self, q: Array, t: int) -> Array:
        """Golden support as dataset row ids.

        The nprobe occupancy floor guarantees the probed windows hold
        >= k_t real rows, so these are always valid candidates."""
        return self._select_body(q, t)[0]

    def _denoise_body(self, x_t: Array, t: int) -> Array:
        """Fused static step: coarse -> rerank -> aggregate, distances
        computed exactly once."""
        a, sig2 = self.constants(t)
        q = x_t / a
        idx, d2 = self._select_body(q, t)
        with jax.named_scope("stage.aggregate"):
            # +inf distances (capacity-padded slots) clamp to NEG_INF logits
            lg = jnp.maximum(-d2 / (2.0 * sig2), NEG_INF)
            out = ops.golden_support_aggregate(self.X, idx, lg,
                                               backend=self.backend,
                                               strategy=self.strategy_for(t))
        return out.astype(x_t.dtype)

    def _fused_body(self, x_t: Array, t: int) -> Array:
        """Fused single-pass static step (``ops.fused_step``): coarse
        screen, exact re-rank and aggregation in one program; the
        streaming forms never materialize a [B, N] distance matrix or
        a [B, m, D] candidate tensor."""
        a, sig2 = self.constants(t)
        m_t, k_t = self.sizes(t)
        q = x_t / a
        with jax.named_scope("stage.fused_step"):
            out = ops.fused_step(q, self._proxy_query(q), self.X, self.proxy,
                                 m_t, k_t, sig2, x_norms=self.x_norms,
                                 proxy_norms=self.proxy_norms,
                                 backend=self.backend, strategy=self.strategy,
                                 stream=self.use_stream(x_t.shape[0]),
                                 tile=self.screen_tile)
        return out.astype(x_t.dtype)

    # -- sharded (mesh / shard_map) pipeline ---------------------------------
    def _shard_mapped(self, local, n_extra_rep: int = 0):
        """shard_map ``local`` over the layout's stacked per-shard arrays.

        The returned callable takes ``(x_t, *extra_replicated)``; the
        store (and index routing) arrays are threaded as explicit
        shard_map operands with ``P(shard_axis)`` specs — the query and
        the (small) centroid table are replicated.
        """
        L = self._layout
        row = [L.X, L.x_norms, L.proxy, L.proxy_norms, L.ids]
        rep = []
        if L.indexed:
            row += [L.offsets, L.wrange]
            rep = [L.centroids, L.centroid_norms]
        sp = PartitionSpec(self.shard_axis)
        # 2D (batch x store) mesh: the query batch (and the output)
        # shard over ``batch_axis`` while the store stays sharded over
        # ``shard_axis``; every cross-shard collective names only
        # shard_axis, so it runs independently per batch group.
        bsp = (PartitionSpec() if self.batch_axis is None
               else PartitionSpec(self.batch_axis))
        in_specs = (sp,) * len(row) + (bsp,) + \
            (PartitionSpec(),) * (n_extra_rep + len(rep))
        mapped = shard_map_compat(local, self.mesh, in_specs, bsp)

        def call(x_t, *extra):
            if self.batch_shards > 1 and x_t.shape[0] % self.batch_shards:
                raise ValueError(
                    f"batch {x_t.shape[0]} does not divide over "
                    f"batch_axis {self.batch_axis!r} "
                    f"(size {self.batch_shards})")
            return mapped(*row, x_t, *extra, *rep)

        return call

    def _unpack_local(self, args, n_extra: int = 0):
        """Split a shard_map body's operands back into named pieces
        (squeezing the leading size-1 shard dim off the sharded ones)."""
        L = self._layout
        args = list(args)
        X, xn, pr, pn, ids = (z[0] for z in args[:5])
        i = 5
        offs = wr = cents = cnorms = None
        if L.indexed:
            offs, wr = args[5][0], args[6][0]
            i = 7
        x_t = args[i]
        extra = tuple(args[i + 1: i + 1 + n_extra])
        if L.indexed:
            cents, cnorms = args[i + 1 + n_extra], args[i + 2 + n_extra]
        return (X, xn, pr, pn, ids, offs, wr, cents, cnorms, x_t) + extra

    def _sharded_static(self, kind: str, t: int):
        """Build the shard_map'd program for a static timestep.

        Shard-local coarse screen (exact or indexed) -> shard-local
        exact re-rank -> cross-shard two-stage top-k -> LSE-merged
        golden aggregate.  The surviving candidate partition equals the
        single-host candidate set row-for-row, so the result matches
        the single-host program to fp32 reduction order.
        """
        # deferred: retrieval module-imports repro.core.dataset, so a
        # top-level import would cycle when repro.distributed is the
        # first package imported
        from repro.distributed.retrieval import (golden_local_topk,
                                                 local_coarse_exact,
                                                 merged_golden_mean)

        L, ax = self._layout, self.shard_axis
        a, sig2 = self.constants(t)
        m_t, k_t = self.sizes(t)
        m_cap = min(m_t, L.n_loc)
        use_ix = self.use_index(t)
        if use_ix:
            p_t = self.nprobe(t)
            w_cap = min(p_t, L.w_max)
            k_cap = max(1, min(k_t, w_cap * L.max_cluster))
            strategy = "gather"
        else:
            k_cap = max(1, min(k_t, m_cap))
            strategy = self.strategy
        backend = self.backend

        def local(*args):
            (X, xn, pr, pn, ids, offs, wr, cents, cnorms,
             x_t) = self._unpack_local(args)
            q = x_t / a
            qp = self._proxy_query(q)
            if use_ix:
                with jax.named_scope("stage.ivf_screen"):
                    cand, pd2 = ops.ivf_screen_local(
                        qp, offs, cents, cnorms, wr[0], wr[1], p_t,
                        L.max_cluster, w_cap, L.n_loc, backend=backend)
                    valid = jnp.isfinite(pd2)
            else:
                with jax.named_scope("stage.screen"):
                    cand, valid = local_coarse_exact(
                        qp, pr, pn, m_cap, m_t, m_t, ax, backend=backend,
                        stream=self.use_stream(x_t.shape[0], L.n_loc),
                        tile=self.screen_tile)
            with jax.named_scope("stage.rerank"):
                idx, neg, kth = golden_local_topk(
                    X, xn, q, cand, valid, k_cap, k_t, k_t, ax,
                    backend=backend, strategy=strategy)
            if kind == "select":
                return gather_global_topk(ids[idx], neg, k_t, ax)
            with jax.named_scope("stage.aggregate"):
                out = merged_golden_mean(X, idx, neg, kth, sig2, ax,
                                         strategy=strategy)
            return out.astype(x_t.dtype)

        return self._shard_mapped(local)

    def _sharded_fused_static(self, t: int):
        """Sharded fused static step: same math as
        :meth:`_sharded_static` (bitwise — the fused local step reuses
        the identical kernel ops) with the cross-shard collectives
        issued ahead of the shard-local compute they overlap
        (``distributed/retrieval.fused_local_step``)."""
        from repro.distributed.retrieval import fused_local_step

        L, ax = self._layout, self.shard_axis
        a, sig2 = self.constants(t)
        m_t, k_t = self.sizes(t)
        m_cap = min(m_t, L.n_loc)
        k_cap = max(1, min(k_t, m_cap))
        strategy = self.strategy
        backend = self.backend

        def local(*args):
            (X, xn, pr, pn, ids, offs, wr, cents, cnorms,
             x_t) = self._unpack_local(args)
            q = x_t / a
            qp = self._proxy_query(q)
            with jax.named_scope("stage.fused_step"):
                out = fused_local_step(
                    X, xn, q, qp, pr, pn, m_cap, m_t, m_t, k_cap, k_t, k_t,
                    sig2, ax, backend=backend, strategy=strategy,
                    stream=self.use_stream(x_t.shape[0], L.n_loc),
                    tile=self.screen_tile)
            return out.astype(x_t.dtype)

        return self._shard_mapped(local)

    def _sharded_masked_body(self, x_t: Array, t: Array,
                             caps=None) -> Array:
        """Scan/pjit-compatible sharded step (one program, traced t).

        Mirrors ``denoise_masked`` exactly — same (m_t, k_t) masks,
        per-bucket caps, probe schedule, and occupancy floor — with
        the k_t cut applied through the cross-shard threshold instead
        of a positional mask (the same set, up to distance ties).
        """
        from repro.distributed.retrieval import (fused_local_step,
                                                 golden_local_topk,
                                                 local_coarse_exact,
                                                 merged_golden_mean)

        L, ax = self._layout, self.shard_axis
        n = self.store.n
        m_min, m_max, k_min, k_max = self.cfg.sizes(n)
        m_cap, k_cap, p_cap, use_ix = self._masked_caps(caps)
        fused = self._fused_masked(use_ix)
        m_loc = min(m_cap, L.n_loc)
        if use_ix:
            p_pad = p_cap
            w_cap = min(p_pad, L.w_max)
            k_loc = max(1, min(k_cap, w_cap * L.max_cluster))
            strategy = "gather"
        else:
            k_loc = max(1, min(k_cap, m_loc))
            strategy = self.strategy
        backend = self.backend

        def local(*args):
            (X, xn, pr, pn, ids, offs, wr, cents, cnorms, x_t,
             tt) = self._unpack_local(args, n_extra=1)
            g = self.schedule.g(tt)
            m_t = jnp.floor(m_min + (m_max - m_min) * (1.0 - g)) \
                .astype(jnp.int32)
            k_t = jnp.floor(k_min + (k_max - k_min) * g).astype(jnp.int32)
            m_t = jnp.minimum(m_t, m_cap)
            k_t = jnp.minimum(k_t, k_cap)
            a = jnp.asarray(self.schedule.a)[tt]
            sig = jnp.asarray(self.schedule.b)[tt] / a
            q = x_t / a
            qp = self._proxy_query(q)
            if fused:
                with jax.named_scope("stage.fused_step"):
                    out = fused_local_step(
                        X, xn, q, qp, pr, pn, m_loc, m_cap, m_t, k_loc,
                        k_cap, k_t, sig * sig, ax, backend=backend,
                        strategy=strategy,
                        stream=self.use_stream(x_t.shape[0], L.n_loc),
                        tile=self.screen_tile)
                return out.astype(x_t.dtype)
            if use_ix:
                with jax.named_scope("stage.ivf_screen"):
                    nprobe_t = self._masked_nprobe_t(g, m_t, k_t, p_pad)
                    cand, pd2 = ops.ivf_screen_local(
                        qp, offs, cents, cnorms, wr[0], wr[1], p_pad,
                        L.max_cluster, w_cap, L.n_loc, nprobe=nprobe_t,
                        backend=backend)
                    valid = jnp.isfinite(pd2)
            else:
                with jax.named_scope("stage.screen"):
                    cand, valid = local_coarse_exact(
                        qp, pr, pn, m_loc, m_cap, m_t, ax, backend=backend,
                        stream=self.use_stream(x_t.shape[0], L.n_loc),
                        tile=self.screen_tile)
            with jax.named_scope("stage.rerank"):
                idx, neg, kth = golden_local_topk(
                    X, xn, q, cand, valid, k_loc, k_cap, k_t, ax,
                    backend=backend, strategy=strategy)
            with jax.named_scope("stage.aggregate"):
                out = merged_golden_mean(X, idx, neg, kth, sig * sig, ax,
                                         strategy=strategy)
            return out.astype(x_t.dtype)

        return self._shard_mapped(local, n_extra_rep=1)(
            x_t, jnp.asarray(t, jnp.int32))

    def _sharded_full_scan(self, t: int):
        """Exact posterior mean over the sharded store: local partial
        softmax states (dense or tile-streamed), one LSE merge."""
        L, ax = self._layout, self.shard_axis
        a, sig2 = self.constants(t)

        def local(*args):
            (X, xn, pr, pn, ids, offs, wr, cents, cnorms,
             x_t) = self._unpack_local(args)
            q = x_t / a
            acc, m_l, l_l = ops.golden_full_partial(
                q, X, sig2, x_norms=xn,
                stream=self.use_stream(x_t.shape[0], L.n_loc),
                tile=self.screen_tile)
            return lse_merge_mean(acc, m_l, l_l, ax).astype(x_t.dtype)

        return self._shard_mapped(local)

    # -- observability (spans around host-level dispatches) -------------------
    def _traced(self, kind: str, t: int, x_t: Array, fn, compiled: bool):
        """Run ``fn(x_t)`` inside an ``engine.<kind>`` span.

        Only reached when the current tracer is enabled (callers branch
        on ``tracer().enabled`` first, so the disabled path stays
        bit-identical with zero extra work).  The stages inside the
        program carry ``stage.*`` named scopes, which the device trace
        reports; the dispatch blocks inside the span so the recorded
        duration is wall-clock, not enqueue time.
        """
        tr = obs_trace.tracer()
        with tr.span(f"engine.{kind}", t=int(t), backend=self.backend,
                     shape=tuple(x_t.shape), compile=bool(compiled),
                     indexed=bool(self.use_index(t))):
            out = fn(x_t)
            jax.block_until_ready(out)
        return out

    # -- public entry points -------------------------------------------------
    def select(self, x_t: Array, t: int, jit: bool = True) -> Array:
        """Golden support S_t for each query; [B, k_t] (static shapes).

        Always returns dataset row ids (indexed steps map back through
        ``index.perm``).
        """
        t = int(t)
        a, _ = self.constants(t)
        if self.mesh is not None:
            body = lambda: self._sharded_static("select", t)
        else:
            body = lambda: lambda x: self._select_ids_body(x / a, t)
        if not jit:
            return body()(x_t)
        b0 = self._builds
        fn = self.program(self._key("select", t, x_t, self._index_sig(t)),
                          lambda: self.jitter(body()))
        if not obs_trace.tracer().enabled:
            return fn(x_t)
        return self._traced("select", t, x_t, fn, self._builds > b0)

    def denoise(self, x_t: Array, t: int, jit: bool = True) -> Array:
        """Full GoldDiff step for the Optimal base (unbiased SS on S_t)."""
        t = int(t)
        fused = self.use_fused(t)
        kind = "fused_step" if fused else "denoise"
        if self.mesh is not None:
            body = (lambda: self._sharded_fused_static(t)) if fused \
                else (lambda: self._sharded_static("denoise", t))
        elif fused:
            body = lambda: lambda x: self._fused_body(x, t)
        else:
            body = lambda: lambda x: self._denoise_body(x, t)
        if not jit:
            return body()(x_t)
        b0 = self._builds
        fn = self.program(self._key(kind, t, x_t, self._index_sig(t)),
                          lambda: self.jitter(body()))
        if not obs_trace.tracer().enabled:
            return fn(x_t)
        return self._traced(kind, t, x_t, fn, self._builds > b0)

    # -- masked (scan/pjit-compatible) path -----------------------------------
    def _masked_nprobe_pad(self) -> int:
        """Worst-case nprobe_t over the whole t grid (static pad for the
        single masked program)."""
        if not hasattr(self, "_nprobe_pad"):
            T = self.schedule.num_steps
            self._nprobe_pad = max(self.nprobe(t) for t in range(1, T + 1))
        return self._nprobe_pad

    def _use_index_masked(self) -> bool:
        """The masked path is ONE program, so the indexed/exact decision
        is global: index only when even the worst-case probe width stays
        below the gather/GEMM crossover.

        ``index_mode="always"`` bypasses that guard: with a wide
        schedule (the default ProbeSchedule has f_hi = 1.0) the single
        program then pays worst-case probes — near the whole store —
        at EVERY step.  That mode exists for correctness testing; for
        performance use "auto", or a capped schedule (see
        ``benchmarks.index_speedup.SCALE_PROBES``)."""
        if self.index is None:
            return False
        if self.index_mode == "always":
            return True
        touched = self._masked_nprobe_pad() * self.index.max_cluster
        return touched <= self.crossover_frac * self.store.n

    def _masked_caps(self, caps) -> tuple[int, int, int, bool]:
        """Resolve a plan bucket's ``caps`` (or None for the legacy
        one-program-per-trajectory mode) into the masked program's
        static pads ``(m_cap, k_cap, nprobe_cap, use_index)``.

        ``caps=None`` pads to the worst case over the whole schedule —
        exactly the single masked program PR 4 served — while a
        ``plan.BucketCaps`` pads only to the bucket's own maxima, which
        is how ``sample_plan`` keeps static mode's FLOP savings at a
        handful of compiled programs (``core/plan.py``).
        """
        n = self.store.n
        _, m_max, _, k_max = self.cfg.sizes(n)
        if caps is None:
            use_ix = self._use_index_masked()
            return (m_max, k_max,
                    self._masked_nprobe_pad() if use_ix else 0, use_ix)
        use_ix = bool(caps.indexed) and self.index is not None
        return (min(int(caps.m_cap), n), int(caps.k_cap),
                int(caps.nprobe_cap), use_ix)

    def _masked_nprobe_t(self, g, m_t, k_t, p_cap: int):
        """Traced probe count for the masked/plan path.

        Mirrors :meth:`nprobe` exactly — the occupancy floor is
        evaluated at the *traced* k_t (``jnp.searchsorted`` over the
        ascending-occupancy cumsum), so on-grid steps probe the same
        windows as their static programs — then clips at the bucket's
        static pad ``p_cap`` (probes beyond the pad have no gather
        lanes to land in).
        """
        c = self.index.num_clusters
        nprobe_t = self.probe_schedule.nprobe_jnp(g, m_t, self.store.n, c)
        need = jnp.searchsorted(jnp.asarray(self._occ_cum, jnp.int32),
                                k_t.astype(jnp.int32)) + 1
        nprobe_t = jnp.maximum(nprobe_t, jnp.minimum(need, c))
        return jnp.clip(nprobe_t, 1, p_cap)

    def denoise_masked(self, x_t: Array, t: Array, caps=None) -> Array:
        """Scan/pjit-compatible step: shapes padded to the caps — the
        global (m_max, k_max) / worst-case probe width by default, or a
        plan bucket's ``caps`` (``plan.BucketCaps``) — sizes enter only
        through masks, ``t`` may be traced.

        Exact candidate distances are computed exactly once (over the
        padded candidate count) and the selected ones are reused for the
        aggregation softmax.
        """
        if self.mesh is not None:
            return self._sharded_masked_body(x_t, t, caps)
        n = self.store.n
        m_min, m_max, k_min, k_max = self.cfg.sizes(n)
        m_cap, k_cap, p_cap, use_ix = self._masked_caps(caps)
        g = self.schedule.g(t)
        m_t = jnp.floor(m_min + (m_max - m_min) * (1.0 - g)).astype(jnp.int32)
        k_t = jnp.floor(k_min + (k_max - k_min) * g).astype(jnp.int32)
        m_t = jnp.minimum(m_t, m_cap)
        k_t = jnp.minimum(k_t, k_cap)
        a = jnp.asarray(self.schedule.a)[t]
        sig = jnp.asarray(self.schedule.b)[t] / a
        q = x_t / a
        if self._fused_masked(use_ix):
            # fused single-pass masked step: the traced (m_t, k_t)
            # masks enter the fused epilogue (same +inf / NEG_INF
            # semantics as the staged masks below)
            with jax.named_scope("stage.fused_step"):
                out = ops.fused_step(
                    q, self._proxy_query(q), self.X, self.proxy,
                    m_cap, min(k_cap, m_cap), sig * sig,
                    x_norms=self.x_norms, proxy_norms=self.proxy_norms,
                    backend=self.backend, strategy=self.strategy,
                    stream=self.use_stream(x_t.shape[0]),
                    tile=self.screen_tile, m_t=m_t, k_t=k_t)
            return out.astype(x_t.dtype)
        if use_ix:
            # probe width varies with the traced t through the mask; the
            # gather is padded to the bucket's (or the grid's) worst
            # case.  All probed rows are candidates (IVF-Flat), so the
            # time-aware candidate budget is nprobe_t, not the m_t mask.
            p_pad = p_cap
            m_pad = p_pad * self.index.max_cluster
            with jax.named_scope("stage.ivf_screen"):
                nprobe_t = self._masked_nprobe_t(g, m_t, k_t, p_pad)
                pos, pd2 = self.coarse_indexed(q, m_pad, p_pad,
                                               nprobe=nprobe_t)
                cand = self.index_perm[pos]
                cand_mask = jnp.isfinite(pd2)
            strategy = "gather"          # dense [B, N] math would void
        else:                            # the index's sublinear coarse
            m_pad = m_cap
            with jax.named_scope("stage.screen"):
                cand = self.coarse(q, m_pad)                # top-m sorted
                cand_mask = jnp.arange(m_pad)[None, :] < m_t
            strategy = self.strategy
        k_pad = min(k_cap, m_pad)
        with jax.named_scope("stage.rerank"):
            d2 = ops.support_distances(q, self.X, cand, x_norms=self.x_norms,
                                       backend=self.backend,
                                       strategy=strategy)
            d2 = jnp.where(cand_mask, d2, jnp.inf)
            neg, pos = jax.lax.top_k(-d2, k_pad)
        with jax.named_scope("stage.aggregate"):
            idx = jnp.take_along_axis(cand, pos, axis=-1)
            # selection distances (neg == -d2) reused for the softmax
            # (k_max <= m_min <= m_t, so in the exact path every selected
            # candidate is valid; indexed capacity-padded slots carry -inf
            # and clamp to NEG_INF -> zero weight)
            lg = jnp.maximum(neg / (2.0 * sig * sig), NEG_INF)
            k_mask = jnp.arange(k_pad)[None, :] < k_t
            lg = jnp.where(k_mask, lg, NEG_INF)
            out = ops.golden_support_aggregate(self.X, idx, lg,
                                               backend=self.backend,
                                               strategy=strategy)
        return out.astype(x_t.dtype)

    def full_scan(self, x_t: Array, t: int, jit: bool = True) -> Array:
        """Exact posterior mean over the whole store (Eq. 2) via ops."""
        t = int(t)
        a, sig2 = self.constants(t)
        if self.mesh is not None:
            body = self._sharded_full_scan(t)
        else:
            body = lambda x: ops.golden_aggregate(
                x / a, self.X, sig2, x_norms=self.x_norms,
                backend=self.backend, stream=self.use_stream(x.shape[0]),
                tile=self.screen_tile).astype(x_t.dtype)
        if not jit:
            return body(x_t)
        b0 = self._builds
        fn = self.program(self._key("full_scan", t, x_t),
                          lambda: self.jitter(body))
        if not obs_trace.tracer().enabled:
            return fn(x_t)
        return self._traced("full_scan", t, x_t, fn, self._builds > b0)
