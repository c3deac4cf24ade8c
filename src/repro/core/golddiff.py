"""GoldDiff: Dynamic Time-Aware Golden Subset selection (paper Sec. 3.4).

Coarse-to-fine, training-free, plug-and-play:

1. *Adaptive coarse screening* — proxy distances in the downsampled space
   (``DatasetStore.proxy``) pick a candidate set C_t of size

       m_t = floor(m_min + (m_max - m_min) * (1 - g(sigma_t)))      (Eq. 4)

   (monotonically *increasing* as noise decreases: recall safety margin).

2. *Precision golden selection* — exact distances inside C_t pick the
   golden support S_t of size

       k_t = floor(k_min + (k_max - k_min) * g(sigma_t))            (Eq. 6)

   (monotonically *decreasing*: posterior progressive concentration).

3. The base denoiser is evaluated with ``support=S_t`` using the unbiased
   streaming softmax (Sec. 3.2).

Execution is delegated to :class:`repro.core.engine.GoldDiffEngine`,
which routes every stage through the kernel layer
(``repro.kernels.ops``: tiled ``pdist`` screening, ``golden_rerank``
returning indices + distances, streaming ``golden_support_aggregate``)
and caches one compiled program per (timestep, shape, backend, dtype).

Two execution modes:

* ``static`` — each timestep uses its integer (m_t, k_t); separate XLA
  programs per step, true FLOP savings (matches the paper's complexity
  table; used by the benchmarks).
* ``masked`` — a single program padded to (m_max, k_max) with validity
  masks, suitable for ``lax.scan``-based samplers / pjit.  Exact
  candidate distances are computed exactly once per step and reused for
  the aggregation softmax.

Note: Eq. 5 in the paper writes the exact re-ranking distance as
``||x_t - x_i||``; we use the rescaled ``||x_t/a_t - x_i||`` which induces
the same ordering as the true logits (it differs only by the global 1/a_t
factor on the query), so the selected set equals the top-k *by posterior
weight* — the quantity Theorem 1 bounds.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dataset import DatasetStore, downsample_proxy
from repro.core.denoisers import OptimalDenoiser
from repro.core.engine import (GoldDiffConfig, GoldDiffEngine,
                               schedule_sizes)
from repro.core.schedules import Schedule
from repro.kernels import ops

Array = jnp.ndarray

__all__ = ["GoldDiff", "GoldDiffConfig", "GoldDiffEngine", "schedule_sizes",
           "coarse_screen", "golden_select"]


def coarse_screen(store: DatasetStore, q: Array, m: int, proxy_factor: int,
                  backend: str | None = None) -> Array:
    """Top-m candidate indices by proxy distance.  q: [B, D] -> [B, m].

    Routed through ``ops.pdist`` (tiled matmul form, precomputed norms).
    """
    q_img = q.reshape(q.shape[:-1] + tuple(store.image_shape))
    qp = downsample_proxy(q_img, proxy_factor)
    d2 = ops.pdist(qp, store.proxy, x_norms=store.proxy_norms,
                   backend=backend)
    return jax.lax.top_k(-d2, m)[1]


def golden_select(store: DatasetStore, q: Array, cand: Array, k: int,
                  backend: str | None = None) -> Array:
    """Exact re-ranking inside the candidate set (Eq. 5). Returns [B, k].

    Matmul-form distances via ``ops.golden_rerank`` — no [B, m, D]
    broadcast-subtract temporaries.
    """
    idx, _ = ops.golden_rerank(q, store.rows, cand, k,
                               x_norms=store.x_norms,
                               backend=backend)
    return idx


class GoldDiff:
    """Plug-and-play wrapper: GoldDiff(base_denoiser) (paper Tab. 5).

    ``backend`` / ``storage_dtype`` / ``strategy`` configure the
    execution engine (see :class:`GoldDiffEngine`); ``backend=None``
    (default) inherits the base denoiser's backend so the fused path and
    the explicit ``support=`` path run the same kernels, and a base
    without one takes the platform's (``ops.platform_backend``:
    ``pallas`` on TPU, ``xla`` elsewhere).  Pass
    ``index=repro.index.build_index(store)`` to route coarse screening
    through the clustered Golden Index (sublinear in N; probe width set
    by ``probe_schedule``).  Pass ``mesh=``/``shard_axis=`` to
    data-shard the golden store (and the index) across a mesh axis:
    selection and aggregation then run under shard_map with a
    cross-shard two-stage top-k + log-sum-exp merge (see
    :class:`GoldDiffEngine`).  ``screen=``/``screen_tile=`` control the
    streamed-vs-materialized exact screening crossover (one-pass tiled
    top-m at O(B (m + tile)) memory vs the dense [B, N] matrix).
    ``fused="auto"|True|False`` routes eligible steps through the
    single-pass fused step kernel (``kernels/fused_step.py``: screen +
    re-rank + aggregate in one program, no [B, m, D] candidate
    materialization); ``batch_axis=`` shards the *query* batch over a
    second mesh axis (2D batch x store mesh).
    """

    def __init__(self, base, cfg: GoldDiffConfig | None = None,
                 jit_steps: bool = True, backend: str | None = None,
                 storage_dtype=None, index=None, probe_schedule=None,
                 strategy: str = "auto", index_mode: str = "auto",
                 mesh=None, shard_axis: str = "data",
                 screen: str = "auto", screen_tile: int | None = None,
                 fused: str | bool = "auto", batch_axis: str | None = None):
        self.base = base
        self.cfg = cfg or GoldDiffConfig()
        self.store: DatasetStore = base.store
        self.schedule: Schedule = base.schedule
        # GoldDiff always aggregates with the *unbiased* streaming softmax.
        if getattr(base, "weighting", "ss") == "wss":
            base.weighting = "ss"
        self.name = f"golddiff+{base.name}"
        self.jit_steps = jit_steps
        if backend is None:
            backend = getattr(base, "backend", None)
        engine_kw = {} if screen_tile is None else \
            {"screen_tile": screen_tile}
        self.engine = GoldDiffEngine(self.store, self.schedule, self.cfg,
                                     backend=backend,
                                     storage_dtype=storage_dtype,
                                     index=index,
                                     probe_schedule=probe_schedule,
                                     strategy=strategy,
                                     index_mode=index_mode,
                                     mesh=mesh, shard_axis=shard_axis,
                                     screen=screen, fused=fused,
                                     batch_axis=batch_axis, **engine_kw)

    @property
    def backend(self) -> str:
        return self.engine.backend

    # -- static mode ---------------------------------------------------------
    def select(self, x_t: Array, t: int) -> Array:
        """Golden support S_t for each query; [B, k_t] (static shapes)."""
        return self.engine.select(x_t, int(t), jit=self.jit_steps)

    def __call__(self, x_t: Array, t: int, support: Array | None = None) -> Array:
        if support is not None:
            return self.base(x_t, t, support=support)
        t = int(t)
        if isinstance(self.base, OptimalDenoiser):
            # fused engine path: selection distances reused for the
            # aggregation softmax, one compiled program per step
            return self.engine.denoise(x_t, t, jit=self.jit_steps)
        # patch-family bases compute their own (feature-space) logits on
        # the golden support; only the selection runs through the engine
        if not self.jit_steps:
            return self.base(x_t, t, support=self.select(x_t, t))
        # patch-based bases build numpy feature caches lazily; force
        # them OUTSIDE the traced program
        if hasattr(self.base, "_dataset_features"):
            self.base._dataset_features(self.base.patch_size(t))
        if self.engine.mesh is not None:
            # sharded selection is its own shard_map program; the base's
            # feature-space logits then run on the replicated support
            return self.base(x_t, t, support=self.select(x_t, t))
        a, _ = self.engine.constants(t)
        fn = self.engine.program(
            self.engine._key(("wrap", self.base.name), t, x_t,
                             self.engine._index_sig(t)),
            lambda: self.engine.jitter(lambda x: self.base(
                x, t, support=self.engine._select_ids_body(x / a, t))))
        return fn(x_t)

    # -- masked (scan-compatible) mode ----------------------------------------
    def call_masked(self, x_t: Array, t: Array, caps=None) -> Array:
        """One-program variant: shapes padded to (m_max, k_max), sizes masked.

        ``t`` may be a traced integer array; m_t/k_t enter only through
        masks, so this body is safe inside ``lax.scan`` / pjit.  (Optimal
        base only: patch bases need static patch sizes -> static mode.)
        ``caps`` (a ``plan.BucketCaps``) pads to one trajectory-plan
        bucket's shapes instead of the global worst case — the body
        ``sampler.sample_plan`` scans per bucket.
        """
        return self.engine.denoise_masked(x_t, t, caps)
