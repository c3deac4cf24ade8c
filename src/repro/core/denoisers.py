"""Analytical denoisers (the paper's baseline hierarchy, Sec. 4.1).

Every denoiser maps a batch of noisy points ``x_t: [B, D]`` at integer
timestep ``t`` to the posterior-mean estimate ``x0_hat: [B, D]``:

* ``OptimalDenoiser``  — exact empirical-Bayes posterior mean (Eq. 2),
  O(N D) full scan (De Bortoli, 2022).
* ``WienerDenoiser``   — linear-MMSE estimator from dataset mean/covariance
  (Wiener, 1949); O(D^2) but independent of N at sampling time.
* ``PatchDenoiser``    — Kamb & Ganguli (2024) style per-pixel patch
  posterior with a timestep-dependent patch size p_t.
* ``PCADenoiser``      — Lukoianov et al. (2025): patch features projected
  onto a rank-r PCA basis; default *biased* WSS weighting (the smoothing
  bias of Sec. 3.2).

All support an optional per-query golden ``support`` (integer indices
``[B, k]``): when given, the posterior is computed *only* over those
training points — this is the hook GoldDiff plugs into (Tab. 5
"orthogonality": GoldDiff + {Optimal, Kamb, PCA}).
"""
from __future__ import annotations

import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import streaming
from repro.core.dataset import DatasetStore, pairwise_sq_dists
from repro.core.schedules import Schedule
from repro.kernels import ops
from repro.kernels.common import HIGHEST, check_rows, gather_rows

Array = jnp.ndarray
Weighting = Literal["ss", "wss"]


# ---------------------------------------------------------------------------
# Optimal (full-scan empirical Bayes, Eq. 2)
# ---------------------------------------------------------------------------

class OptimalDenoiser:
    """Exact posterior mean over the training set (or a golden support).

    The unbiased (``ss``) paths route through ``repro.kernels.ops``
    (full scans via the streaming-softmax ``golden_aggregate`` kernel,
    supports via matmul-form ``support_distances`` +
    ``golden_support_aggregate``); ``backend`` selects
    xla / pallas_interpret / pallas uniformly, and ``None`` takes the
    platform's (``ops.platform_backend``).  The biased ``wss``
    weighting keeps the chunked streaming estimators (the bias model of
    Sec. 3.2 is chunk-structured by definition).
    """

    name = "optimal"

    def __init__(self, store: DatasetStore, schedule: Schedule,
                 chunk: int = 8192, weighting: Weighting = "ss",
                 backend: str | None = None):
        self.store = store
        self.schedule = schedule
        self.chunk = chunk
        self.weighting = weighting
        self.backend = ops.platform_backend() if backend is None else backend

    def logits(self, x_t: Array, t: int) -> Array:
        """Full-scan logits l_i = -||x_t/a_t - x_i||^2 / (2 sigma_t^2); [B,N]."""
        a = float(self.schedule.a[t])
        sig2 = float(self.schedule.sigma_np(t)) ** 2
        q = x_t / a
        d2 = pairwise_sq_dists(q, self.store.rows, self.store.x_norms)
        return -d2 / (2.0 * sig2)

    def __call__(self, x_t: Array, t: int, support: Array | None = None) -> Array:
        if support is not None:
            return self._on_support(x_t, t, support)
        if self.weighting == "wss":
            # the WSS baseline's chunk einsums take [N, D] values: on a
            # TPU this squeeze copies the store (not a serving path)
            return streaming.weighted_streaming_softmax_mean(
                self.logits(x_t, t), self.store.rows[:, 0], self.chunk)
        a = float(self.schedule.a[t])
        sig2 = float(self.schedule.sigma_np(t)) ** 2
        return ops.golden_aggregate(x_t / a, self.store.rows, sig2,
                                    x_norms=self.store.x_norms,
                                    backend=self.backend).astype(x_t.dtype)

    def _on_support(self, x_t: Array, t: int, idx: Array,
                    mask: Array | None = None) -> Array:
        a = float(self.schedule.a[t])
        sig2 = float(self.schedule.sigma_np(t)) ** 2
        q = x_t / a                                # [B, D]
        d2 = ops.support_distances(q, self.store.rows, idx,
                                   x_norms=self.store.x_norms,
                                   backend=self.backend)
        lg = -d2 / (2.0 * sig2)
        if mask is not None:
            lg = jnp.where(mask, lg, streaming.NEG_INF)
        if self.weighting == "wss":
            return streaming.wss_combine(lg, gather_rows(self.store.rows, idx))
        return ops.golden_support_aggregate(
            self.store.rows, idx, lg, backend=self.backend
        ).astype(x_t.dtype)


# ---------------------------------------------------------------------------
# Wiener (linear MMSE; N enters only through precomputed statistics)
# ---------------------------------------------------------------------------

class WienerBasis(NamedTuple):
    """The Gaussian fit of a store: ``Sigma = V diag(lam) V^T``."""

    mu: Array                   # [D] mean of the store's rows
    V: Array                    # [D, r] top-r eigenvectors of Sigma
    lam: Array                  # [r] their eigenvalues, descending, >= 0


# Rows of the store centred and contracted at a time: 64 MiB of fp32
# (5,461 rows at D=3072, 1,365 at D=12288), so no step of the Gram sum
# holds more than a block beside the [D, D] accumulator.
WIENER_BLOCK_BYTES = 1 << 26


def wiener_basis(rows: Array, x_norms: Array, rank: int | None = None
                 ) -> WienerBasis:
    """Mean and top-``rank`` eigenpairs of the covariance of the store
    rows ``[N, 1, D]``, on the device: one program
    (:func:`_wiener_basis`).

    Rows whose ``x_norms`` entry is ``+inf`` are padding (the repo-wide
    convention: capacity-padded and sharded stores) and are left out.
    The rank defaults to ``min(N, D)``, the SVD form's ``lam = s^2 / N``
    and ``V = Vt^T``.  Rows sharded over their leading axis are split
    into the shards' row ranges, so each device sums the Gram matrix of
    its own rows and one all-reduce adds them."""
    n, _, d = check_rows(rows).shape
    r = min(n, d) if rank is None else min(int(rank), n, d)
    groups = _row_shards(rows)
    block = max(1, min(n // groups, WIENER_BLOCK_BYTES // (4 * d)))
    return _wiener_basis(rows, x_norms, rank=r, groups=groups, block=block)


def _row_shards(rows: Array) -> int:
    """How many ways ``rows`` is split along its leading axis."""
    sharding = getattr(rows, "sharding", None)
    if sharding is None:
        return 1
    return rows.shape[0] // sharding.shard_shape(rows.shape)[0]


@functools.partial(jax.jit, static_argnames=("rank", "groups", "block"))
def _wiener_basis(rows, x_norms, rank: int, groups: int, block: int
                  ) -> WienerBasis:
    """``rows [N, 1, D]`` -> :class:`WienerBasis`, all in fp32.

    The leading axis is split into ``groups`` row ranges (a bitcast);
    ``C = sum (x - mu)(x - mu)^T / n`` is summed ``block`` rows of each
    range at a time, each block centred as it is read, at ``HIGHEST``
    precision; the eigendecomposition of ``C`` gives the basis.  The last block
    of a range starts early rather than padding the store, and masks
    the rows it re-reads."""
    n, _, d = rows.shape
    per = n // groups
    xg = rows.reshape(groups, per, 1, d)
    valid = jnp.isfinite(x_norms).reshape(groups, per)
    count = jnp.sum(valid).astype(jnp.float32)
    mu = jnp.sum(jnp.where(valid[..., None, None], xg, 0.0),
                 axis=(0, 1, 2), dtype=jnp.float32) / count

    def add_block(i, gram):
        start = jnp.minimum(i * block, per - block)
        xb = jax.lax.dynamic_slice_in_dim(xg, start, block, axis=1)
        keep = jax.lax.dynamic_slice_in_dim(valid, start, block, axis=1)
        keep &= start + jnp.arange(block) >= i * block
        xc = jnp.where(keep[..., None, None], xb.astype(jnp.float32) - mu,
                       0.0)
        return gram + jnp.einsum("gnod,gnoe->gde", xc, xc,
                                 precision=HIGHEST)

    gram = jax.lax.fori_loop(0, -(-per // block), add_block,
                             jnp.zeros((groups, d, d), jnp.float32))
    lam, vecs = _eigh(jnp.sum(gram, 0) / count)
    return WienerBasis(mu=mu, V=vecs[:, ::-1][:, :rank],
                       lam=jnp.maximum(lam[::-1][:rank], 0.0))


def _eigh(c: Array) -> tuple[Array, Array]:
    """(eigenvalues ascending, eigenvectors) of the symmetric ``c``.  On
    a TPU the Jacobi custom call (one op; at D=12288 under five
    ``[D, D]`` temporaries): JAX's default there, QDWH, took 79 s to
    compile for a v5e at D=1024 alone and held about nine."""
    def jacobi(c):
        vecs, lam = jax.lax.linalg.eigh(
            c, implementation=jax.lax.linalg.EighImplementation.JACOBI)
        return lam, vecs
    return jax.lax.platform_dependent(
        c, tpu=jacobi, default=lambda c: tuple(jnp.linalg.eigh(c)))


def wiener_x0(basis: WienerBasis, x_t: Array, a, b) -> Array:
    """The Wiener posterior mean of ``x_t`` at the noise level
    ``(a, b)``: ``mu + V diag(a lam / (a^2 lam + b^2)) V^T (x_t - a mu)``."""
    mu, V, lam = basis
    coeff = (a * lam) / (a * a * lam + b * b)                 # [r]
    return mu + (((x_t - a * mu) @ V) * coeff) @ V.T


class WienerDenoiser:
    """x0_hat = mu + Sigma a (a^2 Sigma + b^2 I)^-1 (x_t - a mu).

    Sigma is represented through its top-``rank`` eigenpairs
    (:func:`wiener_basis`, built on the device from the store's rows),
    so the inverse is exact and rank-limited.
    """

    name = "wiener"

    def __init__(self, store: DatasetStore, schedule: Schedule,
                 rank: int | None = None):
        self.store = store
        self.schedule = schedule
        self.basis = wiener_basis(store.rows, store.x_norms, rank)

    def __call__(self, x_t: Array, t: int, support: Array | None = None) -> Array:
        # support is meaningless for a statistics-only estimator (paper
        # excludes Wiener from the orthogonality study for this reason).
        return wiener_x0(self.basis, x_t, float(self.schedule.a[t]),
                         float(self.schedule.b[t]))


# ---------------------------------------------------------------------------
# Patch-based (Kamb & Ganguli) and PCA (Lukoianov et al.)
# ---------------------------------------------------------------------------

def _box_patch_dist(qf: Array, xf: Array, patch: int) -> Array:
    """Per-pixel patch squared distance between query/data feature maps.

    qf: [B, H, W, C], xf: [Nc, H, W, C] -> [B, Nc, H, W]
    (sum over a patch x patch window of per-pixel squared diffs, SAME pad).
    """
    diff2 = jnp.sum((qf[:, None] - xf[None]) ** 2, axis=-1)   # [B,Nc,H,W]
    if patch <= 1:
        return diff2
    return jax.lax.reduce_window(
        diff2, 0.0, jax.lax.add,
        window_dimensions=(1, 1, patch, patch),
        window_strides=(1, 1, 1, 1), padding="SAME")


class PatchDenoiser:
    """Kamb-style per-pixel patch posterior.

    Each pixel is denoised with its own softmax over the training set where
    the logit compares the local patch around that pixel.  Patch size p_t
    follows the paper's heuristic receptive-field schedule: large when the
    noise dominates (global averaging), small near the data manifold
    (locality -> generalization).
    """

    name = "kamb"
    default_weighting: Weighting = "ss"

    def __init__(self, store: DatasetStore, schedule: Schedule,
                 patch_min: int = 3, patch_max: int = 11, chunk: int = 128,
                 weighting: Weighting | None = None):
        if len(store.image_shape) != 3:
            raise ValueError("patch denoisers need [H, W, C] data")
        self.store = store
        self.schedule = schedule
        self.patch_min = patch_min
        self.patch_max = patch_max
        self.chunk = chunk
        self.weighting = weighting or self.default_weighting
        self.h, self.w, self.c = store.image_shape

    # -- hooks overridden by PCADenoiser ------------------------------------
    def features(self, imgs: Array, patch: int) -> Array:
        """Feature map whose per-pixel L2 distance defines the patch logit."""
        return imgs

    def _chunk_features(self, s: int, e: int, ximg: Array, patch: int) -> Array:
        return self.features(ximg, patch)

    def feature_dist(self, qf: Array, xf: Array, patch: int) -> Array:
        return _box_patch_dist(qf, xf, patch)

    # ------------------------------------------------------------------------
    def patch_size(self, t: int) -> int:
        g = self.schedule.g_np(t)
        p = int(round(self.patch_min + (self.patch_max - self.patch_min) * g))
        return p | 1  # odd

    def _imgs(self, flat: Array) -> Array:
        return flat.reshape(flat.shape[:-1] + (self.h, self.w, self.c))

    def __call__(self, x_t: Array, t: int, support: Array | None = None,
                 mask: Array | None = None) -> Array:
        a = float(self.schedule.a[t])
        sig2 = float(self.schedule.sigma_np(t)) ** 2
        patch = self.patch_size(t)
        q = self._imgs(x_t / a)                                 # [B,H,W,C]
        qf = self.features(q, patch)
        b = q.shape[0]
        d = self.h * self.w * self.c

        if support is not None:
            return self._on_support(q, qf, t, support, patch, sig2, mask)

        # full scan, chunked over the dataset with online softmax per pixel
        n = self.store.n
        state = streaming.init_state((b, self.h * self.w), self.c)
        chunk = min(self.chunk, n)
        for s in range(0, n, chunk):
            e = min(s + chunk, n)
            ximg = self._imgs(self.store.rows[s:e, 0])
            xf = self._chunk_features(s, e, ximg, patch)
            dist = self.feature_dist(qf, xf, patch)             # [B,nc,H,W]
            lg = (-dist / (2.0 * sig2)).reshape(b, e - s, -1)
            lg = jnp.moveaxis(lg, 1, -1)                        # [B,HW,nc]
            vals = jnp.moveaxis(ximg.reshape(e - s, -1, self.c), 0, 1)  # [HW,nc,C]
            state = streaming.update_state(state, lg, vals)
        out = streaming.finalize(state)                          # [B,HW,C]
        return out.reshape(b, d)

    def _on_support(self, q: Array, qf: Array, t: int, idx: Array,
                    patch: int, sig2: float, mask: Array | None) -> Array:
        bsz = q.shape[0]

        def one(qi, qfi, ids, mi):
            ximg = self._imgs(gather_rows(self.store.rows, ids))  # [k,H,W,C]
            xf = self.features(ximg, patch)
            dist = self.feature_dist(qfi[None], xf, patch)[0]    # [k,H,W]
            lg = -dist / (2.0 * sig2)
            if mi is not None:
                lg = jnp.where(mi[:, None, None], lg, streaming.NEG_INF)
            if self.weighting == "wss":
                k = lg.shape[0]
                lgp = jnp.moveaxis(lg.reshape(k, -1), 0, -1)     # [HW,k]
                vals = jnp.moveaxis(ximg.reshape(k, -1, self.c), 0, 1)
                out = streaming.wss_combine(lgp, vals)           # [HW,C]
                return out.reshape(self.h, self.w, self.c)
            w = jax.nn.softmax(lg, axis=0)                       # [k,H,W]
            return jnp.einsum("khw,khwc->hwc", w, ximg)

        m_arg = mask if mask is not None else jnp.ones(idx.shape, bool)
        out = jax.vmap(lambda a_, b_, c_, d_: one(a_, b_, c_, d_))(
            q, qf, idx, m_arg)
        return out.reshape(bsz, -1)


class PCADenoiser(PatchDenoiser):
    """Lukoianov et al.: patch features projected on a rank-r PCA basis.

    Patch extraction + projection is a single convolution with the PCA
    filters, so the per-pixel distance runs in the r-dim subspace
    (O(N p_t D) -> O(N r D / p^2) distance work).  Default weighting is the
    *biased* WSS the original method uses; GoldDiff swaps it for the
    unbiased SS on the golden support (Sec. 3.2).
    """

    name = "pca"
    default_weighting: Weighting = "wss"

    def __init__(self, store: DatasetStore, schedule: Schedule,
                 rank: int = 8, num_fit_patches: int = 4096, seed: int = 0,
                 **kw):
        super().__init__(store, schedule, **kw)
        self.rank = rank
        self.num_fit_patches = num_fit_patches
        self.seed = seed
        self._bases: dict[int, Array] = {}

    def _dataset_features(self, patch: int) -> Array:
        """Cached PCA feature maps of the WHOLE dataset for this patch size.

        Features are query-independent, so the golden-support path gathers
        precomputed features instead of re-running the projection conv per
        query (the fix for the 2.4x slowdown first measured in Tab. 2).
        """
        key = ("feat", patch)
        if key not in self._bases:
            imgs = self._imgs(self.store.rows[:, 0])   # a relayout anyway
            chunks = []
            step = max(1, 4096 // max(self.h // 8, 1))
            for s in range(0, self.store.n, step):
                chunks.append(self.features(imgs[s:s + step], patch))
            self._bases[key] = jnp.concatenate(chunks, axis=0)
        return self._bases[key]

    def _on_support(self, q, qf, t, idx, patch, sig2, mask):
        bsz = q.shape[0]
        feats = self._dataset_features(patch)                # [N,H,W,r]

        def one(qfi, ids, mi):
            xf = feats[ids]                                  # [k,H,W,r]
            dist = jnp.sum((qfi[None] - xf) ** 2, axis=-1)   # [k,H,W]
            lg = -dist / (2.0 * sig2)
            if mi is not None:
                lg = jnp.where(mi[:, None, None], lg, streaming.NEG_INF)
            ximg = self._imgs(gather_rows(self.store.rows, ids))
            if self.weighting == "wss":
                k = lg.shape[0]
                lgp = jnp.moveaxis(lg.reshape(k, -1), 0, -1)
                vals = jnp.moveaxis(ximg.reshape(k, -1, self.c), 0, 1)
                return streaming.wss_combine(lgp, vals).reshape(
                    self.h, self.w, self.c)
            w = jax.nn.softmax(lg, axis=0)
            return jnp.einsum("khw,khwc->hwc", w, ximg)

        m_arg = mask if mask is not None else jnp.ones(idx.shape, bool)
        out = jax.vmap(one)(qf, idx, m_arg)
        return out.reshape(bsz, -1)

    def _basis(self, patch: int) -> Array:
        """PCA filters [patch, patch, C, r] fit on random training patches."""
        if patch in self._bases:
            return self._bases[patch]
        rng = np.random.default_rng(self.seed + patch)
        x = self.store.X.reshape(-1, self.h, self.w, self.c)
        n = x.shape[0]
        cnt = min(self.num_fit_patches, 16384)
        ii = rng.integers(0, n, cnt)
        hh = rng.integers(0, max(self.h - patch, 0) + 1, cnt)
        ww = rng.integers(0, max(self.w - patch, 0) + 1, cnt)
        patches = np.stack([x[i, a:a + patch, b:b + patch, :]
                            for i, a, b in zip(ii, hh, ww)])
        flat = patches.reshape(cnt, -1)
        flat = flat - flat.mean(0)
        r = min(self.rank, flat.shape[1])
        _, _, vt = np.linalg.svd(flat, full_matrices=False)
        basis = vt[:r].T.reshape(patch, patch, self.c, r)
        self._bases[patch] = jnp.asarray(basis, jnp.float32)
        return self._bases[patch]

    def features(self, imgs: Array, patch: int) -> Array:
        basis = self._basis(patch)
        return jax.lax.conv_general_dilated(
            imgs, basis, window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def feature_dist(self, qf: Array, xf: Array, patch: int) -> Array:
        # distance already lives in the projected patch space; no box sum
        return jnp.sum((qf[:, None] - xf[None]) ** 2, axis=-1)

    def _chunk_features(self, s: int, e: int, ximg: Array, patch: int) -> Array:
        return self._dataset_features(patch)[s:e]


DENOISERS = {
    "optimal": OptimalDenoiser,
    "wiener": WienerDenoiser,
    "kamb": PatchDenoiser,
    "pca": PCADenoiser,
}


def make_denoiser(name: str, store: DatasetStore, schedule: Schedule, **kw):
    return DENOISERS[name](store, schedule, **kw)
