"""In-memory dataset store consumed by the analytical denoisers.

The store keeps the training set flattened, one row per point, together
with the low-dimensional proxy embedding ``proxy: [N, d]`` used by
GoldDiff's coarse screening (paper Sec. 3.4: 4x spatial downsample) and
precomputed squared norms (so pairwise distances become a single matmul).

The rows are held once, as ``rows: [N, 1, D]``: the form the re-rank
and aggregate kernels fetch single rows from (``kernels/common.py``
states the layout rule).  Device code takes ``rows``; ``X`` is a host
``[N, D]`` copy for host code, tests and the benchmark's reference
(reshaping the rows on a TPU would copy the whole store).
"""
from __future__ import annotations

from typing import NamedTuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.common import dot_rows, row_sq_norms

Array = jnp.ndarray
# Rows pooled into the proxy at a time: a TPU lays an image-shaped array
# out with padding and relayout temporaries several times its size, so
# pooling a whole store at once would hold several copies of it.
PROXY_CHUNK = 4096


class DatasetStore(NamedTuple):
    rows: Array                 # [N, 1, D] flattened training points
    proxy: Array                # [N, d] proxy-space embedding (d << D)
    x_norms: Array              # [N]    ||x_i||^2
    proxy_norms: Array          # [N]    ||proxy_i||^2
    image_shape: tuple          # e.g. (32, 32, 3) or (2,) for 2-D toys
    labels: Array | None = None  # [N] int class ids (conditional generation)

    @property
    def X(self) -> np.ndarray:
        """The training points as a host ``[N, D]`` array: a copy in host
        memory, reshaped on the host (for host code and tests; device
        code takes ``rows``)."""
        return np.asarray(self.rows).reshape(self.n, self.dim)

    @property
    def n(self) -> int:
        return self.rows.shape[0]

    @property
    def dim(self) -> int:
        return self.rows.shape[-1]


def downsample_proxy(x_img: Array, factor: int = 4) -> Array:
    """Paper's proxy: spatially average-pooled image, flattened.

    ``x_img``: [..., H, W, C].  Falls back to identity for non-image data
    (ndim < 3 trailing dims) or tiny spatial dims.
    """
    if x_img.ndim < 3 or x_img.shape[-2] < factor or x_img.shape[-3] < factor:
        return x_img.reshape(x_img.shape[: x_img.ndim - 1] + (-1,)) \
            if x_img.ndim >= 2 else x_img
    h, w, c = x_img.shape[-3:]
    hh, ww = h // factor, w // factor
    lead = x_img.shape[:-3]
    v = x_img[..., : hh * factor, : ww * factor, :]
    v = v.reshape(lead + (hh, factor, ww, factor, c)).mean(axis=(-4, -2))
    return v.reshape(lead + (hh * ww * c,))


def make_store(x: np.ndarray | Array, image_shape: tuple,
               labels: np.ndarray | None = None,
               proxy_factor: int = 4, dtype=jnp.float32) -> DatasetStore:
    """Build a DatasetStore from raw data of shape [N, *image_shape]
    (or [N, D]): one program, which on a TPU holds no temporaries beyond
    its outputs."""
    rows, proxy, x_norms, proxy_norms = _store_arrays(
        jnp.asarray(x, dtype), tuple(image_shape), int(proxy_factor))
    return DatasetStore(
        rows=rows, proxy=proxy, x_norms=x_norms, proxy_norms=proxy_norms,
        image_shape=tuple(image_shape),
        labels=None if labels is None else jnp.asarray(labels),
    )


@functools.partial(jax.jit, static_argnames=("image_shape", "factor"))
def _store_arrays(x, image_shape: tuple, factor: int):
    """``(rows [N, 1, D], proxy [N, d], x_norms, proxy_norms)``; the
    proxy is pooled ``PROXY_CHUNK`` rows at a time, the last chunk
    overlapping back instead of padding the store."""
    n = x.shape[0]
    flat = x.reshape(n, -1)
    c = max(min(PROXY_CHUNK, n), 1)
    starts = jnp.maximum(jnp.minimum(jnp.arange(-(-n // c)) * c, n - c), 0)
    pooled = jax.lax.map(lambda s: downsample_proxy(
        jax.lax.dynamic_slice_in_dim(flat, s, c).reshape((c,) + image_shape),
        factor), starts)                                   # [chunks, c, d]
    seen = pooled.shape[0] * c - n          # rows the last chunk re-reads
    proxy = jnp.concatenate([pooled[:-1].reshape(-1, pooled.shape[-1]),
                             pooled[-1, seen:]])
    return (flat[:, None, :], proxy, jnp.sum(flat * flat, -1),
            jnp.sum(proxy * proxy, -1))


def restrict(store: DatasetStore, idx: Array) -> DatasetStore:
    """Materialize the sub-store at integer indices ``idx`` (e.g. one class)."""
    return DatasetStore(
        rows=store.rows[idx], proxy=store.proxy[idx],
        x_norms=store.x_norms[idx],
        proxy_norms=store.proxy_norms[idx], image_shape=store.image_shape,
        labels=None if store.labels is None else store.labels[idx],
    )


def pairwise_sq_dists(q: Array, x: Array, x_norms: Array | None = None) -> Array:
    """||q - x_i||^2 for q: [B, D], x: [N, D] or the store rows
    [N, 1, D] -> [B, N] via the matmul form (``x`` is not reshaped)."""
    if x_norms is None:
        x_norms = row_sq_norms(x)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)
    d2 = qn + x_norms[None, :] - 2.0 * dot_rows(q, x)
    return jnp.maximum(d2, 0.0)
