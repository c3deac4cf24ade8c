"""Procedural datasets standing in for the paper's benchmarks.

The container is offline (no MNIST/CIFAR/AFHQ/ImageNet files), so we
generate *procedural* datasets with matched shape and cardinality.  Every
claim the reproduction validates (speedup vs N, golden-subset == full scan,
Theorem 1, progressive concentration, WSS bias) is algorithmic and
dataset-agnostic — see DESIGN.md §7.

Image generator: each class c has a smooth random-Fourier prototype; a
sample is prototype + smooth random deformation field + band-limited
texture + pixel noise, standardized to roughly [-1, 1].  This yields a
manifold with genuine low-frequency structure, so the paper's downsampled
proxy screening (hierarchical consistency of natural images) is exercised
meaningfully rather than trivially.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from repro.core.dataset import DatasetStore, make_store


def moons(n: int = 2000, noise: float = 0.08, seed: int = 0) -> DatasetStore:
    """Two interleaved half-circles (the Fig. 1 toy), standardized."""
    rng = np.random.default_rng(seed)
    n2 = n // 2
    th1 = rng.uniform(0, np.pi, n2)
    th2 = rng.uniform(0, np.pi, n - n2)
    a = np.stack([np.cos(th1), np.sin(th1)], -1)
    b = np.stack([1 - np.cos(th2), -np.sin(th2) + 0.5], -1)
    x = np.concatenate([a, b]) + rng.normal(0, noise, (n, 2))
    y = np.concatenate([np.zeros(n2, int), np.ones(n - n2, int)])
    x = (x - x.mean(0)) / x.std(0)
    return make_store(x.astype(np.float32), (2,), labels=y, proxy_factor=1)


def gmm(n: int = 4096, dim: int = 16, num_modes: int = 8,
        spread: float = 0.15, seed: int = 0) -> DatasetStore:
    rng = np.random.default_rng(seed)
    centers = rng.normal(0, 1, (num_modes, dim))
    y = rng.integers(0, num_modes, n)
    x = centers[y] + rng.normal(0, spread, (n, dim))
    x = (x - x.mean(0)) / (x.std() + 1e-8)
    return make_store(x.astype(np.float32), (dim,), labels=y, proxy_factor=1)


def _fourier_field(rng, h, w, c, max_freq: int, count: int) -> np.ndarray:
    """[count, h, w, c] smooth random fields from low-frequency Fourier modes.

    The random draws come first, in a fixed order; the cosine sums then
    run on row blocks in a thread pool (numpy releases the GIL), each
    block adding the modes in the same order, so the field is
    bit-identical to a serial sum at a fraction of its wall time.
    """
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    modes = []
    for f in range(1, max_freq + 1):
        for (gy, gx) in ((f, 0), (0, f), (f, f)):
            phase = rng.uniform(0, 2 * np.pi, (count, 1, 1, c))
            amp = rng.normal(0, 1.0 / f, (count, 1, 1, c))
            modes.append((2 * np.pi * (gy * yy + gx * xx), phase, amp))
    out = np.zeros((count, h, w, c), np.float32)

    def fill(s):
        e = min(s + step, count)
        for base, phase, amp in modes:
            out[s:e] += amp[s:e] * np.cos(base[None, :, :, None]
                                          + phase[s:e])

    workers = min(os.cpu_count() or 1, 16)
    step = max(1, -(-count // workers))
    with ThreadPoolExecutor(workers) as pool:
        list(pool.map(fill, range(0, count, step)))
    return out


def procedural_images(n: int, h: int, w: int, c: int = 3,
                      num_classes: int = 10, seed: int = 0,
                      deform: float = 1.5, texture: float = 0.35,
                      pixel_noise: float = 0.05,
                      batch: int = 4096) -> tuple[np.ndarray, np.ndarray]:
    """Raw arrays (x [n,h,w,c] float32 standardized, labels [n])."""
    rng = np.random.default_rng(seed)
    protos = _fourier_field(rng, h, w, c, max_freq=3, count=num_classes)
    protos /= (np.abs(protos).max(axis=(1, 2, 3), keepdims=True) + 1e-6)
    labels = rng.integers(0, num_classes, n)
    xs = np.empty((n, h, w, c), np.float32)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    for s in range(0, n, batch):
        e = min(s + batch, n)
        m = e - s
        lab = labels[s:e]
        # smooth per-sample deformation of the prototype (shift field)
        dy = _fourier_field(rng, h, w, 1, 2, m)[..., 0] * deform
        dx = _fourier_field(rng, h, w, 1, 2, m)[..., 0] * deform
        iy = np.clip((yy[None] + dy).round().astype(int), 0, h - 1)
        ix = np.clip((xx[None] + dx).round().astype(int), 0, w - 1)
        base = protos[lab]                                   # [m,h,w,c]
        warped = base[np.arange(m)[:, None, None], iy, ix, :]
        tex = _fourier_field(rng, h, w, c, 6, m) * texture * 0.3
        xs[s:e] = warped + tex + rng.normal(0, pixel_noise, (m, h, w, c))
    xs -= xs.mean()
    xs /= (xs.std() + 1e-8)
    return xs, labels


def image_store(n: int, h: int, w: int, c: int = 3, num_classes: int = 10,
                seed: int = 0, **kw) -> DatasetStore:
    x, y = procedural_images(n, h, w, c, num_classes, seed, **kw)
    return make_store(x.reshape(n, -1), (h, w, c), labels=y)


# Named dataset registry mirroring the paper's benchmark suite ---------------

def mnist_like(n=4096, seed=0):
    return image_store(n, 28, 28, 1, num_classes=10, seed=seed)


def cifar_like(n=8192, seed=0):
    return image_store(n, 32, 32, 3, num_classes=10, seed=seed)


def celeba_like(n=4096, seed=0):
    return image_store(n, 64, 64, 3, num_classes=2, seed=seed)


def afhq_like(n=4096, seed=0):
    return image_store(n, 64, 64, 3, num_classes=3, seed=seed)


def imagenet_like(n=20000, seed=0, num_classes=1000):
    return image_store(n, 64, 64, 3, num_classes=num_classes, seed=seed)


DATASETS = {
    "moons": moons,
    "gmm": gmm,
    "mnist_like": mnist_like,
    "cifar_like": cifar_like,
    "celeba_like": celeba_like,
    "afhq_like": afhq_like,
    "imagenet_like": imagenet_like,
}


def make_dataset(name: str, **kw) -> DatasetStore:
    return DATASETS[name](**kw)
