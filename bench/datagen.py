"""Procedural image stores, made on the device from the seed.

A JAX port of the procedural Fourier-field generator: each class has a
smooth random-Fourier prototype, and a sample is its class prototype
warped by a smooth random shift field, plus band-limited texture and
pixel noise, standardized over the whole store.  The shapes, class
counts and generator parameters come from the configuration file
(``dataset``), and the whole store is made in one jitted call, in
blocks of rows, so a run spends no time on host-side generation.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def _modes(max_freq: int) -> np.ndarray:
    """[M, 3] (gy, gx, f) of the field's Fourier modes."""
    return np.array([(gy, gx, f) for f in range(1, max_freq + 1)
                     for gy, gx in ((f, 0), (0, f), (f, f))], np.float32)


def fourier_field(key, h: int, w: int, c: int, max_freq: int,
                  count: int) -> jax.Array:
    """[count, h, w, c] smooth random fields: a sum over the modes of
    ``amp * cos(2 pi (gy y + gx x) + phase)`` with amp ~ N(0, 1/f) and
    phase ~ U(0, 2 pi), on a unit grid; computed as one contraction over
    the modes (cos(a + b) = cos a cos b - sin a sin b)."""
    modes = _modes(max_freq)
    kp, ka = jax.random.split(key)
    phase = jax.random.uniform(kp, (count, len(modes), c)) * (2 * np.pi)
    amp = (jax.random.normal(ka, (count, len(modes), c))
           / jnp.asarray(modes[:, 2])[None, :, None])
    yy, xx = np.meshgrid(np.linspace(0, 1, h), np.linspace(0, 1, w),
                         indexing="ij")
    base = 2 * np.pi * (modes[:, 0, None, None] * yy
                        + modes[:, 1, None, None] * xx)        # [M, h, w]
    cb = jnp.asarray(np.cos(base).reshape(len(modes), h * w), jnp.float32)
    sb = jnp.asarray(np.sin(base).reshape(len(modes), h * w), jnp.float32)
    out = (jnp.einsum("nmc,mp->npc", amp * jnp.cos(phase), cb,
                      precision=HIGHEST)
           - jnp.einsum("nmc,mp->npc", amp * jnp.sin(phase), sb,
                        precision=HIGHEST))
    return out.reshape(count, h, w, c)


def block_rows(n: int, cap: int = 2048) -> int:
    """Rows per block: the largest divisor of ``n`` not above ``cap``,
    a multiple of 8 (the TPU's sublane tile) where ``n`` has one."""
    divs = [d for d in range(1, min(n, cap) + 1) if n % d == 0]
    return max([d for d in divs if d % 8 == 0] or divs)


@partial(jax.jit, static_argnames=("n", "shape", "class_counts", "params"))
def _generate(key, n: int, shape: tuple, class_counts: tuple,
              params: tuple):
    h, w, c = shape
    deform, texture, pixel_noise, proto_freq, shift_freq, tex_freq = params
    k_proto, k_lab, k_rows = jax.random.split(key, 3)
    protos = fourier_field(k_proto, h, w, c, proto_freq, len(class_counts))
    protos = protos / (jnp.abs(protos).max(axis=(1, 2, 3), keepdims=True)
                       + 1e-6)
    labels = jax.random.permutation(k_lab, jnp.asarray(
        np.repeat(np.arange(len(class_counts)), class_counts), jnp.int32))
    flat_protos = protos.reshape(-1, c)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    rows = block_rows(n)

    def block(args):
        i, lab = args
        k = jax.random.fold_in(k_rows, i)
        ky, kx, kt, kn = jax.random.split(k, 4)
        dy = fourier_field(ky, h, w, 1, shift_freq, rows)[..., 0] * deform
        dx = fourier_field(kx, h, w, 1, shift_freq, rows)[..., 0] * deform
        iy = jnp.clip(jnp.round(yy[None] + dy).astype(jnp.int32), 0, h - 1)
        ix = jnp.clip(jnp.round(xx[None] + dx).astype(jnp.int32), 0, w - 1)
        warped = flat_protos[(lab[:, None, None] * h + iy) * w + ix]
        tex = fourier_field(kt, h, w, c, tex_freq, rows) * (texture * 0.3)
        noise = jax.random.normal(kn, (rows, h, w, c)) * pixel_noise
        return (warped + tex + noise).reshape(rows, h * w * c)

    nb = n // rows
    x = jax.lax.map(block, (jnp.arange(nb), labels.reshape(nb, rows)))
    mean = jnp.mean(x)
    std = jnp.sqrt(jnp.mean(jnp.square(x - mean)))
    x = (x - mean) / (std + 1e-8)        # [blocks, rows, D]: merging the
    return x.reshape(n, h * w * c), labels   # leading dims moves nothing


def generate(dataset: dict, seed: int) -> tuple[jax.Array, jax.Array]:
    """(X [N, D] float32 standardized, labels [N] int32) on the default
    device, from a configuration's ``dataset`` section and the seed."""
    shape = tuple(int(s) for s in dataset["image_shape"])
    counts = tuple(int(c) for c in dataset["class_counts"])
    n = int(dataset["n"])
    if sum(counts) != n:
        raise ValueError(f"class counts sum to {sum(counts)}, not n={n}")
    g = dataset["generator"]
    params = (float(g["deform"]), float(g["texture"]),
              float(g["pixel_noise"]), int(g["proto_freq"]),
              int(g["shift_freq"]), int(g["texture_freq"]))
    return _generate(seed_key(seed), n=n, shape=shape, class_counts=counts,
                     params=params)

