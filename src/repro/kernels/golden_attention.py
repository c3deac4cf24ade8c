"""Pallas TPU kernel: golden (top-k block-sparse) decode attention.

The paper's coarse-to-fine golden-subset mechanism transplanted onto the
KV cache (DESIGN §4): the host-side selector scores each query against
*block summaries* (mean-pooled keys per block — the downsample proxy) and
hands this kernel the top-k golden block indices.  The kernel then runs
exact attention only over those blocks, paged-attention style: the block
index array is scalar-prefetched and drives the K/V BlockSpec index maps,
so only golden blocks ever move HBM -> VMEM.

Decode shape: one query token per sequence, GQA with G = Hq/Hkv query
heads sharing each KV head.

    q:   [B, Hkv, G, dh]
    k,v: [B, Hkv, S, dh]   (S = num_blocks * block_size)
    idx: [B, Hkv, kb]      golden block indices (int32)
    valid: [B, Hkv, kb]    1 = real block, 0 = padding
    out: [B, Hkv, G, dh]
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _gattn_kernel(idx_ref, valid_ref, q_ref, k_ref, v_ref, out_ref,
                  m_ref, l_ref, acc_ref, *, kb: int, scale: float):
    b, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[b, h, j] == 1)
    def _update():
        q = q_ref[0, 0]                                   # [G, dh]
        k = k_ref[0, 0]                                   # [Bs, dh]
        v = v_ref[0, 0]                                   # [Bs, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [G, Bs]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        sc = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * sc + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * sc + jax.lax.dot(
            p, v.astype(jnp.float32), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == kb - 1)
    def _emit():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "interpret"))
def golden_attention_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                            block_idx: jnp.ndarray, valid: jnp.ndarray,
                            block_size: int = 128,
                            interpret: bool = False) -> jnp.ndarray:
    """Exact attention over golden blocks only.

    q: [B, Hkv, G, dh]; k/v: [B, Hkv, S, dh]; block_idx/valid: [B, Hkv, kb].
    Returns [B, Hkv, G, dh].
    """
    b, hkv, g, dh = q.shape
    s = k.shape[2]
    kb = block_idx.shape[-1]
    assert s % block_size == 0, "cache length must be block-aligned"
    scale = 1.0 / (dh ** 0.5)
    # clamp padded indices into range (masked out by `valid` anyway)
    block_idx = jnp.clip(block_idx, 0, s // block_size - 1).astype(jnp.int32)

    grid = (b, hkv, kb)
    out = pl.pallas_call(
        functools.partial(_gattn_kernel, kb=kb, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g, dh), lambda bb, hh, jj, idx, val: (bb, hh, 0, 0)),
                pl.BlockSpec((1, 1, block_size, dh),
                             lambda bb, hh, jj, idx, val: (bb, hh, idx[bb, hh, jj], 0)),
                pl.BlockSpec((1, 1, block_size, dh),
                             lambda bb, hh, jj, idx, val: (bb, hh, idx[bb, hh, jj], 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, dh),
                                   lambda bb, hh, jj, idx, val: (bb, hh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
    )(block_idx, valid.astype(jnp.int32), q, k, v)
    return out


def select_golden_blocks(q: jnp.ndarray, k: jnp.ndarray, num_blocks: int,
                         block_size: int = 128) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Coarse screening over block summaries (paper Eq. 4 analogue).

    Scores each (batch, kv-head) query group against mean-pooled keys per
    block; returns (block_idx, valid): [B, Hkv, num_blocks].
    """
    b, hkv, g, dh = q.shape
    s = k.shape[2]
    nb = s // block_size
    summaries = k.reshape(b, hkv, nb, block_size, dh).mean(3)     # [B,Hkv,nb,dh]
    qbar = q.mean(2)                                              # [B,Hkv,dh]
    scores = jnp.einsum("bhd,bhnd->bhn", qbar.astype(jnp.float32),
                        summaries.astype(jnp.float32))
    kb = min(num_blocks, nb)
    _, idx = jax.lax.top_k(scores, kb)
    return idx.astype(jnp.int32), jnp.ones_like(idx, jnp.int32)
