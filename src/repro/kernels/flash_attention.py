"""Pallas TPU kernel: causal flash attention (train/prefill hot loop).

GQA layout: q [B, Hkv, G, S, dh], k/v [B, Hkv, S, dh].  Grid =
(B*Hkv, q-tiles, kv-tiles) with the kv dimension innermost sequential;
the online-softmax (max, denom, accum) carry lives in VMEM scratch and
the output tile is emitted on the last kv step.  Tiles above the causal
diagonal are skipped entirely (`pl.when`), so compute is ~S^2/2 not S^2
(the pure-JAX `models.layers.flash_attention` masks but still computes —
this kernel is the TPU-target replacement; DESIGN §5).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_QC = 256
DEFAULT_KC = 512


def _flash_kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref,
                  *, qc: int, kc: int, nk: int, scale: float, causal: bool):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # skip tiles entirely above the causal diagonal
    run = (ki * kc <= qi * qc + qc - 1) if causal else True

    @pl.when(run)
    def _update():
        q = q_ref[0, 0]                                   # [G, qc, dh]
        k = k_ref[0, 0]                                   # [kc, dh]
        v = v_ref[0, 0]
        g = q.shape[0]
        s = jax.lax.dot_general(
            q.reshape(g * qc, -1), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(g, qc, kc) * scale
        if causal:
            qpos = qi * qc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 0)
            kpos = ki * kc + jax.lax.broadcasted_iota(jnp.int32, (qc, kc), 1)
            s = jnp.where((qpos >= kpos)[None], s, NEG_INF)
        m_prev = m_ref[...]                               # [G, qc]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1))
        sc = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_ref[...] = l_ref[...] * sc + jnp.sum(p, -1)
        pv = jax.lax.dot_general(
            p.reshape(g * qc, kc), v.astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(g, qc, -1)
        acc_ref[...] = acc_ref[...] * sc[..., None] + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        out_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
                         ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "qc", "kc", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True, qc: int = DEFAULT_QC,
                    kc: int = DEFAULT_KC,
                    interpret: bool = False) -> jnp.ndarray:
    """q: [B, Hkv, G, S, dh]; k/v: [B, Hkv, S, dh] -> [B, Hkv, G, S, dh]."""
    b, hkv, g, s, dh = q.shape
    qc = min(qc, s)
    kc = min(kc, s)
    assert s % qc == 0 and s % kc == 0, "seq must tile evenly"
    nq, nk = s // qc, s // kc
    scale = dh ** -0.5
    bh = b * hkv
    q4 = q.reshape(bh, 1, g, s, dh)
    k4 = k.reshape(bh, 1, s, dh)
    v4 = v.reshape(bh, 1, s, dh)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, qc=qc, kc=kc, nk=nk, scale=scale,
                          causal=causal),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, g, qc, dh), lambda i, qi, ki: (i, 0, 0, qi, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda i, qi, ki: (i, 0, ki, 0)),
            pl.BlockSpec((1, 1, kc, dh), lambda i, qi, ki: (i, 0, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, qc, dh),
                               lambda i, qi, ki: (i, 0, 0, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, 1, g, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, qc), jnp.float32),
            pltpu.VMEM((g, qc), jnp.float32),
            pltpu.VMEM((g, qc, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q4, k4, v4)
    return out.reshape(b, hkv, g, s, dh)
