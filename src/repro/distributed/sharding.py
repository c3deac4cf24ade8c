"""Logical-axis sharding (MaxText-style) plus cross-shard merge primitives.

Every parameter / activation carries *logical* axis names; a ``Rules``
table maps logical names to mesh axes per execution mode.  A thread-local
context makes ``shard(x, *axes)`` a no-op outside a mesh (CPU smoke tests
see one device and zero sharding machinery).

Mesh axes: ``("data", "model")`` single pod, ``("pod", "data", "model")``
multi-pod (launch/mesh.py).  GSPMD pads non-divisible dimensions (e.g. 40
query heads over model=16); the padding waste shows up in the roofline's
MODEL_FLOPS / HLO_FLOPs ratio, where we track it.

The bottom half of this module holds the **cross-shard merge
primitives** used inside ``shard_map`` bodies by both the sharded
``GoldDiffEngine`` (core/engine.py) and the standalone distributed
retrieval path (distributed/retrieval.py) — the two-stage top-k
threshold, the exact log-sum-exp softmax-state merge, and the gathered
global top-k.  They are the *only* implementation of the cross-shard
screening math in the repo; keeping them here (engine-callable, free of
engine state) is what lets ``tests/test_sharded_engine.py`` pin
"two-stage merge == global top-k + softmax" once for every consumer.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_CTX = threading.local()


@dataclasses.dataclass(frozen=True)
class Rules:
    mesh: Mesh | None
    table: dict[str, Any]  # logical axis -> mesh axis | tuple | None

    def spec(self, logical_axes: tuple, shape: tuple | None = None) -> P:
        """Resolve logical axes to a PartitionSpec.

        When ``shape`` is given, mesh axes whose (cumulative) size does not
        evenly divide the dimension are dropped — NamedSharding on real
        avals requires exact divisibility (non-divisible cases, e.g. 40 q
        heads over model=16, use the flattened head*dim layouts instead;
        see models/layers.py).
        """
        if self.mesh is None:
            return P()
        out = []
        used: set[str] = set()
        for i, ax in enumerate(logical_axes):
            m = self.table.get(ax) if ax is not None else None
            if m is None:
                out.append(None)
                continue
            ms = (m,) if isinstance(m, str) else tuple(m)
            ms = tuple(a for a in ms if a in self.mesh.axis_names
                       and a not in used)
            if shape is not None:
                keep = []
                prod = 1
                for a in ms:
                    prod *= self.mesh.shape[a]
                    if shape[i] % prod == 0:
                        keep.append(a)
                    else:
                        break
                ms = tuple(keep)
            used.update(ms)
            out.append(ms if len(ms) > 1 else (ms[0] if ms else None))
        return P(*out)

    def sharding(self, logical_axes: tuple,
                 shape: tuple | None = None) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec(logical_axes, shape))


def _pod(mesh: Mesh | None) -> tuple[str, ...]:
    if mesh is not None and "pod" in mesh.axis_names:
        return ("pod", "data")
    return ("data",)


def make_rules(mode: str, mesh: Mesh | None = None,
               overrides: dict | None = None) -> Rules:
    """mode: 'train' | 'prefill' | 'decode' | 'decode_long' | 'none'."""
    if mode == "none" or mesh is None:
        return Rules(None, {})
    batch = _pod(mesh)
    base = {
        # weights
        "embed": batch,          # FSDP / ZeRO-3 over the data axis
        "vocab": "model",
        "mlp": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "experts": "model",
        "expert_mlp": batch,     # second shard dim of expert weights
        "mamba_inner": "model",
        "mamba_conv": "model",
        "mamba_heads": "model",
        "layers": None,
        # activations
        "batch": batch,
        "seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_mlp": "model",
        "act_experts": "model",
        "kv_seq": None,
    }
    if mode == "train":
        # Shard the residual stream's d_model over `model` (Megatron-SP
        # analogue): the remat-saved per-layer activations [B, S, d]
        # dominate train HBM (43 GiB/chip for qwen2.5-32b unsharded).
        # d_model divides 16 for every assigned arch; sharding SEQ instead
        # provokes involuntary SPMD rematerialization inside the flash
        # attention q-chunk dynamic_slice (observed: +40% HBM).
        base["act_embed"] = "model"
    elif mode == "prefill":
        base["act_embed"] = "model"
        base["kv_seq"] = "model"       # prefill writes a model-sharded cache
    elif mode == "decode":
        base["kv_seq"] = "model"       # flash-decoding: split-S over model
        base["act_heads"] = None       # q replicated for the seq-split merge
    elif mode == "decode_long":
        base["kv_seq"] = ("data", "model") if "pod" not in mesh.axis_names \
            else ("pod", "data", "model")
        base["batch"] = None           # global_batch = 1
        base["act_heads"] = None
        base["expert_mlp"] = ("data",)
        base["embed"] = ("data",)
    else:
        raise ValueError(mode)
    if overrides:
        base.update(overrides)
    return Rules(mesh, base)


@contextlib.contextmanager
def use_rules(rules: Rules):
    prev = getattr(_CTX, "rules", None)
    _CTX.rules = rules
    try:
        yield rules
    finally:
        _CTX.rules = prev


def current_rules() -> Rules:
    r = getattr(_CTX, "rules", None)
    return r if r is not None else Rules(None, {})


def shard(x, *logical_axes):
    """Constrain activation sharding (no-op without an active mesh)."""
    r = current_rules()
    if r.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, r.sharding(tuple(logical_axes), tuple(x.shape)))


def mesh_axis_size(*names: str) -> int:
    r = current_rules()
    if r.mesh is None:
        return 1
    n = 1
    for name in names:
        if name in r.mesh.axis_names:
            n *= r.mesh.shape[name]
    return n


# -- cross-shard merge primitives (shard_map bodies only) --------------------

def shard_map_compat(f, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off (outputs are
    psum/pmax-replicated)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def crossshard_kth(neg_local: jnp.ndarray, k_sort: int, k,
                   axis: str) -> jnp.ndarray:
    """Value of the k-th *largest* entry across all shards; [B].

    Stage two of the two-stage top-k: every shard contributes its local
    top candidates ``neg_local`` [B, k_loc] (use negated distances so
    "largest" means "closest"; invalid slots -inf/NEG_INF sort last),
    the [B, S * k_loc] gather is k_loc floats per shard — never data
    rows — and the returned threshold selects exactly the global top-k
    (``neg >= kth``), matching a single-host ``top_k`` up to ties.

    ``k_sort`` is the static sort width (an upper bound on k); ``k``
    itself may be a traced scalar, which is how the masked (scan/pjit)
    engine path varies k_t inside one program.
    """
    g = jax.lax.all_gather(neg_local, axis, axis=1)
    return kth_from_gathered(g, k_sort, k)


def kth_from_gathered(g: jnp.ndarray, k_sort: int, k) -> jnp.ndarray:
    """Threshold-extraction half of :func:`crossshard_kth`, for callers
    that issue the ``all_gather`` themselves.

    The fused sharded step (``distributed/retrieval.fused_local_step``)
    starts the gather *before* the shard-local exact re-rank — the two
    have no data dependency, so the collective hides behind the GEMM —
    and only then extracts the threshold from the landed buffer.  Keeping
    the extraction here (same top_k, same clip) guarantees the overlap
    form selects bit-for-bit the same candidates as the staged
    ``crossshard_kth``.
    """
    flat = g.reshape(g.shape[0], -1)
    k_sort = min(k_sort, flat.shape[-1])
    vals = jax.lax.top_k(flat, k_sort)[0]
    kidx = jnp.clip(jnp.asarray(k, jnp.int32) - 1, 0, k_sort - 1)
    kidx = jnp.broadcast_to(jnp.reshape(kidx, (1, 1)), (vals.shape[0], 1))
    return jnp.take_along_axis(vals, kidx, axis=-1)[:, 0]


def gather_global_topk(ids_local: jnp.ndarray, neg_local: jnp.ndarray,
                       k: int, axis: str) -> jnp.ndarray:
    """Global top-k ids across shards: gather (id, score) pairs — k ints
    + k floats per shard — and re-select; [B, k] (static k)."""
    g_neg = jax.lax.all_gather(neg_local, axis, axis=1)
    g_ids = jax.lax.all_gather(ids_local, axis, axis=1)
    b = neg_local.shape[0]
    pos = jax.lax.top_k(g_neg.reshape(b, -1), k)[1]
    return jnp.take_along_axis(g_ids.reshape(b, -1), pos, axis=-1)


def lse_merge_mean(acc: jnp.ndarray, m: jnp.ndarray, l: jnp.ndarray,
                   axis: str) -> jnp.ndarray:
    """Exact log-sum-exp merge of per-shard softmax partial states.

    ``(acc [B, D], m [B], l [B])`` are the unnormalized weighted sum,
    running max-logit, and running partition sum of each shard's golden
    members (``streaming.merge`` semantics).  Shards with no members
    carry the *finite* ``NEG_INF`` sentinel max, so their scale factor
    underflows to exactly 0 and the merged estimate is bit-comparable
    to the single-host softmax up to fp32 reduction order.
    """
    m_g = jax.lax.pmax(m, axis)
    # NaN guard: if EVERY shard carries a hard -inf max (a degenerate
    # all-masked candidate set that bypassed the finite sentinel),
    # ``m - m_g`` is ``-inf - -inf`` = NaN; such shards have zero
    # weight by definition, so their scale is forced to 0 and the merge
    # degrades to a finite zero-mean instead of propagating NaN.
    diff = m - m_g
    sc = jnp.where(jnp.isnan(diff), 0.0, jnp.exp(diff))
    l_g = jax.lax.psum(l * sc, axis)
    acc_g = jax.lax.psum(acc * sc[:, None], axis)
    return acc_g / jnp.maximum(l_g, 1e-30)[:, None]
