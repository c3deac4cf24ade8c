"""Tiling and matmul-precision rules shared by the Pallas kernels.

* **Precision** — every distance and aggregation contraction runs at
  ``HIGHEST`` precision.  On a TPU the default precision of an fp32
  matmul is a single bf16 pass (XLA and Mosaic alike), which perturbs
  ``||q||^2 + ||x||^2 - 2 q.x`` by ~1e-3 relative — enough to reorder
  the golden-set boundary.  ``HIGHEST`` keeps the "fp32 accumulation"
  contract on every platform; the CPU computes fp32 either way, so CPU
  results are unchanged.  At serving batch sizes these contractions are
  bound by HBM bandwidth, not by MXU passes.
* **VMEM tiles** — a store-row block ``(rows, D)`` is sized so that one
  fp32 buffer stays under ``VMEM_BLOCK_BYTES`` (D=3072 -> 256 rows,
  D=12288 -> 128 rows).  Pallas double-buffers it, and a ``HIGHEST``
  contraction splits each fp32 operand into bf16 parts in VMEM, so a
  kernel that contracts one block twice (the full-scan aggregate) needs
  more than the default 16 MiB of scoped VMEM: the store-tile kernels
  raise it to ``VMEM_LIMIT_BYTES``, half of a v5e core's 128 MiB.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
VMEM_BLOCK_BYTES = 4 << 20
VMEM_LIMIT_BYTES = 64 << 20
LANE = 128


def row_tile(d: int, n: int) -> int:
    """Rows per ``(rows, d)`` fp32 block: a multiple of the 128-lane
    width (the row count is also a lane dimension of the norm/logit
    blocks), at least one lane group, and the whole axis when ``n``
    already fits."""
    rows = max(LANE, VMEM_BLOCK_BYTES // (4 * d) // LANE * LANE)
    return n if n <= rows else rows


def dot_f32(a, b, contract: tuple[tuple[int, ...], tuple[int, ...]]):
    """fp32 ``dot_general`` at ``HIGHEST`` precision (operands upcast)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), (contract, ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
