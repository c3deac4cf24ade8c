"""Tiling and matmul-precision rules shared by the Pallas kernels.

* **Precision** — every distance and aggregation contraction runs at
  ``HIGHEST`` precision.  On a TPU the default precision of an fp32
  matmul is a single bf16 pass (XLA and Mosaic alike), which perturbs
  ``||q||^2 + ||x||^2 - 2 q.x`` by ~1e-3 relative — enough to reorder
  the golden-set boundary.  ``HIGHEST`` keeps the "fp32 accumulation"
  contract on every platform; the CPU computes fp32 either way, so CPU
  results are unchanged.  At serving batch sizes these contractions are
  bound by HBM bandwidth, not by MXU passes.  The re-rank and
  support-aggregate kernels, with one query row per step, sum on the
  VPU in fp32 instead.
* **VMEM tiles** — a store-row block ``(rows, D)`` is sized so that one
  fp32 buffer stays under ``VMEM_BLOCK_BYTES`` (D=3072 -> 256 rows,
  D=12288 -> 128 rows).  Pallas double-buffers it, and a ``HIGHEST``
  contraction splits each fp32 operand into bf16 parts in VMEM, so a
  kernel that contracts one block twice (the full-scan aggregate) needs
  more than the default 16 MiB of scoped VMEM: the store-tile kernels
  raise it to ``VMEM_LIMIT_BYTES``, half of a v5e core's 128 MiB.
* **Store layout** — the store rows are ``[N, 1, D]`` everywhere in
  ``src``, and nothing reshapes them inside a step program.  Under the
  TPU's tiled layout a 32-bit ``[N, D]`` row is spread over 8-row tiles
  and Mosaic refuses a one-row DMA out of it, while each ``(1, D)`` slab
  of a 32-bit ``[N, 1, D]`` array is contiguous (layout ``T(1,128)``):
  the re-rank and support-aggregate kernels fetch their candidate rows
  straight from it in HBM (:func:`fetch_tile`), so no ``[B, m, D]``
  gathered copy exists.  XLA lays a 16-bit ``[N, 1, D]`` array out as
  ``[N, D]`` tiles (its second-minor axis is ``N``), which no one-row
  DMA can address, so the kernels read a 16-bit store through its
  ``[N, D]`` view (:func:`tiled_rows`, a bitcast for that layout) and
  an XLA row gather, as for a 2-D store.  Reshaping a 32-bit store
  between the two forms copies all of it on a TPU: XLA math contracts
  the rows' last axis (:func:`dot_rows`, :func:`weigh_rows`) and squeezes
  gathered rows (:func:`gather_rows`), never the store.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
VMEM_BLOCK_BYTES = 4 << 20
VMEM_LIMIT_BYTES = 64 << 20
LANE = 128
# row copies started per unrolled step of the fetch loop (1.76 ms against
# 1.82 ms at 8 for the aggregate at CIFAR-10 width on a v5e)
FETCH_UNROLL = 32


def check_rows(x):
    """``x`` if it is store rows ``[N, 1, D]``; raise on any other form
    (a ``[N, D]`` store would have to be reshaped, a copy on a TPU)."""
    if x.ndim != 3 or x.shape[1] != 1:
        raise ValueError(f"store rows must be [N, 1, D], not {x.shape}")
    return x


def row_dma(x) -> bool:
    """Whether a kernel can DMA single rows of the store rows ``x``:
    32-bit ``[N, 1, D]`` rows are contiguous on a TPU, 16-bit ones are
    laid out in ``[N, D]`` tiles."""
    return x.dtype.itemsize == 4


def tiled_rows(x):
    """The ``[N, D]`` view of 16-bit store rows ``x`` ``[N, 1, D]``: XLA
    lays them out as ``[N, D]`` tiles, so on a TPU this reshape is a
    bitcast (checked by ``tests/test_tpu_compile.py``)."""
    if row_dma(x):
        raise ValueError("a 32-bit store is read by row DMA, not reshaped")
    return x.reshape(x.shape[0], x.shape[-1])


def gather_rows(x, idx):
    """Rows ``idx`` of ``x`` (``[N, D]``, or store rows ``[N, 1, D]``)
    -> ``[..., D]``: an XLA gather, after which only the gathered rows
    drop the unit axis."""
    return x[idx] if x.ndim == 2 else x[idx, 0]


def dot_rows(q, x):
    """``q`` [B, D] against each row of ``x`` -> [B, N] fp32 at
    ``HIGHEST``.  ``x`` is ``[N, D]`` or store rows ``[N, 1, D]``: the
    contraction runs over its last axis, so the rows are not reshaped."""
    dot = dot_f32(q, x, ((1,), (x.ndim - 1,)))
    return dot.reshape(q.shape[0], x.shape[0])


def weigh_rows(w, x):
    """``w`` [B, N] weights over the rows of ``x`` (``[N, D]`` or
    ``[N, 1, D]``, not reshaped) -> [B, D] fp32 at ``HIGHEST``."""
    return dot_f32(w, x, ((1,), (0,))).reshape(w.shape[0], x.shape[-1])


def row_sq_norms(x):
    """``||x_i||^2`` of each row of ``x`` (``[N, D]`` or ``[N, 1, D]``)."""
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, tuple(range(1, x.ndim)))


def candidate_rows(x, idxp, rows: int):
    """``(operand, in_spec, scratch)`` by which a kernel on a flat grid
    over ``(query, tile)`` steps reads candidate tiles of ``rows`` rows
    of ``x`` at the padded ids ``idxp`` [B, Mp] (flat and
    scalar-prefetched): 32-bit store rows ``[N, 1, D]`` stay in HBM for
    :func:`fetch_tile`; 16-bit store rows, or a 2-D table ``[N, D]``,
    are gathered by XLA into a ``[B, Mp, D]`` copy read in
    ``(rows, D)`` blocks.  The kernel takes its tile with
    :func:`candidate_tile`."""
    if x.ndim == 3 and row_dma(check_rows(x)):
        return (x, pl.BlockSpec(memory_space=pl.ANY),
                fetch_scratch(rows, x.shape[-1], x.dtype))
    tiles = idxp.shape[1] // rows
    spec = pl.BlockSpec((None, rows, x.shape[-1]),
                        lambda s, ids: (s // tiles, s % tiles, 0))
    table = x if x.ndim == 2 else tiled_rows(x)
    return gather_rows(table, idxp), spec, []


def candidate_tile(ids_ref, x_ref, scratch):
    """This grid step's candidate tile ``(rows, D)`` in fp32, read as
    :func:`candidate_rows` set it up (``scratch`` is its scratch)."""
    if scratch:
        return fetch_tile(ids_ref, x_ref, *scratch).astype(jnp.float32)
    return x_ref[...].astype(jnp.float32)


def fetch_scratch(rows: int, d: int, dtype) -> list:
    """Scratch of :func:`fetch_tile`: two ``(rows, 1, d)`` VMEM slots
    and one DMA semaphore each."""
    return [pltpu.VMEM((2, rows, 1, d), dtype), pltpu.SemaphoreType.DMA((2,))]


def _start_fetch(ids_ref, x_hbm, buf, sem, step, slot):
    """Start one row copy per slot of grid step ``step``'s tile."""
    rows = buf.shape[1]
    base = step * rows

    def body(g, carry):
        for u in range(FETCH_UNROLL):      # Mosaic unrolls by 1 or by all
            r = g * FETCH_UNROLL + u
            pltpu.make_async_copy(x_hbm.at[ids_ref[base + r]],
                                  buf.at[slot, r], sem.at[slot]).start()
        return carry

    jax.lax.fori_loop(0, rows // FETCH_UNROLL, body, 0)


def fetch_tile(ids_ref, x_hbm, buf, sem):
    """This grid step's tile of store rows, ``(rows, D)``, by row DMA.

    The kernel's grid is one flat, in-order ("arbitrary") axis over
    ``(query, tile)`` pairs, and step ``s`` takes the rows that the ids
    ``ids_ref[s * rows:][:rows]`` (flat, scalar-prefetched into SMEM)
    address in ``x_hbm``, the ``[N, 1, D]`` store left in HBM.  The
    copies of step ``s + 1`` start before step ``s`` waits on its own,
    into the other slot of ``buf`` (:func:`fetch_scratch`), so the fetch
    overlaps the contraction.  One wait per slot covers all its row
    copies: a DMA semaphore counts bytes.  ``rows`` must be a multiple
    of ``FETCH_UNROLL``.
    """
    s = pl.program_id(0)
    slot = s % 2

    @pl.when(s == 0)
    def _first():
        _start_fetch(ids_ref, x_hbm, buf, sem, s, slot)

    @pl.when(s + 1 < pl.num_programs(0))
    def _next():
        _start_fetch(ids_ref, x_hbm, buf, sem, s + 1, 1 - slot)

    pltpu.make_async_copy(buf.at[slot], buf.at[slot], sem.at[slot]).wait()
    return buf[slot].reshape(buf.shape[1], buf.shape[-1])


def row_tile(d: int, n: int) -> int:
    """Rows per ``(rows, d)`` fp32 block: a multiple of the 128-lane
    width (the row count is also a lane dimension of the norm/logit
    blocks), at least one lane group, and the whole axis when ``n``
    already fits."""
    rows = max(LANE, VMEM_BLOCK_BYTES // (4 * d) // LANE * LANE)
    return n if n <= rows else rows


def fetch_tile_rows(d: int, n: int, tile: int | None = None) -> int:
    """Rows per :func:`fetch_tile` tile over ``n`` ids: ``row_tile`` (or
    ``tile``, a multiple of 128) of the ids padded to a multiple of
    ``FETCH_UNROLL``, so a tile is the whole padded axis or a multiple
    of 128."""
    n_pad = -(-n // FETCH_UNROLL) * FETCH_UNROLL
    if tile is None:
        return row_tile(d, n_pad)
    if tile % LANE:
        raise ValueError(f"tile {tile} is not a multiple of {LANE}")
    return n_pad if n_pad <= tile else tile


def dot_f32(a, b, contract: tuple[tuple[int, ...], tuple[int, ...]]):
    """fp32 ``dot_general`` at ``HIGHEST`` precision (operands upcast)."""
    return jax.lax.dot_general(
        a.astype(jnp.float32), b.astype(jnp.float32), (contract, ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)
