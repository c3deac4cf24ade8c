"""Compile every stage the engine runs on a TPU, for a described v5e.

No chip is attached: the TPU compiler builds each program for a
``v5e:2x2`` topology described in a fixture (never at import — only one
process may load the TPU library, and xdist workers import every test
module).  Each case lowers what ``ops`` selects for ``backend="pallas"``
with ``interpret=False`` at a deployment's published width — CIFAR-10
(N=50,000, D=3072) and AFHQ (N=15,000, D=12288, where VMEM binds) — so
a kernel that Mosaic refuses (an unsupported primitive, a block over
scoped VMEM) or a program over one chip's HBM fails here, at no chip
time.  The cases also pin the stage choices: the kernel stages compile
to a Pallas custom call; the streamed screen and the fused candidate
pass compile to plain XLA (``lax.scan``), because Mosaic has no
``top_k`` for their merge.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.distributed.retrieval import (fused_local_step, golden_local_topk,
                                         local_coarse_exact,
                                         merged_golden_mean)
from repro.distributed.sharding import shard_map_compat
from repro.kernels import ops

B = 8
HBM_BYTES = 16 * 10**9            # one v5e chip
KERNEL = "tpu_custom_call"

# (name, N, image side): proxy_factor=4 keeps D/16 proxy dims, and the
# GoldDiffConfig defaults put m_max = N/4, k_max = N/10
WIDTHS = {"cifar10": (50_000, 32), "afhq": (15_000, 64)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(width):
    n, side = WIDTHS[width]
    d, dp = side * side * 3, (side // 4) ** 2 * 3
    return n, d, dp, n // 4, n // 10


def _stage(name, n, d, dp, m, k, xdt=jnp.float32):
    """(fn, [(shape, dtype)], expects_kernel) for one engine stage, the
    store rows in ``xdt``."""
    f32, i32 = jnp.float32, jnp.int32
    if name == "screen_materialized":
        return (lambda qp, p, pn: ops.screen_topm(
            qp, p, m, x_norms=pn, stream=False, backend="pallas"),
            [((B, dp), f32), ((n, dp), f32), ((n,), f32)], True)
    if name == "screen_streamed":
        return (lambda qp, p, pn: ops.screen_topm(
            qp, p, m, x_norms=pn, stream=True, backend="pallas"),
            [((B, dp), f32), ((n, dp), f32), ((n,), f32)], False)
    if name == "rerank":
        return (lambda q, x, c, xn: ops.golden_rerank(
            q, x, c, k, x_norms=xn, backend="pallas"),
            [((B, d), f32), ((n, 1, d), xdt), ((B, m), i32), ((n,), f32)],
            True)
    if name == "aggregate":
        return (lambda x, i, lg: ops.golden_support_aggregate(
            x, i, lg, backend="pallas"),
            [((n, 1, d), xdt), ((B, k), i32), ((B, k), f32)], True)
    if name == "fused_step":
        return (lambda q, qp, x, p, xn, pn: ops.fused_step(
            q, qp, x, p, m, k, 0.5, x_norms=xn, proxy_norms=pn,
            backend="pallas", stream=True),
            [((B, d), f32), ((B, dp), f32), ((n, 1, d), f32),
             ((n, dp), f32), ((n,), f32), ((n,), f32)], True)
    if name == "centroid_scan":
        c = int(n ** 0.5)
        return (lambda qp, cs, cn: ops.centroid_scan(
            qp, cs, cn, backend="pallas"),
            [((B, dp), f32), ((c, dp), f32), ((c,), f32)], True)
    if name == "full_scan":
        return (lambda q, x, xn: ops.golden_aggregate(
            q, x, 0.5, x_norms=xn, backend="pallas"),
            [((B, d), f32), ((n, 1, d), xdt), ((n,), f32)], True)
    raise KeyError(name)


STAGES = ["screen_materialized", "screen_streamed", "rerank", "aggregate",
          "fused_step", "centroid_scan", "full_scan"]


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("width", list(WIDTHS))
def test_stage_compiles_for_v5e(one_chip, width, stage):
    fn, specs, expects_kernel = _stage(stage, *_shapes(width))
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert (KERNEL in compiled.as_text()) == expects_kernel
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < HBM_BYTES


@pytest.mark.parametrize("stage", ["rerank", "aggregate", "fused_step",
                                   "full_scan"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_row_fetch_stage_reads_the_store_in_place(one_chip, width, stage):
    """The re-rank and support-aggregate stages take the store in the
    kernels' ``[N, 1, D]`` layout and fetch each candidate row inside
    the kernel: the compiled program holds no gathered ``[B, m, D]`` /
    ``[B, k, D]`` f32 copy of the candidates, and its temporaries stay
    below the store's own bytes (no per-call relayout of the store).
    The fused step (tiles of the store contracted in XLA, the fetch in
    its epilogue) and the full scan hold to the same."""
    n, d, dp, m, k = _shapes(width)
    fn, specs, _ = _stage(stage, n, d, dp, m, k)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert KERNEL in text
    # any f32 array of half the candidates' rows or more, the store aside
    cands = B * (m if stage == "rerank" else k) * d
    copies = {dims for dims in re.findall(r"f32\[([\d,]+)\]", text)
              if dims != f"{n},1,{d}"
              and math.prod(int(v) for v in dims.split(",")) >= cands // 2}
    assert not copies, copies
    assert compiled.memory_analysis().temp_size_in_bytes < n * d * 4


def _f32_arrays(text, at_least):
    """Shapes of the f32 arrays in compiled HLO ``text`` with at least
    ``at_least`` elements."""
    return {dims for dims in re.findall(r"f32\[([\d,]+)\]", text)
            if math.prod(int(v) for v in dims.split(",")) >= at_least}


@pytest.mark.parametrize("stage", ["rerank", "aggregate", "full_scan"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_16bit_store_stage_reads_the_store_in_place(one_chip, width, stage):
    """A bf16 store's ``[N, 1, D]`` rows are laid out in ``[N, D]``
    tiles, which no one-row DMA can address: the re-rank and aggregate
    stages gather their candidates in bf16 (the one copy the XLA gather
    makes) and the full scan reads the store in blocks.  No stage widens
    the rows to an f32 copy or relays the store out per call."""
    n, d, dp, m, k = _shapes(width)
    fn, specs, _ = _stage(stage, n, d, dp, m, k, xdt=jnp.bfloat16)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert KERNEL in text
    # the gathered bf16 candidates, padded to whole tiles
    gathered = {"rerank": B * (m + 255) // 256 * 256 * d * 2,
                "aggregate": B * (k + 255) // 256 * 256 * d * 2,
                "full_scan": 0}[stage]
    assert not _f32_arrays(text, min(n, B * k) * d // 2)
    assert (compiled.memory_analysis().temp_size_in_bytes
            < gathered + n * d * 2 // 2)


# (N, D) of the stores whose Wiener basis the Gaussian rung builds
BASIS_WIDTHS = {"cifar10": (50_000, 3072), "afhq64": (14_630, 12_288)}


@pytest.mark.parametrize("width", list(BASIS_WIDTHS))
def test_wiener_basis_compiles_for_v5e(one_chip, width):
    """The Gaussian rung's basis program (blocked Gram matrix and
    ``eigh``) for one chip: it makes no f32 array of the store's size
    besides the rows themselves (no centred or flattened copy), and its
    temporaries stay under eight ``[D, D]`` fp32 matrices."""
    from repro.core.denoisers import WIENER_BLOCK_BYTES, _wiener_basis
    n, d = BASIS_WIDTHS[width]
    args = [jax.ShapeDtypeStruct((n, 1, d), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)]
    compiled = _wiener_basis.lower(
        *args, rank=min(n, d), groups=1,
        block=WIENER_BLOCK_BYTES // (4 * d)).compile()
    store_sized = {dims for dims in _f32_arrays(compiled.as_text(), n * d)
                   if math.prod(int(v) for v in dims.split(",")) >= n * d}
    assert store_sized <= {f"{n},1,{d}", f"1,{n},1,{d}"}, store_sized
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * d * d * 4, mem.temp_size_in_bytes
    print(f"{width}: temp {mem.temp_size_in_bytes} B, args "
          f"{mem.argument_size_in_bytes} B, out {mem.output_size_in_bytes} B")


def test_wiener_basis_on_sharded_rows_compiles_for_v5e_2x2(topo):
    """The same basis program over rows sharded across the four chips
    (CIFAR-10 width): each chip sums the Gram matrix of its own rows and
    an all-reduce adds them; no chip gathers the store or holds a
    store-sized f32 array besides its rows."""
    from repro.core.denoisers import WIENER_BLOCK_BYTES, _wiener_basis
    n, d = BASIS_WIDTHS["cifar10"]
    rows = NamedSharding(Mesh(topo.devices[:4], ("data",)),
                         PartitionSpec("data"))
    compiled = _wiener_basis.lower(
        jax.ShapeDtypeStruct((n, 1, d), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=rows),
        rank=d, groups=4, block=WIENER_BLOCK_BYTES // (4 * d)).compile()
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" not in text
    n_loc = n // 4
    assert _f32_arrays(text, n_loc * d) <= {
        f"{n_loc},1,{d}", f"1,{n_loc},1,{d}"}, _f32_arrays(text, n_loc * d)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * d * d * 4


@pytest.mark.parametrize("path", ["fused", "staged", "indexed"])
def test_sharded_step_compiles_for_v5e_2x2(topo, path):
    """The sharded engine's shard-local steps across the four chips of
    the mesh, at CIFAR-10 width: the fused exact step, the staged exact
    step (``select`` and the static steps it shares its stages with) and
    the indexed step (globally probed windows, ``ivf_screen_local``),
    each ending in the cross-shard top-k threshold and LSE merge.  No
    step relays out its shard of the store: no f32 array of the local
    store's size other than the rows themselves, and temporaries below
    half the local store's bytes over the ``[B, k_cap, D]`` golden rows
    that the shard-local partial aggregate gathers."""
    n, d, dp, m, k = _shapes("cifar10")
    mesh = Mesh(topo.devices[:4], ("data",))
    n_loc = n // 4
    m_cap, k_cap = min(m, n_loc), min(k, n_loc)
    # a balance-capped index of ~sqrt(N) clusters, a quarter probed
    n_clusters, max_cluster = 224, 512
    w_loc = n_clusters // 4
    rows = NamedSharding(mesh, PartitionSpec("data"))
    rep = NamedSharding(mesh, PartitionSpec())

    def local(x, xn, p, pn, offs, wr, q, qp, cents, cn):
        if path == "fused":
            return fused_local_step(x, xn, q, qp, p, pn, m_cap, m, m, k_cap,
                                    k, k, 0.5, "data", backend="pallas",
                                    strategy="gather")
        if path == "staged":
            cand, valid = local_coarse_exact(qp, p, pn, m_cap, m, m, "data",
                                             backend="pallas")
        else:
            cand, pd2 = ops.ivf_screen_local(
                qp, offs, cents, cn, wr[0], wr[1], w_loc, max_cluster,
                w_loc, n_loc, backend="pallas")
            valid = jnp.isfinite(pd2)
        idx, neg, kth = golden_local_topk(x, xn, q, cand, valid, k_cap, k, k,
                                          "data", backend="pallas")
        return merged_golden_mean(x, idx, neg, kth, 0.5, "data")

    spec = PartitionSpec("data")
    step = shard_map_compat(local, mesh, (spec,) * 6 + (PartitionSpec(),) * 4,
                            PartitionSpec())
    f32, i32 = jnp.float32, jnp.int32
    args = [jax.ShapeDtypeStruct((n, 1, d), f32, sharding=rows),
            jax.ShapeDtypeStruct((n,), f32, sharding=rows),
            jax.ShapeDtypeStruct((n, dp), f32, sharding=rows),
            jax.ShapeDtypeStruct((n,), f32, sharding=rows),
            jax.ShapeDtypeStruct((4 * (w_loc + 1),), i32, sharding=rows),
            jax.ShapeDtypeStruct((4 * 2,), i32, sharding=rows),
            jax.ShapeDtypeStruct((B, d), f32, sharding=rep),
            jax.ShapeDtypeStruct((B, dp), f32, sharding=rep),
            jax.ShapeDtypeStruct((n_clusters, dp), f32, sharding=rep),
            jax.ShapeDtypeStruct((n_clusters,), f32, sharding=rep)]
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert KERNEL in text and "all-gather" in text
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes) < HBM_BYTES
    store_sized = {dims for dims in _f32_arrays(text, n_loc * d)
                   if math.prod(int(v) for v in dims.split(","))
                   in (n_loc * d, n * d)}
    assert store_sized <= {f"{n_loc},1,{d}", f"{n},1,{d}"}, store_sized
    assert mem.temp_size_in_bytes < (B * k_cap + n_loc // 2) * d * 4, \
        mem.temp_size_in_bytes
