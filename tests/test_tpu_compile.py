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
import os

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


def _stage(name, n, d, dp, m, k):
    """(fn, [(shape, dtype)], expects_kernel) for one engine stage."""
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
            [((B, d), f32), ((n, d), f32), ((B, m), i32), ((n,), f32)],
            True)
    if name == "aggregate":
        return (lambda x, i, lg: ops.golden_support_aggregate(
            x, i, lg, backend="pallas"),
            [((n, d), f32), ((B, k), i32), ((B, k), f32)], True)
    if name == "fused_step":
        return (lambda q, qp, x, p, xn, pn: ops.fused_step(
            q, qp, x, p, m, k, 0.5, x_norms=xn, proxy_norms=pn,
            backend="pallas", stream=True),
            [((B, d), f32), ((B, dp), f32), ((n, d), f32), ((n, dp), f32),
             ((n,), f32), ((n,), f32)], True)
    if name == "centroid_scan":
        c = int(n ** 0.5)
        return (lambda qp, cs, cn: ops.centroid_scan(
            qp, cs, cn, backend="pallas"),
            [((B, dp), f32), ((c, dp), f32), ((c,), f32)], True)
    if name == "full_scan":
        return (lambda q, x, xn: ops.golden_aggregate(
            q, x, 0.5, x_norms=xn, backend="pallas"),
            [((B, d), f32), ((n, d), f32), ((n,), f32)], True)
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


@pytest.mark.parametrize("path", ["fused", "staged", "indexed"])
def test_sharded_step_compiles_for_v5e_2x2(topo, path):
    """The sharded engine's shard-local steps across the four chips of
    the mesh, at CIFAR-10 width: the fused exact step, the staged exact
    step (``select`` and the static steps it shares its stages with) and
    the indexed step (globally probed windows, ``ivf_screen_local``),
    each ending in the cross-shard top-k threshold and LSE merge."""
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
    args = [jax.ShapeDtypeStruct((n, d), f32, sharding=rows),
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
