"""Fused single-pass GoldDiff step (``kernels/fused_step.py``).

The fused megakernel / scan twin collapses coarse screen -> exact
re-rank -> softmax aggregation into ONE pass over the store, emitting
the posterior mean directly.  These tests pin:

* fused == staged engine outputs to fp32 reduction order on every
  backend (candidate *sets* are bit-identical; distances differ only
  by per-tile vs [B, N] GEMM blocking), static and masked/caps paths;
* ops-level edges — m > N surplus slots stay weightless, an all-masked
  step (m_t = k_t = 0) degrades finitely instead of NaN;
* the engine's fused policy (``fused="auto"|True|False``) and its
  program-cache kind;
* sharded parity on an emulated 8-device mesh: the overlap-ordered
  ``fused_local_step`` is BITWISE identical to the staged sharded path
  (same ops in the same order, only collective issue order differs),
  and a 2D (batch x store) mesh matches the single host;
* zero post-warmup compiles with ``fused=True`` in static and plan
  serving modes, including the continuous-batching ``plan_seg_mix``
  programs.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
from repro.data import gmm
from repro.kernels import ops

REPO = Path(__file__).resolve().parent.parent
SCH = make_schedule("ddpm_linear", 1000)

BACKENDS = ["xla", "pallas_interpret"]
if any(d.platform == "tpu" for d in jax.devices()):
    BACKENDS.append("pallas")


def _pair(backend, **kw):
    """(store, staged engine, fused engine) sharing one store."""
    store = gmm(512, dim=16, seed=0)
    staged = GoldDiffEngine(store, SCH, GoldDiffConfig(), backend=backend,
                            fused=False, **kw)
    fused = GoldDiffEngine(store, SCH, GoldDiffConfig(), backend=backend,
                           fused=True, **kw)
    return store, staged, fused


def _noisy(store, t, b=4, seed=0):
    x0 = store.X[:b]
    eps = jax.random.normal(jax.random.PRNGKey(seed), x0.shape)
    return SCH.add_noise(x0, eps, t)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_matches_staged_static(backend):
    store, staged, fused = _pair(backend)
    for t in (900, 500, 100):
        xt = _noisy(store, t, seed=t)
        np.testing.assert_allclose(np.asarray(fused.denoise(xt, t)),
                                   np.asarray(staged.denoise(xt, t)),
                                   rtol=1e-5, atol=5e-6)
    kinds = {k[0] for k in fused._programs}
    assert "fused_step" in kinds
    assert "fused_step" not in {k[0] for k in staged._programs}


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_matches_staged_masked(backend):
    """Traced-t masked path (the serve-plan body) with caps."""
    store, staged, fused = _pair(backend)
    for t in (800, 300):
        xt = _noisy(store, t, seed=t)
        tt = jnp.asarray(t)
        np.testing.assert_allclose(
            np.asarray(fused.denoise_masked(xt, tt)),
            np.asarray(staged.denoise_masked(xt, tt)),
            rtol=1e-5, atol=5e-6)


def test_fused_policy():
    """``use_fused``: False never fuses, True always, auto fuses the
    dense-strategy steps on a single host (a gather step touches only
    m_t rows — streaming the full store cannot beat it)."""
    store = gmm(512, dim=16, seed=0)
    dense = GoldDiffEngine(store, SCH, strategy="dense")
    gather = GoldDiffEngine(store, SCH, strategy="gather")
    t = 500
    assert dense.use_fused(t)
    assert not gather.use_fused(t)
    assert GoldDiffEngine(store, SCH, strategy="gather",
                          fused=True).use_fused(t)
    assert not GoldDiffEngine(store, SCH, strategy="dense",
                              fused=False).use_fused(t)
    with pytest.raises(ValueError, match="fused"):
        GoldDiffEngine(store, SCH, fused="yes")


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_surplus_slots_weightless(backend):
    """m > N: surplus candidate slots carry +inf and contribute zero
    weight — the posterior equals the m = N result exactly."""
    n, d = 50, 8
    kx, kq = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    q = jax.random.normal(kq, (4, d), jnp.float32)
    rows = x[:, None, :]                  # the store rows the ops take
    out_big = ops.fused_step(q, q, rows, x, 80, 10, 0.5, backend=backend)
    out_fit = ops.fused_step(q, q, rows, x, n, 10, 0.5, backend=backend)
    assert np.isfinite(np.asarray(out_big)).all()
    np.testing.assert_allclose(np.asarray(out_big), np.asarray(out_fit),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_all_masked_is_finite(backend):
    """m_t = k_t = 0 (every slot masked): the clamped-logit sentinel
    keeps the softmax defined — uniform over the gathered rows, no
    NaN."""
    n, d = 64, 8
    kx, kq = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(kx, (n, d), jnp.float32)
    q = jax.random.normal(kq, (3, d), jnp.float32)
    out = ops.fused_step(q, q, x[:, None, :], x, 16, 4, jnp.asarray(0.5),
                         backend=backend, m_t=jnp.asarray(0),
                         k_t=jnp.asarray(0))
    assert np.isfinite(np.asarray(out)).all()


def _run_child(code: str, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, cwd=str(REPO), env=env)
    assert "PASS" in r.stdout, r.stdout + r.stderr
    return r.stdout


_PRELUDE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.core import GoldDiffConfig, GoldDiffEngine, make_schedule
from repro.data import gmm

def maxerr(a, b):
    return float(np.abs(np.asarray(a) - np.asarray(b)).max())
"""


def test_fused_sharded_overlap_bitwise_subprocess():
    """8-device mesh: the overlap-ordered fused local step is BITWISE
    equal to the staged sharded path (identical ops, identical order —
    only collective issue order differs), and both match the single
    host to fp32 reduction order.  Uneven N exercises padded shards."""
    code = _PRELUDE + r"""
mesh = jax.make_mesh((8,), ("data",))
store = gmm(1003, dim=16, seed=0)
sch = make_schedule("ddpm_linear", 1000)
host = GoldDiffEngine(store, sch, fused=True)
sh_st = GoldDiffEngine(store, sch, mesh=mesh, fused=False)
sh_fu = GoldDiffEngine(store, sch, mesh=mesh, fused=True)
x0 = store.X[:4]
ok = True
for t in (100, 500, 900):
    eps = jax.random.normal(jax.random.PRNGKey(t), x0.shape)
    xt = sch.add_noise(x0, eps, t)
    bit = maxerr(sh_fu.denoise(xt, t), sh_st.denoise(xt, t))
    e_h = maxerr(sh_fu.denoise(xt, t), host.denoise(xt, t))
    tt = jnp.asarray(t)
    bit_m = maxerr(sh_fu.denoise_masked(xt, tt), sh_st.denoise_masked(xt, tt))
    print("t", t, "bitwise", bit, bit_m, "vs host", e_h)
    ok &= bit == 0.0 and bit_m == 0.0 and e_h < 1e-5
kinds = {k[0] for k in sh_fu._programs}
ok &= "fused_step" in kinds
print("PASS" if ok else "FAIL")
"""
    _run_child(code)


@pytest.mark.slow
def test_fused_2d_mesh_parity_subprocess():
    """2D (batch x store) mesh: queries shard over the batch axis,
    collectives stay on the store axis, outputs match the single host;
    an indivisible batch raises instead of silently mis-sharding."""
    code = _PRELUDE + r"""
host = None
ok = True
store = gmm(1000, dim=16, seed=0)
sch = make_schedule("ddpm_linear", 1000)
host = GoldDiffEngine(store, sch, fused=True)
x0 = store.X[:8]
for shape, names in (((2, 4), ("batch", "data")), ((4, 2), ("data", "batch"))):
    mesh = jax.make_mesh(shape, names)
    eng = GoldDiffEngine(store, sch, mesh=mesh, shard_axis="data",
                         batch_axis="batch", fused=True)
    for t in (150, 750):
        eps = jax.random.normal(jax.random.PRNGKey(t), x0.shape)
        xt = sch.add_noise(x0, eps, t)
        e = maxerr(eng.denoise(xt, t), host.denoise(xt, t))
        em = maxerr(eng.denoise_masked(xt, jnp.asarray(t)),
                    host.denoise_masked(xt, jnp.asarray(t)))
        print("mesh", shape, "t", t, e, em)
        ok &= e < 1e-5 and em < 1e-5
    try:
        eng.denoise(xt[:5], 500)         # 5 % batch_shards != 0
        ok = False
    except ValueError as err:
        ok &= "batch" in str(err)
print("PASS" if ok else "FAIL")
"""
    _run_child(code)


def test_fused_warmup_zero_recompiles():
    """ServeEngine.warmup() with fused=True precompiles the fused
    program kinds: serving afterward never touches the compiler, in
    static and plan modes."""
    from repro.launch.serve import Request, ServeEngine
    for mode in ("static", "plan"):
        eng = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=5,
                          max_batch=4, mode=mode, fused=True)
        eng.warmup()
        n0 = len(eng.engine._programs)
        b0 = eng.engine._builds
        eng.serve([Request(0, 1, seed=1), Request(1, 3, seed=2),
                   Request(2, 4, seed=3)])
        assert len(eng.engine._programs) == n0, f"{mode}: cache grew"
        assert eng.engine._builds == b0, f"{mode}: recompiled"
        if mode == "static":
            assert "fused_step" in {k[0] for k in eng.engine._programs
                                    if isinstance(k, tuple)}


def test_fused_runtime_warms_mixed_segments():
    """ServeRuntime.warmup() with fused=True also precompiles every
    continuous-batching ``plan_seg_mix`` program — re-requesting them
    is a pure cache hit (build counter unchanged)."""
    from repro.launch.runtime import RuntimeConfig, ServeRuntime
    from repro.launch.serve import ServeEngine
    eng = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=5,
                      max_batch=4, mode="plan", fused=True)
    rt = ServeRuntime(eng, RuntimeConfig())
    rt.warmup()
    kinds = {k[0] for k in rt.engine._programs if isinstance(k, tuple)}
    assert "plan_seg_mix" in kinds
    b0 = rt.engine._builds
    for b in eng.batch_buckets():
        for plan in rt.plans.values():
            for pb in plan.buckets:
                rt._mixed_program(b, plan, pb, compile_only=True)
    assert rt.engine._builds == b0, "mixed segment recompiled post-warmup"
