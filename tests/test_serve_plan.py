"""ServeEngine on trajectory plans: seeds, warmup, and recompile guard.

* **per-request determinism** — a request's images depend only on its
  own seed (per-row ``fold_in`` keys), never on which wave co-batched
  it or which batch bucket the wave padded to.  The pre-plan engine
  seeded a whole wave from its first request's seed, so outputs
  changed with wave packing.
* **warmup** — ``warmup()`` precompiles every (batch-bucket x
  shape-bucket) program; serving any mixed request stream afterwards
  never grows the engine's ``_programs`` cache.  The subprocess
  variant runs the same guard under ``jax.log_compiles`` on an
  emulated 8-device mesh (the CI `mesh` job's recompile guard).
* **mode parity** — plan / scan / static serving agree on identical
  requests.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.launch.serve import Request, ServeEngine

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def eng():
    return ServeEngine("gmm", {"n": 1024, "dim": 16}, num_steps=6,
                       max_batch=8)


def test_batch_buckets(eng):
    assert eng.batch_buckets() == [1, 2, 4, 8]
    assert eng._bucket_for(1) == 1
    assert eng._bucket_for(3) == 4
    assert eng._bucket_for(8) == 8
    assert eng._bucket_for(100) == 8      # oversized: capped at max_batch
    odd = ServeEngine("gmm", {"n": 256, "dim": 8}, num_steps=3, max_batch=6)
    assert odd.batch_buckets() == [1, 2, 4, 6]


def test_serve_seed_determinism(eng):
    """Same request alone vs co-batched (different wave AND different
    batch bucket) -> same images; rows are key-independent."""
    alone = eng.serve([Request(0, 2, seed=7)])[0].images
    res = eng.serve([Request(0, 2, seed=7), Request(1, 3, seed=9)])
    np.testing.assert_allclose(res[0].images, alone, rtol=0, atol=1e-6)
    # order flipped: request 7 lands at a different row offset
    res2 = eng.serve([Request(1, 3, seed=9), Request(0, 2, seed=7)])
    np.testing.assert_allclose(res2[1].images, alone, rtol=0, atol=1e-6)
    # and request 9's images are equally wave-independent
    np.testing.assert_allclose(res2[0].images, res[1].images,
                               rtol=0, atol=1e-6)
    # different seeds genuinely differ
    other = eng.serve([Request(0, 2, seed=8)])[0].images
    assert not np.allclose(alone, other)


def test_serve_request_packing(eng):
    res = eng.serve([Request(0, 3, seed=1), Request(1, 2, seed=2),
                     Request(2, 6, seed=3)])
    assert [r.request_id for r in res] == [0, 1, 2]
    assert sum(r.images.shape[0] for r in res) >= 3 + 2 + 6
    assert all(np.isfinite(r.images).all() for r in res)


def test_serve_oversized_request_fully_served(eng):
    """A request larger than max_batch is chunked across waves: every
    image is delivered, and chunking does not change any row's noise
    stream (row i always draws from fold_in(seed, i))."""
    res = eng.serve([Request(0, 19, seed=5)])          # max_batch = 8
    assert res[0].images.shape[0] == 19
    assert np.isfinite(res[0].images).all()
    # same request on a wider engine: rows agree, so chunk boundaries
    # are invisible to the caller
    wide = ServeEngine("gmm", {"n": 1024, "dim": 16}, num_steps=6,
                       max_batch=32)
    res_w = wide.serve([Request(0, 19, seed=5)])
    np.testing.assert_allclose(res[0].images, res_w[0].images,
                               rtol=0, atol=1e-6)
    # zero-image requests come back empty, not broken
    res0 = eng.serve([Request(1, 0, seed=1), Request(2, 2, seed=2)])
    assert res0[0].images.shape[0] == 0
    assert res0[1].images.shape[0] == 2


def test_serve_warmup_then_no_recompile():
    eng = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=5,
                      max_batch=4)
    stats = eng.warmup()
    # (batch buckets) x (plan segments + init-noise + row-key programs)
    n_batch = len(eng.batch_buckets())
    assert stats["programs_compiled"] == \
        n_batch * (eng.plan.num_buckets + 2)
    n0 = len(eng.engine._programs)
    eng.serve([Request(0, 1, seed=1), Request(1, 3, seed=2),
               Request(2, 2, seed=3), Request(3, 4, seed=4)])
    assert len(eng.engine._programs) == n0, \
        "serving recompiled after warmup"


def test_serve_modes_agree():
    """plan == scan == static serving on identical requests (identical
    per-row noise streams, fp32-tolerance outputs)."""
    reqs = [Request(0, 2, seed=3), Request(1, 2, seed=4)]
    outs = {}
    for mode in ("plan", "scan", "static"):
        e = ServeEngine("gmm", {"n": 512, "dim": 16}, num_steps=5,
                        max_batch=4, mode=mode)
        outs[mode] = np.concatenate(
            [r.images.reshape(r.images.shape[0], -1)
             for r in e.serve(list(reqs))])
    np.testing.assert_allclose(outs["plan"], outs["static"],
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(outs["plan"], outs["scan"],
                               rtol=1e-4, atol=1e-5)


def test_serve_mode_validation():
    with pytest.raises(ValueError):
        ServeEngine("gmm", {"n": 256, "dim": 8}, mode="bogus")
    with pytest.raises(ValueError):
        ServeEngine("cifar_like", {"n": 128}, base="pca", mode="plan")
    # patch bases fall back to static under auto
    e = ServeEngine("cifar_like", {"n": 128}, base="pca", num_steps=3)
    assert e.mode == "static" and e.plan is None


@pytest.mark.slow
def test_serve_warmup_recompile_guard_subprocess():
    """CI recompile guard (emulated 8-device mesh): after warmup(), a
    mixed request stream must not compile ANY program — checked both
    by the engine cache size and by jax.log_compiles capture."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import io, logging
import jax, numpy as np
from repro.launch.serve import Request, ServeEngine

mesh = jax.make_mesh((8,), ("data",))
eng = ServeEngine("gmm", {"n": 1003, "dim": 16}, num_steps=5,
                  max_batch=8, mesh=mesh)
stats = eng.warmup()
print("warmup:", stats)
n0 = len(eng.engine._programs)

log = io.StringIO()
handler = logging.StreamHandler(log)
logging.getLogger("jax").addHandler(handler)
with jax.log_compiles(True):
    res = eng.serve([Request(0, 1, seed=1), Request(1, 5, seed=2),
                     Request(2, 3, seed=3), Request(3, 8, seed=4),
                     Request(4, 2, seed=5)])
logging.getLogger("jax").removeHandler(handler)

ok = all(np.isfinite(r.images).all() for r in res)
cache_grew = len(eng.engine._programs) - n0
compiled = [ln for ln in log.getvalue().splitlines()
            if "Compiling" in ln and "jit(" in ln]
print("cache delta:", cache_grew)
print("post-warmup compiles:", compiled[:5])
print("PASS" if ok and cache_grew == 0 and not compiled else "FAIL")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=420, cwd=str(REPO), env=env)
    assert "PASS" in r.stdout, r.stdout + r.stderr


def test_cli_exits_nonzero_on_nonfinite_images(monkeypatch, capsys):
    """The serving CLI reports ``finite=False`` per request AND fails:
    NaN rows injected into every plan segment make ``main`` exit
    non-zero instead of printing a result and returning 0."""
    from repro.launch import serve
    from repro.launch.faults import FaultConfig, injected
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    argv = ["--dataset", "gmm", "--n", "256", "--requests", "2",
            "--batch", "2", "--steps", "3", "--no-warmup"]
    serve.main(argv)                     # healthy run: returns normally
    assert "finite=False" not in capsys.readouterr().out
    with injected(FaultConfig(nan_rate=1.0)):
        with pytest.raises(SystemExit) as exc:
            serve.main(argv)
    assert exc.value.code not in (0, None)
    assert "finite=False" in capsys.readouterr().out
