"""A whole run at a CPU-sized cell: the comparison that decides
``correct`` passes the program, and fails its lower-precision control
and every fault the cell can have, planted in the timed path."""
import io
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from bench import harness
from bench.faults import FAULTS
from bench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 4099
SECONDS = 1.5


@pytest.fixture(scope="module")
def cell():
    return tiny.cell()


@pytest.fixture(scope="module")
def served(cell):
    return harness.build(cell, SEED)


def _run(cell, served, seed=SEED):
    res = harness.run(cell, seed, SECONDS, False, require_tpu=False,
                      served=served, out=io.StringIO(), err=io.StringIO())
    served.rt.run_until_idle()
    return res


def test_program_is_correct(cell, served):
    res = _run(cell, served)
    chk = res["checks"]
    assert res["correct"], chk
    assert list(res)[-1] == "checks"
    assert chk["images_compared"]["value"] >= chk["images_compared"]["limit"]
    assert chk["compiles_in_window"]["value"] == 0
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(cell, served, fault, monkeypatch):
    FAULTS[fault](served.rt, monkeypatch.setattr)
    res = _run(cell, served)
    assert not res["correct"], res["checks"]
    chk = res["checks"]["rel_err_p90"]
    assert chk["value"] > chk["limit"]


def test_lower_precision_control_is_not_correct(cell):
    control = harness.build(cell, SEED, storage_dtype=jnp.bfloat16)
    assert control.eng.engine.X.dtype == jnp.bfloat16
    res = _run(cell, control)
    assert not res["correct"], res["checks"]
    chk = res["checks"]["rel_err_p90"]
    assert chk["value"] > 3 * chk["limit"]


def test_no_accelerator_raises():
    with pytest.raises(harness.NoAccelerator):
        harness.device_info(1, require_tpu=True)


def _command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "cifar10-golddiff.batch", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("where", ["checkout", "benchmark_files_only"])
def test_command_exits_nonzero_without_a_tpu(where, tmp_path):
    cwd = ROOT
    if where == "benchmark_files_only":
        shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
        shutil.copytree(ROOT / "bench", tmp_path / "bench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = tmp_path
    p = _command(cwd)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
