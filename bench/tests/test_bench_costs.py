"""The benchmark's frozen work counts and schedule against the program."""
import copy
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import costs, harness, schedule
from bench.tests import tiny

CONFIG = harness.load_cell("cifar10-golddiff.batch").config


def _program_engine(config):
    """A stand-in engine at the configuration's full width, with the
    program's own size rule, for ``step_stage_costs``."""
    from repro.core import GoldDiffConfig, make_schedule
    from repro.core.engine import schedule_sizes
    n, dim, dp = (int(v) for v in costs.widths(config))
    cfg = GoldDiffConfig(**{k: v for k, v in config["golddiff"].items()})
    sch = make_schedule(config["sampling"]["schedule"], 1000)
    return types.SimpleNamespace(
        store=types.SimpleNamespace(n=n, dim=dim),
        proxy=types.SimpleNamespace(shape=(n, dp)),
        X=types.SimpleNamespace(dtype=np.dtype(np.float32)),
        sizes=lambda t: schedule_sizes(cfg, sch, t, n),
        use_index=lambda t: False, use_stream=lambda b: False,
        strategy_for=lambda t: "gather")


def _tiny_engine():
    """The program's own engine, gather strategy, materialized screen."""
    from repro.core import GoldDiffConfig, make_schedule
    from repro.core.engine import GoldDiffEngine
    from repro.data.synthetic import image_store
    config = tiny.cell().config
    store = image_store(tiny.N, *tiny.SHAPE, num_classes=4, seed=0)
    eng = GoldDiffEngine(store, make_schedule("ddpm_linear", 1000),
                         GoldDiffConfig(), backend="xla",
                         strategy="gather", screen="materialized")
    return config, eng


@pytest.mark.parametrize("batch", [1, 3, 8])
@pytest.mark.parametrize("where", ["full_width", "program_engine"])
def test_stage_costs_match_the_program(where, batch):
    from repro.core.plan import step_stage_costs
    if where == "full_width":
        config, eng = CONFIG, _program_engine(CONFIG)
    else:
        config, eng = _tiny_engine()
    for st in schedule.steps(config):
        want = step_stage_costs(eng, st.t, batch)
        got = costs.stage_costs(config, st.m, st.k, batch)
        assert set(got) == set(want)
        for stage in want:
            assert got[stage] == pytest.approx(want[stage], rel=1e-12)


@pytest.mark.parametrize("n", [512, 14630, 50000])
def test_step_sizes_match_the_served_masked_path(n):
    """The served plan masks each step to (m_t, k_t) computed in float32
    from the traced timestep; the reference's float64 sizes agree."""
    from repro.core import GoldDiffConfig, make_schedule
    config = copy.deepcopy(CONFIG)
    config["dataset"]["n"] = n
    sch = make_schedule("ddpm_linear", 1000)
    m_min, m_max, k_min, k_max = GoldDiffConfig().sizes(n)
    for st in schedule.steps(config):
        g = sch.g(jnp.int32(st.t))
        m = int(jnp.floor(m_min + (m_max - m_min) * (1.0 - g)))
        k = int(jnp.floor(k_min + (k_max - k_min) * g))
        assert (st.m, st.k) == (m, k), st


def test_grid_matches_the_program():
    from repro.core import make_schedule
    from repro.core.schedules import sampling_timesteps
    sch = make_schedule("ddpm_linear", 1000)
    assert schedule.grid(CONFIG["sampling"]) == list(
        sampling_timesteps(sch, CONFIG["sampling"]["num_steps"]))
    a, b = schedule.coefficients(CONFIG["sampling"])
    assert np.array_equal(a, sch.a) and np.array_equal(b, sch.b)


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_known_device_has_peaks(kind):
    p = costs.peaks(kind)
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks(kind)


def test_step_least_is_below_the_staged_counts():
    """The whole step's least work never exceeds what the staged
    kernels are counted to do, so step_mfu <= any stage's share."""
    for st in schedule.steps(CONFIG):
        for active in (1, 5, 8):
            least = costs.step_least(CONFIG, st.m, st.k, active)
            staged = costs.stage_costs(CONFIG, st.m, st.k, active)
            assert least["flops"] <= sum(c["flops"] for c in staged.values())
            assert least["bytes"] <= sum(c["bytes"] for c in staged.values())
