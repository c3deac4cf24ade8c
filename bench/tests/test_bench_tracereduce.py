"""The reduction from a profiler trace to busy time, kernel time and
idle gaps: on hand-made intervals, and on a trace recorded on a v5e."""
from pathlib import Path

import numpy as np
import pytest

from bench import tracereduce as tr

FIXTURE = Path(__file__).resolve().parent / "fixtures"


def _reduced(intervals, names=None, host=None, window=(0.0, 100.0)):
    st = np.array([a for a, _ in intervals], float)
    en = np.array([b for _, b in intervals], float)
    names = names or [f"op{i}" for i in range(len(intervals))]
    dev = tr.DeviceOps("/device:TPU:0", st, en, list(names))
    return tr.Reduced(window=window, devices=[dev],
                      host=host or {k: [] for k in tr.HOST_SPANS})


@pytest.mark.parametrize("intervals,busy", [
    ([], 0.0),
    ([(10, 20)], 10.0),
    ([(10, 20), (15, 30)], 20.0),              # overlap counted once
    ([(10, 20), (20, 30)], 20.0),              # touching
    ([(10, 20), (40, 50), (12, 18)], 20.0),    # nested
    ([(-10, 5), (95, 120)], 10.0),             # clipped to the window
])
def test_busy_union(intervals, busy):
    r = _reduced(intervals)
    assert r.busy_s() == pytest.approx(busy * 1e-9)
    if busy:
        assert r.idle_share() == pytest.approx(1 - busy / 100.0)
    else:
        assert r.idle_share() is None


def test_kernel_time_and_top_ops():
    # a loop (0-60) whose body runs the other four operations
    r = _reduced([(0, 60), (0, 10), (10, 30), (30, 35), (50, 60)],
                 names=["while", "fusion", "support_sqdist", "fusion",
                        "golden_support_aggregate"])
    assert r.kernel_s("support_sqdist") == pytest.approx(20e-9)
    assert r.kernel_s("golden_support_aggregate") == pytest.approx(10e-9)
    assert r.kernel_s("pdist") == 0.0
    top = dict(r.top_ops())
    assert top == pytest.approx({"support_sqdist": 20e-9, "fusion": 15e-9,
                                 "golden_support_aggregate": 10e-9,
                                 "while": 15e-9})
    assert sum(top.values()) == pytest.approx(r.busy_s())


@pytest.mark.parametrize("text,name", [
    ("%support_sqdist.6 = f32[8,1,7168]{2,1,0} custom-call(...)",
     "support_sqdist"),
    ("%golden_support_aggregate = f32[8,1,3072]{2,1,0} custom-call(...)",
     "golden_support_aggregate"),
    ("%fusion.3 = f32[79872,3072]{1,0:T(8,128)} fusion(...)",
     "fusion f32[*,3072]"),
    ("%fusion.2 = (f32[8], s32[8]) fusion(...)", "fusion"),
    ("%copy-done.4 = f32[8,1,3072]{2,1,0} copy-done(...)", "copy-done"),
])
def test_op_name(text, name):
    assert tr.op_name(text) == name


def test_idle_gaps_are_labelled_by_the_host_span():
    host = {"submit": [(0.0, 5.0)], "pump": [(5.0, 60.0)],
            "wait": [(60.0, 100.0)]}
    r = _reduced([(10, 20), (30, 55)], host=host)
    gaps = r.idle_gaps()
    assert [g[0] for g in gaps] == ["wait", "submit", "pump"]
    assert [g[1] for g in gaps] == pytest.approx([45e-9, 10e-9, 10e-9])
    assert sum(g[1] for g in gaps) + r.busy_s() == pytest.approx(100e-9)


@pytest.fixture(scope="module")
def chip():
    """Half a second of the batch cell, traced on one v5e."""
    return tr.reduce(FIXTURE / "v5e_cifar10_batch.xplane.pb")


def _raw(prefix):
    """Independent sum of the raw event durations of one instruction."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE / "v5e_cifar10_batch.xplane.pb"))
    total = 0.0
    for plane in pd.planes:
        for line in plane.lines:
            if plane.name.startswith("/device:") and line.name == "XLA Ops":
                total += sum(e.duration_ns for e in line.events
                             if e.name.startswith(f"%{prefix}."))
    return total * 1e-9


def test_chip_trace_window_and_busy(chip):
    assert chip.window_s == pytest.approx(0.512484653)
    assert 0.0 < chip.busy_s() < chip.window_s
    assert chip.idle_share() == pytest.approx(1 - chip.busy_s()
                                              / chip.window_s)
    assert [d.name for d in chip.devices if d.start.size] == [
        "/device:TPU:0"]
    assert len(chip.host["submit"]) == len(chip.host["pump"]) > 0


@pytest.mark.parametrize("kernel", ["support_sqdist",
                                    "golden_support_aggregate", "pdist"])
def test_chip_trace_kernel_time(chip, kernel):
    assert chip.kernel_s(kernel) > 0.0
    assert chip.kernel_s(kernel) == pytest.approx(_raw(kernel))


def test_chip_trace_breakdown(chip):
    top = chip.top_ops(10_000)
    assert sum(t for _, t in top) == pytest.approx(chip.busy_s())
    assert top[0][0] == "fusion f32[*,3072]"      # the candidate gathers
    gaps = chip.idle_gaps(10_000)
    assert {g[0] for g in gaps} <= set(tr.HOST_SPANS) | {"other"}
    assert sum(g[1] for g in gaps) + chip.busy_s() == pytest.approx(
        chip.window_s)
    assert len(chip.idle_gaps()) == 10
