"""Per-layer arithmetic shared by the metric readers in ``metrics/``.

Each function takes the run (``harness.Run``) and returns a number, or
None when the run holds nothing to read it from (an untraced run, a
trace with no device operations, a kernel that never ran).
"""
from __future__ import annotations

import numpy as np

from bench import costs


def _window_s(run) -> float:
    return run.trace.window_s if run.trace is not None else run.window_s


def _row_steps(run):
    """(step, active rows) for every DDIM step of every traced segment."""
    for seg in run.segments:
        for i in range(int(seg["start"]), int(seg["stop"])):
            yield run.steps[i], int(seg["active"])


def wave_occupancy(run):
    """Share of the padded batch rows that advanced, weighted by time."""
    den = sum(s["bucket"] * s["dur"] for s in run.segments)
    if not den:
        return None
    return 100.0 * sum(s["active"] * s["dur"] for s in run.segments) / den


def segment_ms(run):
    """Mean host-clock time of a runtime segment (ends in a block)."""
    if not run.segments:
        return None
    return 1e3 * float(np.mean([s["dur"] for s in run.segments]))


def step_mfu(run):
    """The whole step's share of the chip's roofline over the window."""
    if run.peak is None or not run.segments:
        return None
    least = 0.0
    for seg in run.segments:
        for i in range(int(seg["start"]), int(seg["stop"])):
            st = run.steps[i]
            c = costs.step_least(run.cell.config, st.m, st.k,
                                 int(seg["active"]))
            least += costs.least_time(c["flops"], c["bytes"], run.peak)
    return 100.0 * least / _window_s(run)


def kernel_roofline(run, kernel: str, stage: str):
    """A kernel's share of its roofline: the least time of the work it
    did (the algorithm's, per active row, at each step's real sizes)
    over its device time in the trace."""
    if run.peak is None or run.trace is None or not run.segments:
        return None
    dev_s = run.trace.kernel_s(kernel)
    if dev_s <= 0.0:
        return None
    least = 0.0
    for st, active in _row_steps(run):
        c = costs.stage_costs(run.cell.config, st.m, st.k, active)[stage]
        least += costs.least_time(c["flops"], c["bytes"], run.peak)
    return 100.0 * least / dev_s


def idle_share(run):
    """Share of the traced window with no operation on the device."""
    if run.trace is None:
        return None
    share = run.trace.idle_share()
    return None if share is None else 100.0 * share


def latencies(run) -> list[float]:
    """Latency of every request due in the window, from when it was due
    to delivery.  A request that never came (expired, failed, rejected,
    or still open when the open loop gave up) counts as missing: it
    takes the longest wait the benchmark allows, the window plus the
    drain, so that it lands in the tail."""
    from bench.harness import DRAIN_S
    closed = run.cell.traffic["loop"] == "closed"
    out = []
    for r in run.records:
        if r.due >= run.window_s or (closed and r.status == "open"):
            continue
        out.append(r.finished - r.due if r.status == "done"
                   else run.window_s + DRAIN_S)
    return out
