"""Work counts and chip peaks: the yardstick of the roofline metrics.

A frozen copy of the program's analytic stage costs (the conventions of
``step_stage_costs`` at the gather strategy with an exact, materialized
screen), taken at each step's real (m_t, k_t) from ``bench/schedule.py``:

* a matmul-form distance counts 2 * rows * dim FLOPs per query;
* bytes are operand traffic read once: stored rows at 4 bytes (fp32
  storage), norms, logits and outputs at 4 bytes;
* the screen reads the whole proxy store once per call, shared by the
  batch; the re-rank and the aggregate read each query's own rows.

``step_least`` is the least that any correct exact step must do, so a
share of the peak built on it cannot pass 100% whatever implements the
step: one read of the proxy store and of one query's m_t candidate rows
per segment-step, and 2 (N d_proxy + m_t D + k_t D) FLOPs per active
row.  An implementation that reads a candidate row once for several
queries of a wave would read less than the per-query kernel counts
assume; the roofline metrics would then have to count distinct rows.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
ESZ = 4.0                  # fp32 storage


def peaks(device_kind: str) -> dict:
    """The chip's peaks; a device missing from the table is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def widths(config: dict) -> tuple[float, float, float]:
    """(N, D, d_proxy) of a configuration."""
    h, w, c = (int(s) for s in config["dataset"]["image_shape"])
    f = int(config["golddiff"]["proxy_factor"])
    return (float(config["dataset"]["n"]), float(h * w * c),
            float((h // f) * (w // f) * c))


def stage_costs(config: dict, m: int, k: int, batch: int) -> dict:
    """``{stage: {"flops", "bytes"}}`` of one step for ``batch`` rows."""
    n, dim, dp = widths(config)
    b = float(batch)
    return {
        "screen": {"flops": 2.0 * b * n * dp,
                   "bytes": n * dp * ESZ + b * dp * 4.0 + b * n * 4.0},
        "rerank": {"flops": 2.0 * b * m * dim,
                   "bytes": b * m * (dim * ESZ + 8.0)},
        "aggregate": {"flops": 2.0 * b * k * dim,
                      "bytes": b * k * (dim * ESZ + 4.0)},
    }


def least_time(flops: float, byts: float, peak: dict) -> float:
    """Seconds at the roofline: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])


def step_least(config: dict, m: int, k: int, active: int) -> dict:
    """Least FLOPs and bytes of one segment-step with ``active`` rows."""
    n, dim, dp = widths(config)
    return {"flops": 2.0 * active * (n * dp + m * dim + k * dim),
            "bytes": n * dp * ESZ + m * dim * ESZ}

