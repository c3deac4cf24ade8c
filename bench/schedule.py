"""The sampler's schedule as a configuration states it, on the host.

The DDPM linear schedule (Ho et al. 2020: betas from 1e-4 to 2e-2 over
1,000 steps) in the affine form x_t = a_t x_0 + b_t eps, the evenly
spaced DDIM grid, and GoldDiff's per-step sizes (arXiv:2602.16498,
Eqs. 4 and 6):

    m_t = floor(m_min + (m_max - m_min) (1 - g(sigma_t)))
    k_t = floor(k_min + (k_max - k_min) g(sigma_t))

with g the log-linear position of sigma_t = b_t / a_t between the
smallest and the largest sigma on the schedule.  The reference and the
work counts both read these; nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Step:
    t: int          # timestep this DDIM step denoises at
    t_next: int     # timestep it lands on
    m: int          # candidates kept by the proxy screen
    k: int          # golden support re-ranked out of them


def ddpm_linear(train_steps: int) -> tuple[np.ndarray, np.ndarray]:
    """(a, b), each [train_steps + 1], float64."""
    betas = np.linspace(1e-4, 2e-2, train_steps)
    abar = np.cumprod(1.0 - betas)
    a = np.concatenate([[1.0], np.sqrt(abar)])
    b = np.concatenate([[1e-4], np.sqrt(1.0 - abar)])
    return a, b


SCHEDULES = {"ddpm_linear": ddpm_linear}


def coefficients(sampling: dict) -> tuple[np.ndarray, np.ndarray]:
    return SCHEDULES[sampling["schedule"]](int(sampling["train_steps"]))


def grid(sampling: dict) -> list[int]:
    """The DDIM grid, descending, ``num_steps + 1`` points."""
    T = int(sampling["train_steps"])
    ts = np.unique(np.linspace(0, T, int(sampling["num_steps"]) + 1)
                   .round().astype(int))
    return [int(t) for t in ts[::-1]]


def size_bounds(golddiff: dict, n: int) -> tuple[int, int, int, int]:
    """(m_min, m_max, k_min, k_max) as fractions of the store's rows."""
    m_min = max(1, int(n * golddiff["m_min_frac"]))
    m_max = max(m_min, int(n * golddiff["m_max_frac"]))
    k_min = max(1, int(n * golddiff["k_min_frac"]))
    k_max = min(max(k_min, int(n * golddiff["k_max_frac"])), m_min)
    return m_min, m_max, k_min, k_max


def steps(config: dict) -> list[Step]:
    """Every DDIM step of one trajectory with its (m_t, k_t)."""
    sampling = config["sampling"]
    n = int(config["dataset"]["n"])
    a, b = coefficients(sampling)
    log_sig = np.log(b[1:] / a[1:])
    lo, hi = log_sig.min(), log_sig.max()
    m_min, m_max, k_min, k_max = size_bounds(config["golddiff"], n)
    ts = grid(sampling)
    out = []
    for t, t_next in zip(ts[:-1], ts[1:]):
        tt = min(max(t, 1), len(a) - 1)
        g = float(np.clip((np.log(b[tt] / a[tt]) - lo) / (hi - lo), 0, 1))
        m = int(math.floor(m_min + (m_max - m_min) * (1.0 - g)))
        k = int(math.floor(k_min + (k_max - k_min) * g))
        m = max(1, min(m, n))
        out.append(Step(t, t_next, m, max(1, min(k, m, n))))
    return out
