"""Faults planted in the timed path, for the check that ``correct``
fails them (``bench/tests/test_bench_run.py``, ``bench/readings.py``).

Each takes the warmed runtime and a ``setattr(obj, name, value)`` that
the caller can undo (pytest's ``monkeypatch.setattr``, or plain
``setattr`` in a process that builds a fresh runtime per fault).
"""
from __future__ import annotations

import numpy as np


def state_unchanged(rt, setattr_):
    """Every segment returns its input state: no step is taken."""
    real = rt._run_segment

    def seg(wave, s):
        status, out = real(wave, s)
        return status, (wave.x if status == "ok" else out)
    setattr_(rt, "_run_segment", seg)


def half_rows_left_out(rt, setattr_):
    """The second half of every wave's rows is left where it was."""
    real = rt._run_segment

    def seg(wave, s):
        status, out = real(wave, s)
        if status == "ok":
            out = np.array(out)
            half = wave.bucket // 2
            out[half:] = wave.x[half:]
        return status, out
    setattr_(rt, "_run_segment", seg)


def answer_altered(rt, setattr_):
    """Each delivered image has one pixel shifted by 0.5."""
    real = rt._deliver_part

    def deliver(wave, p, ofs, now):
        real(wave, p, ofs, now)
        if p.ticket.images is not None:
            p.ticket.images.reshape(p.n, -1)[:, 0] += 0.5
    setattr_(rt, "_deliver_part", deliver)


FAULTS = {f.__name__: f for f in (state_unchanged, half_rows_left_out,
                                  answer_altered)}
