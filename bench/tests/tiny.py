"""A cell of the benchmark cut to a size the CPU tests can hold."""
from __future__ import annotations

import copy
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import harness  # noqa: E402

N = 512
SHAPE = [8, 8, 3]


def cell(name: str = "cifar10-golddiff.batch") -> harness.Cell:
    """The named cell with its store cut to N rows of 8x8x3."""
    c = harness.load_cell(name)
    cfg = copy.deepcopy(c.config)
    cfg["dataset"].update(n=N, image_shape=SHAPE, class_counts=[N // 4] * 4)
    c.config = cfg
    return c
