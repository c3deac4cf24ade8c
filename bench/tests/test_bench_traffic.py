"""The traffic generator is a function of its data file and the seed."""
import collections
import json
from pathlib import Path

import numpy as np
import pytest

from bench import traffic

MIXES = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2**31 + 977                # wider than a signed 32-bit seed


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _closed(seed, count=200):
    s = traffic.Stream(_mix("batch"), seed)
    return [(p.n, p.seed) for p in (s.next(0.0) for _ in range(count))]


def _open(seed):
    return [(p.n, p.seed, p.due)
            for p in traffic.open_schedule(_mix("steady"), seed, 30.0)]


@pytest.mark.parametrize("make", [_closed, _open], ids=["closed", "open"])
@pytest.mark.parametrize("seed", [0, 12345, BIG])
def test_same_seed_same_requests(make, seed):
    assert make(seed) == make(seed)
    assert make(seed) != make(seed + 1)


@pytest.mark.parametrize("mix", ["batch", "steady"])
def test_every_seed_sends_the_same_sizes(mix):
    spec = _mix(mix)
    block = sum(int(v) for v in spec["sizes"].values())
    streams = {seed: traffic.Stream(spec, seed) for seed in (1, 2, BIG)}
    counts = {seed: collections.Counter(s.next(0.0).n
                                        for _ in range(3 * block))
              for seed, s in streams.items()}
    want = {int(k): 3 * int(v) for k, v in spec["sizes"].items()}
    assert all(c == want for c in counts.values())


def test_open_loop_gaps_are_poisson_quantiles():
    spec = _mix("steady")
    due = np.array([p.due for p in traffic.open_schedule(spec, BIG, 60.0)])
    gaps = np.diff(np.concatenate([[0.0], due]))
    k = int(spec["gap_block"])
    assert np.allclose(np.sort(gaps[:k]), traffic.exponential_gaps(
        spec["rate_per_s"], k))
    assert abs(len(due) / 60.0 - spec["rate_per_s"]) < 0.1 * spec[
        "rate_per_s"]


@pytest.mark.parametrize("seed", [0, BIG])
def test_request_seeds_fit_the_engine(seed):
    seeds = [s for _, s in _closed(seed, 500)]
    assert 0 <= min(seeds) and max(seeds) < 2**31
