"""latency_p95_s: 95th percentile of latency over the requests due in the window."""
import numpy as np

from bench.layers import latencies


def read(run):
    lat = latencies(run)
    return float(np.quantile(lat, 0.95)) if lat else None
