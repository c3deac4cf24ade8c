"""latency_p50_s: median latency over the requests due in the window."""
import numpy as np

from bench.layers import latencies


def read(run):
    lat = latencies(run)
    return float(np.quantile(lat, 0.5)) if lat else None
