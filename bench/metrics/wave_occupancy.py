"""wave_occupancy (runtime): active rows over padded rows of the wave.segment spans, time-weighted."""
from bench.layers import wave_occupancy as read  # noqa: F401
