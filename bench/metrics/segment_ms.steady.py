"""segment_ms.steady (plan segments): mean wave.segment span time, in the steady cell."""
from bench.layers import segment_ms as read  # noqa: F401
