"""segment_ms.batch (plan segments): mean wave.segment span time, in the batch cell."""
from bench.layers import segment_ms as read  # noqa: F401
