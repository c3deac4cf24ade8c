"""seam_ms.batch (runtime): mean host time from the end of one wave.execute span to the start of the next, in the batch cell."""
from bench.programtrace import seam_ms as read  # noqa: F401
