"""device_idle_share.batch (device): 1 - busy union over the traced window, in the batch cell."""
from bench.layers import idle_share as read  # noqa: F401
