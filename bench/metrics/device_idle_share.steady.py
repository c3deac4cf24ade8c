"""device_idle_share.steady (device): 1 - busy union over the traced window, in the steady cell."""
from bench.layers import idle_share as read  # noqa: F401
