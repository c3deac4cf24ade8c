"""step_mfu (engine step): least time of the traced steps at the v5e peaks over the window."""
from bench.layers import step_mfu as read  # noqa: F401
