"""Percent of the traced window in which the device ran no operation
(the trace's busy union over the window)."""
from bench.readers import device_idle as read  # noqa: F401
