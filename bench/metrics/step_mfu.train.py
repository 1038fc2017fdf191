"""The window's model-step FLOPs (from shapes, bench/counts.py) over the
window's length and the chip's peak, in percent."""
from bench.readers import train_mfu as read  # noqa: F401
