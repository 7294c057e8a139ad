"""Device time of the stacked sweep kernel per micro-batch (kernel)."""
from layers import sweep_kernel_ms as read  # noqa: F401
