"""The stacked sweep kernel's share of its roofline (kernel)."""
from layers import sweep_roofline_pct as read  # noqa: F401
