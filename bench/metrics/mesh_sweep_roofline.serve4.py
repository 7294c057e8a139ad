"""The stacked sweep kernel's share of its roofline per chip of the
mesh launch (stacked sweep kernel)."""
from exchange import mesh_roofline_pct as read  # noqa: F401
