"""Device idle share inside the benchmark's flush spans (device)."""
from layers import idle_in_flush_pct as read  # noqa: F401
