"""Share of tiles the pruning bounds skipped (tile pruning layer)."""
from layers import tiles_skipped_pct as read  # noqa: F401
