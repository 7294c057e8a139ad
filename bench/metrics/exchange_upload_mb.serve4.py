"""Host-to-device MB the exchange sends per batch: the shards' delta
blocks, the queries and any tree or plane bytes (exchange layer)."""
from exchange import upload_mb_per_batch as read  # noqa: F401
