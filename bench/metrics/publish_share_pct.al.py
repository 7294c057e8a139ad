"""Share of the write calls' host time spent publishing snapshots
(stream write path): ``p2h.publish`` over ``p2h.write.delete`` +
``p2h.write.insert``."""
from program_spans import publish_share_pct as read  # noqa: F401
