"""Share of the device-idle time inside the benchmark's flush spans
that falls while a delta block is on its way to the device (delta scan
layer): from each ``p2h.delta.upload`` call to the end of its transfer
in the trace."""
from program_spans import idle_in_upload_pct as read  # noqa: F401
