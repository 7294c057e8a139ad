"""Rate at which the delta block lands on the device (delta scan
layer): bytes per ``p2h.delta.upload`` (the ``delta_upload_bytes``
counter) over the mean time from the call to the end of its transfer in
the trace."""
from program_spans import delta_upload_gbps as read  # noqa: F401
