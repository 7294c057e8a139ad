"""Median time to land one copy of the delta block on the device (delta
scan layer): from the program's ``p2h.delta.upload`` span, the call
that starts the copy, to the end of the TPU runtime's host-to-device
transfer events that follow it in the trace."""
from program_spans import delta_upload_ms as read  # noqa: F401
