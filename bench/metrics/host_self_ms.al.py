"""Mean host time of one engine micro-batch outside its wait for the
device (engine layer): ``p2h.batch`` less ``p2h.device_wait``, per
batch.  The runtime's copy work on its own threads is not in it."""
from program_spans import host_self_ms as read  # noqa: F401
