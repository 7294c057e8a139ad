"""Median host time of one engine batch call (engine layer)."""
from layers import engine_call_ms as read  # noqa: F401
