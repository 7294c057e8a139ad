"""Ids-plane rewrite time the deletes leave on the query path, per
engine batch (stream write path): the ``p2h.stacked.ids_rewrite`` span's
total over the window's batches."""
from program_spans import ids_rewrite_ms as read  # noqa: F401
