"""The stacked sweep kernel's own step counts as per-layer numbers.

The program counts, for both passes of every stacked launch, the grid
steps that scanned a tile (``stacked_scan_steps``) and the steps of the
top-k insertion loop those scans ran (``stacked_insert_steps``), in the
engine's ``stats()["span_counters"]``.  A program without the counters
reads ``None``.
"""
from __future__ import annotations

import harness


def topk_insert_share(ctx, workload: str):
    """Insertion steps run over the ``k`` steps a tile of a fixed-length
    loop would run, over the window, in %; ``k`` is the cell's mix's."""
    counters = ctx.stats.get("span_counters", {})
    scan = counters.get("stacked_scan_steps")
    insert = counters.get("stacked_insert_steps")
    if not scan or insert is None:
        return None
    k = harness.load_cell(workload)[3]["k"]
    return 100.0 * insert / (k * scan)
