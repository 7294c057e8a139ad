"""Bytes and operations of the stacked sweep kernel per visited tile,
worked out from the tile's shapes: the benchmark's own copy of the
arithmetic, so that no change to the program moves the yardstick.

One grid step of the float32 main pass ("pass B") visits one leaf tile
of one segment for one block of ``bq`` queries.  It streams from HBM:

* the tile's points: ``n0`` rows of ``dp`` float32 lanes, ``dp`` being
  ``d + 1`` (the appended 1) padded to a multiple of 128;
* the tile's packed per-point rows: ``4 x n0`` int32 (rx, xcos, xsin
  bit-cast, ids).

A tile that is scanned (not skipped) also runs the scoring matmul,
``2 bq (d + 1) n0`` operations (the lane padding is not work the
algorithm needs).  A skipped tile is still streamed: its blocks are
fetched before the kernel decides to skip it.

The bfloat16 probe pass ("pass A", :func:`probe_tile_bytes` a step)
sweeps the first ``probe_tiles`` preferred tiles of every segment for
each query block; pass B then sweeps every tile.  Steps over pad tiles
(the tree's pad leaves, the launch's common grid) are charged nothing,
and pass A's matmuls are left out: a share built on
:func:`least_seconds` can read low, never high.
"""
from __future__ import annotations

LANE = 128
ROW_PLANES = 4


def lane_pad(x: int) -> int:
    return -(-x // LANE) * LANE


def main_tile_bytes(n0: int, d: int) -> int:
    """HBM bytes of one pass-B step; ``d`` is the raw point dimension."""
    return n0 * lane_pad(d + 1) * 4 + ROW_PLANES * n0 * 4


def probe_tile_bytes(n0: int, d: int) -> int:
    """HBM bytes of one bfloat16 probe step (points in bf16, the same
    rows plane, one slack scalar)."""
    return n0 * lane_pad(d + 1) * 2 + ROW_PLANES * n0 * 4 + 4


def scan_ops(n0: int, d: int, bq: int) -> int:
    """Operations of the scoring matmul of one scanned tile."""
    return 2 * bq * (d + 1) * n0


def least_seconds(*, scanned: int, steps: int, probe_steps: int, n0: int,
                  d: int, bq: int, peak: dict) -> tuple[float, str]:
    """The least time the chip could take for ``probe_steps`` pass-A
    steps and ``steps`` pass-B steps over built tiles, ``scanned`` of
    which ran the scoring matmul: the larger of bytes over HBM bandwidth
    and operations over peak; returns ``(seconds, "bytes" | "ops")``."""
    t_bytes = (steps * main_tile_bytes(n0, d)
               + probe_steps * probe_tile_bytes(n0, d)) \
        / peak["hbm_bytes_per_s"]
    t_ops = scanned * scan_ops(n0, d, bq) / peak["bf16_flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_ops else (t_ops, "ops")
