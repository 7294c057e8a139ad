"""Readers of the sharded exchange's layer (``p2h.exchange.*`` spans,
``exchange_upload_bytes``) and of the stacked kernel's roofline share
per chip of a mesh launch.

A placed sharded index runs round 2 as one mesh launch: chip ``j``
sweeps the segments of shard ``j``, which live on it.  The least time of
a chip is its own built-tile steps at ``kernel_cost.main_tile_bytes``
over one chip's HBM rate (round 2 enters with the exchanged lambda0 and
runs single-pass, so no probe step is charged); the trace's kernel time
is the mean over the chips that ran it (``trace_reduce``), so the share
divides the steps evenly over the chips.  Like ``layers`` it can read
low, never high: pad tiles are charged nothing.

Every reader returns ``None`` where its span or counter never ran, so
a program without them reads nothing and raises nothing.
"""
from __future__ import annotations

import layers
import program_spans


def span_p50_ms(ctx, name: str):
    """The p50 duration of span ``name`` over the window, in ms."""
    s = program_spans.span(ctx, name)
    return float(s["p50_ms"]) if s and s["count"] else None


def upload_mb_per_batch(ctx):
    """Host-to-device bytes the exchange sent, per engine batch, in MB."""
    n = program_spans.counter(ctx, "exchange_upload_bytes")
    batches = ctx.stats.get("batches", 0)
    if n is None or not batches:
        return None
    return n / batches / 1e6


def mesh_roofline_pct(ctx):
    """Least time of one chip's built-tile steps over the mean kernel
    time of a chip, in %.  The scanned steps are the kernel's own count
    (``stacked_scan_steps``, round 2's launches only): the engine's
    ``stacked`` counters also hold round 1's beam leaves, which the
    kernel never runs."""
    t, steps = layers.kernel_seconds(ctx), layers.offered_steps(ctx)
    scanned = program_spans.counter(ctx, "stacked_scan_steps")
    chips = ctx.config["shards"]
    if not t or not scanned or not steps:
        return None
    cfg = ctx.config
    least, _ = ctx.cost.least_seconds(
        scanned=scanned / chips, steps=steps / chips, probe_steps=0,
        n0=cfg["n0"], d=cfg["d"], bq=cfg["kernel_block_queries"],
        peak=ctx.peak)
    return 100.0 * least / t
