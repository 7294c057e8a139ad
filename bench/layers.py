"""Shared arithmetic of the metric readers in ``bench/metrics/``.

A reader is ``read(ctx) -> float | None``; ``None`` means the run had
nothing to read (no batch, no trace, no kernel event), and the metric is
left out of the result line.  ``ctx`` is :class:`run.Context`.
"""
from __future__ import annotations

import numpy as np

import trace_reduce

#: the stacked sweep's Mosaic kernel among the trace's device ops,
#: matched on the op's name and string stats (``trace_reduce.op_text``).
#: Compiled for a TPU v5e, both passes are ``tpu_custom_call``
#: instructions named after the jitted launch (``_run_stacked.<n>``)
#: with op name ``jit(_run_stacked)/pallas_call``; the serving path runs
#: no other Mosaic kernel.
STACKED_KERNEL = r"tpu_custom_call|pallas_call|^_run_stacked\."


def percentile_ms(samples, p):
    return float(np.percentile(samples, p)) * 1e3 if len(samples) else None


def stacked(ctx):
    """The engine's counters of the ``stacked`` route over the window."""
    return ctx.stats.get("counters", {}).get("stacked")


def stacked_batches(ctx) -> int:
    return ctx.stats.get("routes", {}).get("stacked", 0)


def query_blocks(ctx) -> int:
    """Kernel query blocks over the window: each stacked micro-batch of
    at most ``slot_size`` queries runs in blocks of
    ``kernel_block_queries``."""
    slot, bq = ctx.config["slot_size"], ctx.config["kernel_block_queries"]
    return stacked_batches(ctx) * -(-slot // bq)


def offered_steps(ctx) -> int:
    """Pass-B steps over the segments' built tiles: each query block
    visits every built tile once.  The tree's pad leaves and the
    launch's common-grid pad tiles, which the kernel force-skips, are
    left out.  ``None`` when a compaction changed the tiles during the
    window (the layout is read once, after it)."""
    if ctx.layout["compactions"]:
        return None
    return query_blocks(ctx) * ctx.layout["tiles"]


def engine_call_ms(ctx):
    """Median host time of one engine batch (the engine's own clock,
    which ends in ``np.asarray`` and so waits for the device)."""
    if not ctx.stats.get("batches"):
        return None
    return float(ctx.stats["latency_p50_ms"])


def tiles_skipped_pct(ctx):
    """Built tiles the pruning bounds skipped over built tiles offered,
    in the stacked sweep's main pass.  ``leaves_scanned`` counts the
    steps that ran the scoring matmul, which pad tiles never do."""
    c, offered = stacked(ctx), offered_steps(ctx)
    if not c or not offered:
        return None
    return 100.0 * (offered - c["leaves_scanned"]) / offered


def kernel_seconds(ctx, pattern: str = STACKED_KERNEL):
    """Device seconds of the ops matching ``pattern`` in the window."""
    if not ctx.trace:
        return None
    return trace_reduce.matching_seconds(ctx.trace, pattern) or None


def sweep_kernel_ms(ctx):
    """Device time of the stacked sweep kernel per stacked micro-batch."""
    t, n = kernel_seconds(ctx), stacked_batches(ctx)
    if not t or not n:
        return None
    return t * 1e3 / n


def sweep_roofline_pct(ctx):
    """Least time of both passes' steps (``kernel_cost``) over the
    kernel's device time."""
    t, c, steps = kernel_seconds(ctx), stacked(ctx), offered_steps(ctx)
    if not t or not c or not steps:
        return None
    cfg = ctx.config
    least, _ = ctx.cost.least_seconds(
        scanned=c["leaves_scanned"], steps=steps,
        probe_steps=(query_blocks(ctx) * ctx.layout["segments"]
                     * cfg["probe_tiles"]),
        n0=cfg["n0"], d=cfg["d"], bq=cfg["kernel_block_queries"],
        peak=ctx.peak)
    return 100.0 * least / t


def idle_in_flush_pct(ctx):
    """Share of the time inside the benchmark's ``bench.flush`` spans in
    which no operation ran on the device."""
    t = ctx.trace
    if not t or "bench.flush" not in t["idle_share_in"]:
        return None
    return 100.0 * t["idle_share_in"]["bench.flush"]
