"""The program's own host spans and counters (``p2h.*``, recorded by
``repro.runtime.spans``) as per-layer numbers.

Two sources:

* the engine's ``stats()`` at the end of the window: ``spans`` (per
  name: ``count``, ``total_s``, ``self_s``, ``p50_ms``, ``p95_ms``) and
  ``span_counters``;
* the run's profiler trace, where the spans sit on the host plane on
  the device ops' clock, next to the runtime's own host-to-device copy
  events: :func:`upload_intervals` follows each delta upload to the end
  of its copy, and :func:`idle_by_span` gives every interval in which
  the device ran nothing to the innermost span the host was in.

Every reader returns ``None`` when its span never ran, so a program
without these spans reads nothing and raises nothing.
"""
from __future__ import annotations

import bisect
import functools
import heapq
import re
import statistics
from pathlib import Path

import trace_reduce

BENCH = Path(__file__).resolve().parent
#: where ``run.py`` writes its traces, one directory per cell, cleared
#: before each traced window: the newest file is the last run's
TRACE_ROOT = BENCH.parent / ".bench_cache" / "trace"
#: host spans kept from a trace: the program's and the benchmark's
PREFIXES = ("p2h.", "bench.")
#: the TPU runtime's host events of a host-to-device copy: the re-layout
#: of the host buffer into the device's tiles (``XlaLinearize``, on
#: PJRT's threads after the call that starts the copy has returned), the
#: hand-off to the chip and the transfer's completion events
TRANSFER = frozenset({
    "XlaLinearize", "H2D Dispatch", "tpu::System::TransferToDevice",
    "tpu::System::TransferToDevice=>IssueEvent",
    "tpu::System::TransferToDevice=>IssueEvent=>Done"})
UPLOAD = "p2h.delta.upload"
NO_SPAN = "(no span)"


# ----------------------------------------------------------------------
# from the engine's stats()
# ----------------------------------------------------------------------
def span(ctx, name: str) -> dict | None:
    return ctx.stats.get("spans", {}).get(name)


def counter(ctx, name: str):
    return ctx.stats.get("span_counters", {}).get(name)


def host_self_ms(ctx):
    """Mean host time of a micro-batch outside the wait for the device:
    ``p2h.batch`` less ``p2h.device_wait``, per batch.  The runtime's
    copy work on its own threads (the delta block's re-layout and
    transfer) is not in it: the device waits for that inside
    ``p2h.device_wait``."""
    b, w = span(ctx, "p2h.batch"), span(ctx, "p2h.device_wait")
    if not b or not w:
        return None
    return (b["total_s"] - w["total_s"]) * 1e3 / b["count"]


def ids_rewrite_ms(ctx):
    """Ids-plane rewrite time on the query path per engine batch."""
    s, batches = span(ctx, "p2h.stacked.ids_rewrite"), ctx.stats.get(
        "batches", 0)
    if not s or not batches:
        return None
    return s["total_s"] * 1e3 / batches


def publish_share_pct(ctx):
    """Share of the write calls' host time spent publishing snapshots."""
    pub = span(ctx, "p2h.publish")
    writes = [span(ctx, n) for n in ("p2h.write.delete",
                                     "p2h.write.insert")]
    total = sum(w["total_s"] for w in writes if w)
    if not pub or total <= 0:
        return None
    return 100.0 * pub["total_s"] / total


# ----------------------------------------------------------------------
# from the trace
# ----------------------------------------------------------------------
@functools.lru_cache(maxsize=2)
def load(path: str) -> dict:
    """``{"devices": {plane: [(start, end)]}, "spans": [(name, start,
    end)], "transfers": [(start, end)]}`` in ns: the ``XLA Ops`` of every
    TPU plane, the host spans named with :data:`PREFIXES` (an
    annotation's ``#attr=...#`` suffix cut off) and the host events of
    host-to-device copies (:data:`TRANSFER`)."""
    from jax.profiler import ProfileData

    devices, spans, transfers = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = [(ev.start_ns, ev.end_ns) for line in plane.lines
                   if line.name == "XLA Ops" for ev in line.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIXES):
                        spans.append((ev.name.split("#", 1)[0],
                                      ev.start_ns, ev.end_ns))
                    elif ev.name in TRANSFER:
                        transfers.append((ev.start_ns, ev.end_ns))
    return {"devices": devices, "spans": spans, "transfers": transfers}


def window(trace: dict):
    """The ``bench.window`` span's ``(start, end)``, or None."""
    return next(((s, e) for n, s, e in trace["spans"]
                 if n == trace_reduce.WINDOW_SPAN), None)


def upload_intervals(trace: dict) -> list | None:
    """Each ``p2h.delta.upload`` span in the window, from the call that
    starts the copy to the end of the last host-to-device transfer event
    that starts after it in its micro-batch (up to the batch's next
    upload): the block's re-layout and transfer, which run on after the
    call returns.  The batch's other copies (queries, caps) are a few KB
    and follow it.  ``None`` without an upload or a transfer event."""
    win = window(trace)
    ups = sorted((s, e) for n, s, e in trace["spans"]
                 if n == UPLOAD and win and win[0] <= s < win[1])
    transfers = sorted(trace.get("transfers", ()))
    if not ups or not transfers:
        return None
    batches = sorted((s, e) for n, s, e in trace["spans"]
                     if n == "p2h.batch")
    batch_starts = [s for s, _ in batches]
    starts = [s for s, _ in transfers]
    out = []
    for i, (s, e) in enumerate(ups):
        j = bisect.bisect_right(batch_starts, s) - 1
        limit = (batches[j][1] if j >= 0 and batches[j][1] >= e
                 else win[1])
        if i + 1 < len(ups):
            limit = min(limit, ups[i + 1][0])
        lo, hi = (bisect.bisect_left(starts, s),
                  bisect.bisect_left(starts, limit))
        out.append((s, max([e] + [t[1] for t in transfers[lo:hi]])))
    return out


def idle_by_span(trace: dict, within: str | None = None) -> dict | None:
    """Device-idle seconds inside the window, by the innermost host span
    that covers them (of those open, the latest to start; the window's
    own span left out), the rest under :data:`NO_SPAN`; averaged over
    the devices that ran anything.  ``within`` counts only the idle
    time inside the spans of that name.  ``None`` without a window or a
    device."""
    win = [(s, e) for n, s, e in trace["spans"]
           if n == trace_reduce.WINDOW_SPAN]
    if not win or not trace["devices"]:
        return None
    lo, hi = win[0]
    inner = sorted((s, e, n) for n, s, e in trace["spans"]
                   if n != trace_reduce.WINDOW_SPAN and e > lo and s < hi)
    region = None
    if within is not None:
        region = trace_reduce.merge(
            [(s, e) for s, e, n in inner if n == within], lo, hi)
        region_starts = [r[0] for r in region]
    edges = sorted({lo, hi} | {min(max(t, lo), hi)
                               for s, e, _ in inner for t in (s, e)})
    out: dict[str, float] = {}
    for evs in trace["devices"].values():
        busy = trace_reduce.merge(evs, lo, hi)
        busy_starts = [b[0] for b in busy]
        open_, i = [], 0  # heap of (-start, end, name): innermost on top
        for a, b in zip(edges, edges[1:]):
            while i < len(inner) and inner[i][0] <= a:
                s, e, n = inner[i]
                heapq.heappush(open_, (-s, e, n))
                i += 1
            while open_ and open_[0][1] <= a:
                heapq.heappop(open_)
            if region is not None and trace_reduce.overlap(
                    region, region_starts, a, b) <= 0:
                continue
            idle = (b - a) - trace_reduce.overlap(busy, busy_starts, a, b)
            if idle > 0:
                name = open_[0][2] if open_ else NO_SPAN
                out[name] = out.get(name, 0.0) + idle
    n = len(trace["devices"])
    return {k: v / n * 1e-9 for k, v in sorted(out.items(),
                                                key=lambda kv: -kv[1])}


def last_trace() -> dict | None:
    path = trace_reduce.find_xplane(str(TRACE_ROOT))
    return load(path) if path else None


def _uploads(ctx) -> list | None:
    """The last traced run's upload intervals, or None (an untraced run,
    no device in the window, a program without the span)."""
    if not ctx.trace:
        return None
    trace = last_trace()
    return upload_intervals(trace) if trace else None


def delta_upload_ms(ctx):
    """Median time from the call that starts one delta block's copy to
    the end of its transfer (:func:`upload_intervals`)."""
    ups = _uploads(ctx)
    return statistics.median(e - s for s, e in ups) * 1e-6 if ups else None


def delta_upload_gbps(ctx):
    """Bytes of one delta upload (the ``delta_upload_bytes`` counter per
    ``p2h.delta.upload`` span) over its mean time to land."""
    ups, s = _uploads(ctx), span(ctx, UPLOAD)
    n = counter(ctx, "delta_upload_bytes")
    if not ups or not s or not n:
        return None
    mean_s = sum(e - b for b, e in ups) * 1e-9 / len(ups)
    return n / s["count"] / mean_s / 1e9 if mean_s > 0 else None


def _intersect(a, b):
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_in_upload_pct(ctx, within: str = "bench.flush"):
    """Share of the device-idle time inside ``within`` spans that falls
    while a delta upload is under way (:func:`upload_intervals`),
    whichever span the host is in meanwhile."""
    ups = _uploads(ctx)
    trace = last_trace() if ups else None
    win = window(trace) if trace else None
    if not ups or not win or not trace["devices"]:
        return None
    lo, hi = win
    region = trace_reduce.merge(
        [(s, e) for n, s, e in trace["spans"] if n == within], lo, hi)
    in_upload = _intersect(region, trace_reduce.merge(ups, lo, hi))
    idle = [0.0, 0.0]  # in the region, in the region while uploading
    for evs in trace["devices"].values():
        busy = trace_reduce.merge(evs, lo, hi)
        starts = [b[0] for b in busy]
        for k, part in enumerate((region, in_upload)):
            idle[k] += sum((e - s) - trace_reduce.overlap(busy, starts, s, e)
                           for s, e in part)
    return 100.0 * idle[1] / idle[0] if idle[0] > 0 else None
