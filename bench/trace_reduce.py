"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers.

* device events: the ``XLA Ops`` line of every ``/device:TPU:<n>`` plane
  (one event per executed op; the module and step lines would count the
  same time twice);
* host spans: the benchmark's own ``jax.profiler.TraceAnnotation`` spans
  (names starting ``bench.``) from the ``/host:`` planes, on the same
  clock;
* ``busy``: the union of a device's op intervals inside the window,
  averaged over the devices that ran anything;
* ``op_seconds``: device time per op name inside the window, and
  ``op_text`` the op's name with the string stats of its first event
  (the text a metric reader matches its kernel's pattern against);
* idle time inside a span name, and the device's idle time split by
  the ``bench.`` span the host was in.

Every device number is averaged over the devices that ran anything.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW_SPAN = "bench.window"


def find_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _text(ev) -> str:
    parts = [ev.name]
    for st in ev.stats:
        val = st[1] if isinstance(st, tuple) else getattr(st, "value", "")
        if isinstance(val, str):
            parts.append(val)
    return " ".join(parts)


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start, end)]}, "text": {name: text},
    "spans": [(name, start, end)]}``, times in ns."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans, text = {}, [], {}
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            evs = []
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    evs.append((ev.name, ev.start_ns, ev.end_ns))
                    if ev.name not in text:
                        text[ev.name] = _text(ev)
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    return {"devices": devices, "text": text, "spans": spans}


def matching_seconds(reduced: dict, pattern: str) -> float:
    """Device seconds of the ops whose text matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(s for name, s in reduced["op_seconds"].items()
               if rx.search(reduced["op_text"][name]))


def merge(intervals, lo, hi):
    """Sorted, disjoint union of ``(start, end)`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def overlap(merged, starts, lo, hi) -> float:
    """Length of ``merged`` (sorted, disjoint; ``starts`` its starts)
    inside [lo, hi]."""
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    total = 0.0
    for s, e in merged[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def attribute(spans, starts, lo, hi, into: dict) -> None:
    """Add the gap [lo, hi] to ``into`` by the host span it overlaps
    (``spans`` sorted by start, ``starts`` their starts; the benchmark's
    spans inside the window do not nest); the rest is ``(no span)``."""
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    while i < len(spans) and spans[i][1] < hi:
        name, s, e = spans[i]
        part = min(e, hi) - max(s, lo)
        if part > 0:
            into[name] = into.get(name, 0.0) + part
            covered += part
        i += 1
    if hi - lo > covered:
        into["(no span)"] = into.get("(no span)", 0.0) + (hi - lo - covered)


def reduce(trace: dict, *, top: int = 10) -> dict | None:
    """Busy, per-op and idle numbers over the window span; ``None`` when
    no device ran anything in it."""
    spans = trace["spans"]
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    inner = sorted((sp for sp in spans if sp[0] != WINDOW_SPAN),
                   key=lambda sp: sp[1])
    inner_starts = [sp[1] for sp in inner]
    if not win or not trace["devices"]:
        return None
    lo, hi = win[0]
    busy, ops = [], {}
    idle_in: dict[str, list] = {}
    gaps: dict[str, float] = {}
    for evs in trace["devices"].values():
        merged = merge([(s, e) for _, s, e in evs], lo, hi)
        starts = [m[0] for m in merged]
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in evs:
            if s < hi and e > lo:
                ops[name] = ops.get(name, 0.0) + (min(e, hi) - max(s, lo))
        for name, s, e in inner:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                acc = idle_in.setdefault(name, [0.0, 0.0])
                acc[0] += (e - s) - overlap(merged, starts, s, e)
                acc[1] += e - s
        edges = [lo] + [x for m in merged for x in m] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                attribute(inner, inner_starts, s, e, gaps)
    n = len(trace["devices"])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n * 1e-9,
        "op_seconds": {k: v / n * 1e-9 for k, v in ops.items()},
        "op_text": {k: trace.get("text", {}).get(k, k) for k in ops},
        "idle_share_in": {k: v[0] / v[1] for k, v in idle_in.items()
                          if v[1] > 0},
        "device_ops": [[k, v / n * 1e-9] for k, v in top_ops],
        "idle_gaps": [[k, v / n * 1e-9] for k, v in top_gaps],
    }
