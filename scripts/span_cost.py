"""Host cost of one ``repro.runtime.spans.span``, with the profiler off
and with a profiler session on (the benchmark's options: host tracer
level 1, no Python tracer).

    PYTHONPATH=src python scripts/span_cost.py [--n 200000]

Prints one JSON line: microseconds per span over an empty loop, for a
bare span, a span with an attribute, and ``count``.  The profiler's
trace goes to ``.bench_cache/span_cost`` in the checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import time
from pathlib import Path

import jax

from repro.runtime import spans

TRACE_DIR = Path(__file__).resolve().parents[1] / ".bench_cache" / "span_cost"


def per_call_us(body, n: int) -> float:
    """Best of three: microseconds per call of ``body`` over an empty
    loop's."""
    def loop(f):
        t0 = time.perf_counter()
        for _ in range(n):
            f()
        return time.perf_counter() - t0

    best = min(loop(body) - loop(lambda: None) for _ in range(3))
    return best / n * 1e6


def bare():
    with spans.span("p2h.cost"):
        pass


def with_attr():
    with spans.span("p2h.cost.attr", batch=7):
        pass


def counter():
    spans.count("cost")


def measure(n: int) -> dict:
    out = {name: per_call_us(f, n) for name, f in
           (("span_us", bare), ("span_attr_us", with_attr),
            ("count_us", counter))}
    spans.reset()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    args = ap.parse_args(argv)
    result = {"device": jax.devices()[0].device_kind, "n": args.n,
              "off": measure(args.n)}
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with contextlib.ExitStack() as stack:
        stack.callback(jax.profiler.stop_trace)
        result["on"] = measure(args.n)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
