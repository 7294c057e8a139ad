"""Benchmark driver: one module per paper table/figure + the roofline
table from the dry-run artifacts.  Prints CSV lines; ``python -m
benchmarks.run`` is the bench_output.txt entry point.

Lanes whose ``run(csv)`` returns a result dict additionally get it
serialized to ``BENCH_<lane>.json`` next to the CSV output (``--out-dir``,
default CWD) -- the machine-readable perf trajectory successive PRs
compare against (today: ``BENCH_serve.json`` with qps / p50 / p99 /
tile-skip / probe-overhead numbers, ``BENCH_stream_sharded.json`` with
the sharded equivalents, ``BENCH_durability.json`` with WAL replay
throughput / recovery latency / the zero-invariant loss counters, and
``BENCH_mesh.json`` with the 1/2/4-device qps/p50/p99 scaling curve, and
``BENCH_resilience.json`` with the read-path chaos fences: no-fault
bit-exactness, degraded-answer oracles, breaker cycles, shed counters).
``--only serve,stream_sharded,durability,mesh,resilience --smoke`` is the
CI bench-smoke entry point: tiny registered configs, same JSON schema,
validated by ``tools/check_bench_json.py``.
"""
from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time


def _jsonify(obj):
    """Best-effort conversion of bench results (numpy scalars/arrays,
    tuples) into plain JSON types."""
    import numpy as np

    if isinstance(obj, dict):
        return {str(k): _jsonify(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonify(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return _jsonify(obj.tolist())
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        obj = float(obj)  # fall through to the NaN/inf check below
    if isinstance(obj, float) and (obj != obj or obj in (np.inf, -np.inf)):
        return None  # NaN/inf have no RFC 8259 spelling -> null
    return obj


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated lane names (e.g. "
                         "'serve,stream_sharded'); default: all lanes")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny registered configs (CI bench-smoke lane); "
                         "only lanes that support it are shrunk")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_<lane>.json files are written")
    args = ap.parse_args(argv)

    from repro.launch.platform import use_compile_cache
    use_compile_cache()

    from benchmarks import (bench_ablations, bench_distributed,
                            bench_durability, bench_indexing, bench_kernel,
                            bench_mesh, bench_query, bench_resilience,
                            bench_serve, bench_stream, bench_stream_sharded)

    t0 = time.time()
    emitted = []

    def csv(line: str):
        emitted.append(line)
        print(line, flush=True)

    mods = [
        ("Table III (indexing overhead)", "indexing", bench_indexing),
        ("Figs 5/6 (query time vs recall, k)", "query", bench_query),
        ("Figs 7/8/10/11 (+Thm 5) ablations", "ablations", bench_ablations),
        ("Kernel path", "kernel", bench_kernel),
        ("Distributed lambda exchange", "distributed", bench_distributed),
        ("Serving engine (batching + lambda cache)", "serve", bench_serve),
        ("Streaming index (insert/delete/compaction)", "stream",
         bench_stream),
        ("Sharded streaming index (routed writes, two-round exchange)",
         "stream_sharded", bench_stream_sharded),
        ("Durability (WAL kill-and-recover chaos)", "durability",
         bench_durability),
        ("Multi-device serving mesh (sharded stacked sweep)", "mesh",
         bench_mesh),
        ("Serving resilience (read-path chaos)", "resilience",
         bench_resilience),
    ]
    only = (None if args.only is None
            else {s.strip() for s in args.only.split(",") if s.strip()})
    if only is not None:
        unknown = only - {lane for _, lane, _ in mods}
        if unknown:  # a typo must not look like a clean (empty) pass
            ap.error(f"unknown lane(s) {sorted(unknown)}; known: "
                     f"{sorted(lane for _, lane, _ in mods)} "
                     "(roofline runs only in the full, un-filtered mode)")
    os.makedirs(args.out_dir, exist_ok=True)
    for title, lane, mod in mods:
        if only is not None and lane not in only:
            continue
        print(f"# === {title} ===", flush=True)
        try:
            kw = ({"smoke": True} if args.smoke and "smoke"
                  in inspect.signature(mod.run).parameters else {})
            res = mod.run(csv, **kw)
        except Exception as e:  # keep the suite going; record the failure
            csv(f"ERROR,{mod.__name__},{type(e).__name__}: {e}")
            continue
        if isinstance(res, dict):  # machine-readable perf trajectory
            path = os.path.join(args.out_dir, f"BENCH_{lane}.json")
            with open(path, "w") as f:
                json.dump(_jsonify(res), f, indent=1, sort_keys=True)
            print(f"# wrote {path}", flush=True)
    if only is None:
        print("# === Roofline (from dry-run artifacts) ===", flush=True)
        try:
            from benchmarks import roofline
            roofline.run(csv)
        except Exception as e:
            csv(f"ERROR,roofline,{type(e).__name__}: {e}")
    print(f"# done in {time.time()-t0:.1f}s; {len(emitted)} rows")
    if any(r.startswith("ERROR") for r in emitted):
        sys.exit(1)


if __name__ == "__main__":
    main()
