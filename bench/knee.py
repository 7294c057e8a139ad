"""Knee sweep of an open-loop cell: one index, several offered rates.

    python3 bench/knee.py --workload music100.serve --seed <n> \
        --rates 40,80,120 --seconds 8

First times full slot batches back to back (the service time of one
micro-batch), then offers each rate for ``--seconds`` on fresh
hyperplanes and prints one JSON line per rate: achieved rate, latency
p50 / p95 from the due time, the largest backlog, and the mean lateness
of the first and last third of the arrivals (a growing backlog shows as
a last third far later than the first).  The knee is the highest rate
with no growing backlog; a cell runs at a fixed rate below it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", help="offered rates, per second")
    ap.add_argument("--fractions",
                    help="offered rates as fractions of the measured "
                         "back-to-back capacity")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    _, _, config, mix = harness.load_cell(args.workload)
    import jax

    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    slot, k = config["slot_size"], mix["k"]
    fr = [float(f) for f in (args.fractions or "").split(",") if f]
    rates = [float(r) for r in (args.rates or "").split(",") if r]
    # enough hyperplanes for the largest rate a fraction can ask for
    n_all = 64 * slot + int((sum(rates) + 2000 * len(fr)) * args.seconds)
    data = harness.load_named("generators", config["generator"]).make(
        config, args.seed, n_queries=n_all)
    t0 = time.perf_counter()
    system = harness.build_system(config, data, args.seed, jax.devices())
    print(f"index_s {time.perf_counter() - t0:.1f}", flush=True)
    qs = data.queries
    for b in range(0, 16 * slot, slot):  # warm-up
        system.serve(qs[b:b + slot], k)
    times = []
    for b in range(16 * slot, 64 * slot, slot):
        t = time.perf_counter()
        system.serve(qs[b:b + slot], k)
        times.append(time.perf_counter() - t)
    t_batch = float(np.median(times))
    print(json.dumps({"batch_ms_p50": t_batch * 1e3,
                      "capacity_per_s": slot / t_batch}), flush=True)
    rates += [f * slot / t_batch for f in fr]
    start = 64 * slot
    for rate in rates:
        n = int(rate * args.seconds)
        if start + n > len(qs):
            print(json.dumps({"rate_per_s": rate, "skipped": "out of "
                              "hyperplanes"}), flush=True)
            continue
        log = harness.Log(data)
        view = types.SimpleNamespace(queries=qs[start:start + n])
        start += n
        drv = harness.load_loop(mix).Driver(
            system, dict(mix, rate_per_s=rate, warm_queries=0), view, log,
            harness.Spans(), slot=slot, seed=args.seed)
        info = drv.window(args.seconds)
        lat = np.asarray(log.latency_s) * 1e3
        late = np.asarray(log.lateness_s) * 1e3
        third = max(1, len(late) // 3)
        print(json.dumps({
            "rate_per_s": rate, "queries": n,
            "achieved_per_s": n / info["window_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "backlog_max": info["backlog_max"],
            "lateness_first_third_ms": float(late[:third].mean()),
            "lateness_last_third_ms": float(late[-third:].mean()),
            "failed": log.failed_queries}), flush=True)
    system.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
