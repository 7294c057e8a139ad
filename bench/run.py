"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix.
The run makes its data from ``--seed`` on the device, builds the index
through the program's public API, warms the cell's shapes, then drives
the mix's window for ``--seconds`` (the loop fixes its work from it).
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and reports the per-layer
metrics.  Either way every answer is then checked
against the configuration's plain reference by the configuration's
check (``bench/checks/<check>.py``).

Output: diagnostic lines, then the comparison's numbers with their
limits as the last lines of standard error, and as the last line of
standard output one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``).  A run that finds no TPU, fewer chips than the
cell asks for, or no program under ``src/`` exits non-zero and prints no
result.  JAX's compilation cache lives at ``.bench_cache/jax`` in the
checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import kernel_cost  # noqa: E402
import trace_reduce  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a metric reader (``bench/metrics/<name>.py``) may read."""

    config: dict
    peak: dict
    cost: object
    setup_s: float
    window_s: float
    latency_s: list
    write_s: list
    answered: int
    stats: dict
    layout: dict
    spans: dict
    trace: dict | None


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def read_metrics(metrics, workload, ctx) -> dict:
    out = {}
    for m in metrics:
        if not applies(m, workload):
            continue
        value = harness.load_named("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


def run_cell(bench, entry, config, mix, *, seed: int, seconds: float,
             trace: bool, peak: dict, devices, t0: float,
             trace_dir: Path | None = None,
             system_factory=harness.build_system, say=print) -> dict:
    """One run of one cell; returns the result object (last line).
    ``system_factory(config, data, seed, devices)`` builds the system
    under test (the configuration's ``system`` module)."""
    import jax

    reference = harness.load_reference(config)
    check = harness.load_check(config)
    t_init = time.perf_counter()
    data = harness.make_data(config, mix, seed, seconds)
    t_data = time.perf_counter()
    system = system_factory(config, data, seed, devices)
    t_index = time.perf_counter()
    log, spans = harness.Log(data), harness.Spans()
    driver = harness.load_loop(mix).Driver(
        system, mix, data, log, spans, slot=config["slot_size"], seed=seed)
    driver.warm()
    system.reset_stats()
    compiles, gc_watch = harness.CompileCounter(), harness.GcWatch()
    # what set-up left behind is long-lived: moved out of the
    # collector's reach, a full collection in the window scans only
    # what the window allocates, not the harness's data and logs
    gc.collect()
    gc.freeze()
    t_open = time.perf_counter()
    setup_s = t_open - t0
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # the benchmark's own spans
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles.active = gc_watch.active = True
    jax.config.update("jax_log_compiles", True)  # names what compiles
    with spans(trace_reduce.WINDOW_SPAN):
        info = driver.window(seconds)
    jax.config.update("jax_log_compiles", False)
    compiles.active = gc_watch.active = False
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    stats, layout = system.stats(), system.layout()
    build_s = getattr(system, "build_s", {})
    mem_peak = memory_peak(devices)
    system.close()
    del driver, system
    gc.collect()

    lat = np.asarray(log.lateness_s) * 1e3
    say(f"setup_s split: init {t_init - t0:.3f} data {t_data - t_init:.3f}"
        f" index {t_index - t_data:.3f} warm {t_open - t_index:.3f}"
        f" (index: {json.dumps(build_s)})")
    say(f"window: {json.dumps(info)} queries {log.queries} "
        f"writes {log.writes}")
    say(f"compiles_in_window {compiles.count}")
    say(f"gc_in_window full {gc_watch.full} longest_ms "
        f"{gc_watch.longest_s * 1e3:.3f}")
    if log.latency_s:
        say("latency_ms p50/p90/p95/p99/max " + " ".join(
            f"{v:.3f}" for v in np.percentile(
                np.asarray(log.latency_s) * 1e3, [50, 90, 95, 99, 100])))
    if len(lat):
        say(f"generator_lateness_ms p50 {np.percentile(lat, 50):.3f} "
            f"p95 {np.percentile(lat, 95):.3f} max {lat.max():.3f}")
    say(f"layout {json.dumps(layout)}")
    say(f"peak_bytes_in_use {mem_peak}")
    say(f"routes {json.dumps(stats.get('routes', {}))} "
        f"warm_failures {stats.get('warm_failures')}")

    t_ref = time.perf_counter()
    readings = check.compare(log, reference, mix["k"])
    correct, checks = check.verdict(readings, config["limits"])
    correct = correct and log.bad_gids == 0
    say(f"reference_s {time.perf_counter() - t_ref:.3f} answers "
        f"{readings['answers']}")

    reduced = None
    if trace:
        path = trace_reduce.find_xplane(str(trace_dir))
        if path:
            reduced = trace_reduce.reduce(trace_reduce.load(path))
        say(f"trace {path} device_ops "
            f"{json.dumps(reduced['device_ops']) if reduced else None}")
    ctx = Context(config=config, peak=peak, cost=kernel_cost,
                  setup_s=setup_s, window_s=info["window_s"],
                  latency_s=log.latency_s, write_s=log.write_s,
                  answered=log.queries - log.failed_queries, stats=stats,
                  layout=layout, spans=spans.seconds, trace=reduced)
    metrics = read_metrics(bench["per_layer"] if trace
                           else bench["end_to_end"], entry["name"], ctx)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(correct),
              "attempted": log.queries + log.writes,
              "failed": log.failed_queries + log.failed_writes,
              "metrics": metrics, "device": device}
    if trace and reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    def fail(msg):
        print(f"bench: {msg}; no result", file=sys.stderr)
        return 1

    if not (harness.SRC / "repro").is_dir():
        return fail(f"no program under {harness.SRC}")
    bench, entry, config, mix = harness.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    print(f"device: platform={devices[0].platform} "
          f"kind={devices[0].device_kind} count={len(devices)}", flush=True)
    if devices[0].platform != "tpu":
        return fail("no TPU found")
    if len(devices) < entry["chips"]:
        return fail(f"cell needs {entry['chips']} chips, found "
                    f"{len(devices)}")
    peaks = harness.load_json(BENCH / "peaks.json")["devices"]
    if devices[0].device_kind not in peaks:
        return fail(f"device kind {devices[0].device_kind!r} has no peaks "
                    "in bench/peaks.json")
    result = run_cell(
        bench, entry, config, mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), peak=peaks[devices[0].device_kind],
        devices=devices[:entry["chips"]], t0=T0,
        trace_dir=CACHE / "trace" / args.workload,
        say=lambda *a: print(*a, flush=True))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
