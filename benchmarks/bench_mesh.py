"""Multi-device serving-mesh benchmark: the stacked sweep + lambda
exchange sharded across 1 / 2 / 4 devices.

Device count is a process-level property (``XLA_FLAGS=--xla_force_host_
platform_device_count`` must be set before the first jax import), so
the driver forks one child per device count; each child builds the same
multi-segment sharded workload, fences the mesh placement **bit-exact**
against the single-device launch on its own snapshot (a bench that is
not exact has no speedup to report), then times the stacked cross-shard
query path and emits one JSON line the parent aggregates into
``BENCH_mesh.json``:

  * ``devices_{1,2,4}.qps / p50_ms / p99_ms`` -- the scaling curve;
  * ``devices_*.exact`` -- the per-child parity fence result;
  * ``qps_monotone`` -- whether qps is non-decreasing in device count
    (the simulated-host curve CI watches; real accelerator meshes are
    the production claim).

Every child runs on the CPU (``JAX_PLATFORMS=cpu``, forced host
devices): the numbers are a CPU simulation, labelled ``platform: cpu``,
and a child never contends for an accelerator the parent may hold.  The
chip's mesh path is ``python chip_smoke.py --chips 4``.

Run:

    PYTHONPATH=src python benchmarks/bench_mesh.py
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_RESULT_TAG = "MESH_RESULT "
_DEVICE_COUNTS = (1, 2, 4)


def _child(devices: int, smoke: bool) -> None:
    """Runs inside the forked process (device count already forced)."""
    import numpy as np

    import jax

    assert jax.device_count() >= devices, (jax.device_count(), devices)
    from repro.core.balltree import normalize_query
    from repro.launch.mesh import make_serving_mesh
    from repro.stream.compaction import CompactionPolicy
    from repro.stream.sharded import ShardedMutableP2HIndex

    dim, k = 16, 10
    n = 6000 if smoke else 12000
    nq = 16
    iters = 12 if smoke else 50
    rng = np.random.default_rng(0)
    idx = ShardedMutableP2HIndex.from_data(
        rng.normal(size=(n, dim)).astype(np.float32), 2, n0=64,
        policy=CompactionPolicy(delta_capacity=128, max_segments=16))
    idx.compact(force=True)
    # widen the segment fan-out (the sharded axis) with auto-sealed
    # batches, leaving a small live delta tail -- the serving-shaped
    # mix the mesh shards; below ~8 segments of ~1k rows the launch is
    # host-overhead-bound and the simulated curve measures nothing
    for _ in range(8):
        idx.insert_batch(
            rng.normal(size=(n // 8, dim)).astype(np.float32))
    qn = normalize_query(
        rng.normal(size=(nq, dim + 1))).astype(np.float32)

    mesh = make_serving_mesh(devices) if devices > 1 else None
    if mesh is not None:
        idx.set_mesh(mesh)
    snap = idx.snapshot()

    # exactness fence before any timing: the mesh placement must return
    # the single-device launch's answer bit-for-bit on this snapshot
    import dataclasses

    base = dataclasses.replace(snap, mesh=None)
    bd0, bi0 = base.query(qn, k, method="stacked")
    bd1, bi1 = snap.query(qn, k, method="stacked")
    exact = bool(np.array_equal(np.asarray(bd0), np.asarray(bd1))
                 and np.array_equal(np.asarray(bi0), np.asarray(bi1)))

    for _ in range(3):  # warm the jit cache out of the timed loop
        snap.query(qn, k, method="stacked")
    lat = []
    t0 = time.perf_counter()
    for _ in range(iters):
        t = time.perf_counter()
        snap.query(qn, k, method="stacked")
        lat.append(time.perf_counter() - t)
    total = time.perf_counter() - t0
    lat.sort()

    def pct(p):
        return lat[min(len(lat) - 1,
                       int(round(p / 100 * (len(lat) - 1))))] * 1e3

    idx.close()
    print(_RESULT_TAG + json.dumps({
        "platform": jax.devices()[0].platform,
        "devices": devices,
        "exact": exact,
        "qps": nq * iters / total,
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "fanout": len(snap.segments),
        "live": int(snap.live_count),
    }), flush=True)


def _spawn(devices: int, smoke: bool) -> dict:
    env = dict(os.environ)
    # single-threaded per-device compute: forced host devices share one
    # machine, so without this the 1-device baseline already consumes
    # every core and the curve only measures collective overhead.  With
    # it, device-parallelism is the only parallelism -- the honest
    # simulated-scaling methodology (and the same flag every child
    # gets, so the comparison is like-for-like).
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices} "
        "--xla_cpu_multi_thread_eigen=false")
    env["OPENBLAS_NUM_THREADS"] = "1"
    env["JAX_PLATFORMS"] = "cpu"  # simulated devices; a chip is one process
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    extra = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + (os.pathsep + extra if extra else "")
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--devices", str(devices)] + (["--smoke"] if smoke else [])
    res = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=1200)
    if res.returncode != 0:
        raise RuntimeError(
            f"bench_mesh child (devices={devices}) failed:\n"
            + res.stderr[-4000:])
    for line in reversed(res.stdout.splitlines()):
        if line.startswith(_RESULT_TAG):
            return json.loads(line[len(_RESULT_TAG):])
    raise RuntimeError(f"bench_mesh child (devices={devices}) emitted "
                       "no result line")


def run_mesh(smoke: bool = False) -> dict:
    out: dict = {"device_counts": list(_DEVICE_COUNTS),
                 "platform": "cpu (forced host devices: a simulation)"}
    qps = []
    for devices in _DEVICE_COUNTS:
        r = _spawn(devices, smoke)
        assert r["exact"], \
            f"mesh placement diverged at devices={devices}"
        out[f"devices_{devices}"] = r
        qps.append(r["qps"])
    out["qps_monotone"] = bool(
        all(b >= a * 0.95 for a, b in zip(qps, qps[1:])))
    return out


def run(csv, *, smoke: bool = False) -> dict:
    """benchmarks.run registry entry point; the returned dict becomes
    ``BENCH_mesh.json``."""
    res = run_mesh(smoke=smoke)
    csv("mesh,cpu_simulation,1,,,,")
    csv("mesh,devices,qps,p50_ms,p99_ms,fanout,exact")
    for devices in _DEVICE_COUNTS:
        r = res[f"devices_{devices}"]
        csv(f"mesh,{devices},{r['qps']:.1f},{r['p50_ms']:.3f},"
            f"{r['p99_ms']:.3f},{r['fanout']},{int(r['exact'])}")
    csv(f"mesh,qps_monotone,{int(res['qps_monotone'])},,,,")
    return res


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        _child(args.devices, args.smoke)
        return
    res = run_mesh(smoke=args.smoke)
    print(json.dumps(res, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
