"""Smoke run of the P2H serving path on a TPU.

    python chip_smoke.py [--seed S]           # one chip: phases A and B
    python chip_smoke.py --chips 4 [--seed S]  # the 4-chip serving mesh

Data has the shape of Music-100 (1,000,000 points, d = 100; a public
MIPS / P2HNNS benchmark set), generated from ``--seed`` as clustered
points (``make_p2h_dataset(kind="clustered")``), with 256 random
hyperplane queries served at k = 10 through ``P2HEngine``:

* phase A -- a frozen ``P2HIndex`` (BC-Tree, n0 = 128): the engine routes
  the batches to the ``pallas`` sweep, compiled by Mosaic;
* phase B -- a ``MutableP2HIndex`` bulk-loaded and grown by
  ``insert_batch`` to 4 sealed segments plus a live delta, then 1% of
  the points deleted: the engine routes to the ``stacked`` two-pass
  sweep (Mosaic kernel, bf16 probe);
* ``--chips 4`` runs only a sharded form of phase B: a
  ``ShardedMutableP2HIndex`` of 4 shards, shard ``s`` placed on chip
  ``s`` (``devices=``), served over the 4-chip mesh, checked bit for bit
  against the same snapshot's single-device launch, checked to keep each
  shard's planes and trees on its own chip, and checked that chip 0's
  peak memory is at most 1.5x the smallest chip's.

Every phase is checked against ``exact_search`` on the live set: the
distances must agree within the forward error bound of two f32 dot
products, and the ids must match except where two points tie within
that bound.  Set-up times printed are host build times, not benchmark
numbers.  The last line of a passing run is one JSON object naming the
device; a run without a TPU, or one that fails a check, exits non-zero
and prints no result.  The compile cache follows
``JAX_COMPILATION_CACHE_DIR`` when it is set, else ``.jax_cache/``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

N_POINTS, DIM = 1_000_000, 100  # Music-100
N_QUERIES, K, N0 = 256, 10, 128
SEG_ROWS = 240_000  # rows per sealed segment in phase B: 4 segments + delta
DELETE_FRAC = 0.01


class CheckFailed(Exception):
    pass


def require(cond, msg):
    if not cond:
        raise CheckFailed(msg)


def say(*parts):
    print(*parts, flush=True)


# ----------------------------------------------------------------------
# checks
# ----------------------------------------------------------------------
def check_answers(name, sys_d, sys_i, data, live, queries):
    """``(sys_d, sys_i)`` against ``exact_search`` over ``data[live]``.

    Distances agree within ``2 * d * 2^-24 * ||q|| * R`` per row (the
    forward error bound of one f32 dot product, once for each side;
    ``R`` bounds ``||x||``).  Ids agree except for ties: a returned id the
    oracle lacks must lie within that bound of the oracle's k-th
    distance, and an oracle id left out must lie within it of the
    returned k-th, both judged by float64 distances."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.balltree import append_ones, normalize_query
    from repro.core.exact import exact_search

    qn = normalize_query(queries)
    pts = append_ones(data[live])
    od, oi = exact_search(jnp.asarray(pts), jnp.asarray(qn), k=K)
    od = np.asarray(od.block_until_ready())
    oi = live[np.asarray(oi)]
    k = od.shape[1]
    R = float(np.sqrt((pts.astype(np.float64) ** 2).sum(axis=1)).max())
    tol = (2 * pts.shape[1] * 2.0 ** -24 * R
           * np.linalg.norm(qn.astype(np.float64), axis=1))  # (B,)
    sys_d = np.asarray(sys_d, np.float64)
    sys_i = np.asarray(sys_i, np.int64)
    err = np.abs(sys_d - od)
    require(np.isfinite(sys_d).all(), f"{name}: non-finite distances")
    require((err <= tol[:, None]).all(),
            f"{name}: distance error {err.max():.3e} over the bound")
    alive = np.zeros(len(data), bool)
    alive[live] = True
    require(((sys_i >= 0) & (sys_i < len(data))).all()
            and alive[np.clip(sys_i, 0, len(data) - 1)].all(),
            f"{name}: an id outside the live set was returned")
    require(all(len(set(r)) == k for r in sys_i.tolist()),
            f"{name}: duplicate ids in a row")

    def true_d(b, ids):
        x = append_ones(data[np.asarray(sorted(ids))]).astype(np.float64)
        return np.abs(x @ qn[b].astype(np.float64))

    identical = int((sys_i == oi).all(axis=1).sum())
    swaps = 0
    for b in np.nonzero((np.sort(sys_i, 1) != np.sort(oi, 1)).any(1))[0]:
        extra = set(sys_i[b].tolist()) - set(oi[b].tolist())
        missed = set(oi[b].tolist()) - set(sys_i[b].tolist())
        swaps += len(extra)
        require((true_d(b, extra) <= od[b, -1] + tol[b]).all()
                and (true_d(b, missed) >= sys_d[b, -1] - tol[b]).all(),
                f"{name}: row {b} differs from the oracle beyond a tie")
    out = {"rows": len(sys_i), "rows_with_identical_ids": identical,
           "tie_swaps": swaps, "max_distance_error": float(err.max()),
           "distance_bound_min": float(tol.min())}
    say(f"{name} exactness vs exact_search:", json.dumps(out))
    return out


def peak_bytes(devices):
    return [d.memory_stats().get("peak_bytes_in_use") for d in devices]


def stacked_launch_forms():
    """``(use_kernel, interpret, probe_dtype)`` of the stacked programs
    the query path compiled, read from the compile registry's recent
    signatures (fields 10, 11 and 13 of ``_call_run_stacked``'s)."""
    from repro.kernels.stacked_sweep import stacked_compile_stats

    return sorted({(s[10], s[11], s[13])
                   for s in stacked_compile_stats()["recent_misses"]})


# ----------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------
def phase_a(data, queries, seed):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.api import P2HIndex
    from repro.kernels import ops
    from repro.serve import P2HEngine

    t0 = time.perf_counter()
    index = P2HIndex.build(data, n0=N0, variant="bc", seed=seed)
    say(f"phase A set-up: P2HIndex.build host seconds "
        f"{time.perf_counter() - t0:.1f} ({index.tree.num_leaves} leaves)")
    engine = P2HEngine(index)
    sys_d, sys_i = engine.query(queries, k=K)
    st = engine.stats()
    say("phase A routes:", st["routes"], "warm_failures:",
        st["warm_failures"])
    require(set(st["routes"]) == {"pallas"},
            f"phase A routes {st['routes']}, expected only pallas")
    hlo = ops._run.lower(index.tree, jnp.zeros((8, index.tree.d)), None,
                         k=K, frac=1.0, bq=8, use_ball=True, use_cone=True,
                         use_ref=False, interpret=False).as_text()
    require("tpu_custom_call" in hlo,
            "phase A: the pallas route holds no Mosaic kernel")
    say("phase A pallas route: Mosaic kernel (tpu_custom_call), "
        "interpret=False")
    check_answers("phase A", sys_d, sys_i, data, np.arange(len(data)),
                  queries)
    return st


def build_churned(data, seed, devices=None):
    """The phase-B index: 4 sealed segments + a live delta per shard, 1%
    of the points deleted; with ``devices``, one shard placed on each.
    Returns ``(index, live gids)``."""
    import numpy as np

    from repro.stream import CompactionPolicy, MutableP2HIndex
    from repro.stream.sharded import ShardedMutableP2HIndex

    sharded = devices is not None
    shards = len(devices) if sharded else 1
    policy = CompactionPolicy(delta_capacity=SEG_ROWS // shards,
                              tombstone_frac=0.5, max_segments=8)
    t0 = time.perf_counter()
    if sharded:
        idx = ShardedMutableP2HIndex.from_data(
            data[:SEG_ROWS], num_shards=shards, n0=N0, policy=policy,
            seed=seed, devices=devices)
    else:
        idx = MutableP2HIndex.from_data(data[:SEG_ROWS], n0=N0,
                                        policy=policy, seed=seed)
    idx.insert_batch(data[SEG_ROWS:])
    rng = np.random.default_rng(seed + 1)
    victims = rng.choice(len(data), int(len(data) * DELETE_FRAC),
                         replace=False)
    for g in victims:
        require(idx.delete(int(g)), f"delete of live gid {g} failed")
    say(f"phase B set-up: bulk load + insert_batch + deletes host seconds "
        f"{time.perf_counter() - t0:.1f}")
    alive = np.ones(len(data), bool)
    alive[victims] = False
    snaps = (idx.snapshot().shards if sharded else [idx.snapshot()])
    for s, snap in enumerate(snaps):
        say(f"phase B shard {s}: {len(snap.segments)} sealed segments, "
            f"{snap.delta_live} live delta rows, "
            f"{snap.live_count} live points")
        require(len(snap.segments) >= 4 and snap.delta_live > 0,
                "phase B needs >= 4 sealed segments and a live delta")
    return idx, np.nonzero(alive)[0]


def phase_b(data, queries, seed):
    from repro.kernels.stacked_sweep import (stacked_compile_stats,
                                             warm_stacked)
    from repro.serve import P2HEngine

    idx, live = build_churned(data, seed)
    engine = P2HEngine(idx)
    sys_d, sys_i = engine.query(queries, k=K)
    st = engine.stats()
    say("phase B routes:", st["routes"])
    require(set(st["routes"]) == {"stacked"},
            f"phase B routes {st['routes']}, expected only stacked")
    forms = stacked_launch_forms()
    say("phase B stacked launches (use_kernel, interpret, probe_dtype):",
        forms)
    require(forms == [(True, False, "bf16")],
            "phase B: the stacked route must run the Mosaic kernel with "
            "its bf16 probe")
    check_answers("phase B", sys_d, sys_i, data, live, queries)
    # the warm-up path a compaction runs before it publishes, replayed on
    # the served stack: every recorded template must build
    warmed = warm_stacked(idx.snapshot().stacked_leaves())
    cst = stacked_compile_stats()
    say("phase B warm_stacked replayed", warmed, "templates;",
        "stacked_compile_stats:", cst)
    require(warmed >= 1, "phase B: warm_stacked replayed nothing")
    require(engine.stats()["warm_failures"] == 0 and
            cst["warm_failures"] == 0,
            f"warm-up failures: {cst['recent_warm_errors']}")
    return st


def phase_mesh(data, queries, seed, devices):
    import numpy as np

    from repro.core.balltree import normalize_query
    from repro.serve import P2HEngine

    idx, live = build_churned(data, seed, devices=devices)
    engine = P2HEngine(idx)
    sys_d, sys_i = engine.query(queries, k=K)
    st = engine.stats()
    say("mesh routes:", st["routes"], "mesh_devices:",
        st.get("mesh_devices"), "warm_failures:", st["warm_failures"])
    require(set(st["routes"]) == {"stacked"}
            and st.get("mesh_devices") == len(devices),
            "mesh: expected the stacked route across every device")
    require(st["warm_failures"] == 0, "mesh: warm-up failures")
    check_answers("mesh", sys_d, sys_i, data, live, queries)
    # each shard's planes and round-1 trees on its own chip
    snap = idx.snapshot()
    for s, (shard, dev) in enumerate(zip(snap.shards, devices)):
        stk = shard.stacked_leaves()
        trees = [seg.resident_tree(dev)[0] for seg in shard.segments]
        on = ({dev} == stk.pts.devices() == stk.ids.devices()
              and all(t.points.devices() == {dev} for t in trees))
        require(on, f"mesh: shard {s} has state off chip {s}")
    peaks = peak_bytes(devices)
    say("mesh peak_bytes_in_use per chip:", peaks)
    require(peaks[0] <= 1.5 * min(peaks),
            "mesh: chip 0 peaks above 1.5x the smallest chip")
    # the same snapshot, single-device launch vs the mesh launch
    qn = normalize_query(queries)
    md, mi = snap.query(qn, K, method="stacked", stacked=True)
    sd, si = dataclasses.replace(snap, mesh=None).query(
        qn, K, method="stacked", stacked=True)
    require(np.array_equal(md, sd) and np.array_equal(mi, si),
            "mesh launch differs from the single-device launch")
    say("mesh vs single-device launch on one snapshot: bit-exact")
    check_answers("mesh (direct)", md, mi, data, live, queries)
    return st


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices", file=sys.stderr)
        return 1

    from repro.data.pipeline import make_p2h_dataset
    from repro.launch.platform import platform_diagnostics, use_compile_cache

    say("compile cache:", use_compile_cache())
    say("platform_diagnostics:", platform_diagnostics())
    t0 = time.perf_counter()
    data, queries = make_p2h_dataset(N_POINTS, DIM, kind="clustered",
                                     n_queries=N_QUERIES, seed=args.seed)
    say(f"set-up: data {data.shape} + queries {queries.shape} host seconds "
        f"{time.perf_counter() - t0:.1f}")
    try:
        if args.chips == 4:
            phase_mesh(data, queries, args.seed, devices[:4])
        else:
            phase_a(data, queries, args.seed)
            say("peak_bytes_in_use after phase A:", peak_bytes(devices))
            phase_b(data, queries, args.seed)
        say("peak_bytes_in_use:", peak_bytes(devices))
    except CheckFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
