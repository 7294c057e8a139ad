"""A sharded index placed one shard per device (``devices=``): every
device-side piece of shard ``s`` -- stacked planes, the segments' sweep
views round 1 reads, the delta upload -- lives on device ``s``, the
exchange starts every shard's round 1 before reading any, and round 2
runs on the per-device stacks as they lie.  Answers are checked against
the benchmark's float64 reference over the live set.

Device-count-dependent, so the work runs once in a subprocess under
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (a module
fixture); each test reads one part of its report."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_BODY = textwrap.dedent(
    """
    import json, sys
    import jax
    import numpy as np
    assert jax.device_count() >= 4, jax.device_count()
    sys.path.insert(0, sys.argv[1])  # bench/
    import harness
    ref = harness.load_named("configs", "p2h_exact")

    import repro.stream.snapshot as snapmod
    from repro.core.balltree import normalize_query
    from repro.runtime import spans
    from repro.serve import P2HEngine
    from repro.stream import CompactionPolicy
    from repro.stream.sharded import ShardedMutableP2HIndex

    report = {}
    devs = jax.devices()[:4]
    dim, rows, k = 960, 384, 10
    rng = np.random.default_rng(7)
    centers = 4.0 * rng.normal(size=(8, dim))
    X = (centers[rng.integers(0, 8, 6000)]
         + 0.5 * rng.normal(size=(6000, dim))).astype(np.float32)
    policy = CompactionPolicy(delta_capacity=rows, tombstone_frac=0.5,
                              max_segments=8)
    spans.reset()
    idx = ShardedMutableP2HIndex.from_data(
        X[:4 * rows], 4, n0=32, policy=policy, seed=5, devices=devs)
    idx.insert_batch(X[4 * rows:])
    for g in rng.choice(6000, 60, replace=False):
        assert idx.delete(int(g))
    snap = idx.snapshot()
    report["segments"] = [len(s.segments) for s in snap.shards]
    report["delta_live"] = [int(s.delta_live) for s in snap.shards]
    report["setup_tree_uploads"] = spans.snapshot()["counters"].get(
        "tree_uploads", 0)

    seen = []
    real_delta_topk = snapmod.delta_topk

    def spy(view, q, k, *, device=None):
        out = real_delta_topk(view, q, k, device=device)
        seen.append((str(device), sorted(map(str, out[0].devices()))))
        return out

    snapmod.delta_topk = spy
    engine = P2HEngine(idx, slot_size=8, seed=1)
    Q = rng.normal(size=(16, dim + 1)).astype(np.float32)

    def serve(qs):
        tickets = [engine.submit(q, k) for q in qs]
        engine.flush()
        return [engine.result(t) for t in tickets]

    serve(Q[:8])
    engine.reset_stats()
    seen.clear()
    answers = serve(Q[8:])
    st = engine.stats()
    report["routes"] = st["routes"]
    report["counters"] = st["span_counters"]
    report["spans"] = {n: v["count"] for n, v in st["spans"].items()
                       if n.startswith("p2h.exchange")}
    report["delta_calls"] = seen
    # a warm snapshot: every delta row and gids plane is on its chip
    # already, so a batch sends its queries to the four chips and no more
    query_bytes = 4 * 8 * (dim + 1) * 4
    report["expected_upload"] = query_bytes

    # against the float64 reference over the live set
    P, G = snap.live_points()
    P = P[:, :-1]  # stored rows carry the appended 1
    zeros = np.zeros(len(P), np.int64)
    rd, rr = ref.exact_topk(P, zeros, zeros + 1, Q[8:],
                            np.zeros(8, np.int64), k)
    got_i = np.stack([np.asarray(i) for _, i in answers])
    got_d = np.stack([np.asarray(d) for d, _ in answers])
    report["ids_match"] = bool(np.array_equal(G[rr], got_i))
    report["dist_err"] = float(np.max(np.abs(got_d - rd) / rd.max()))

    # the same pin: mesh launch vs the single-device launch, and the
    # resilient exchange, bit for bit
    import dataclasses
    qn = normalize_query(Q[8:])
    md, mi = snap.query(qn, k, method="stacked", stacked=True)
    sd, si = dataclasses.replace(snap, mesh=None).query(
        qn, k, method="stacked", stacked=True)
    from repro.serve.resilience import ResilienceConfig, ShardSupervisor
    sup = ShardSupervisor(ResilienceConfig(shard_timeout_s=None))
    rd, ri = idx.query(Q[8:], k, method="stacked", resilience=sup)
    report["single_device_equal"] = bool(
        np.array_equal(md, sd) and np.array_equal(mi, si))
    report["resilient_equal"] = bool(
        np.array_equal(md, rd) and np.array_equal(mi, ri))

    # where each shard's state lives
    placed = []
    for s, (shard, dev) in enumerate(zip(snap.shards, devs)):
        stk = shard.stacked_leaves()
        trees = [seg.resident_tree(dev)[0] for seg in shard.segments]
        placed.append({
            "stack": sorted({str(d) for f in ("pts", "ids", "rx",
                                              "leaf_centers")
                             for d in getattr(stk, f).devices()}),
            "lane": sorted(map(str, stk.padded_pts().devices())),
            "trees": sorted({str(d) for t in trees
                             for d in t.points.devices()}),
            "device": str(dev)})
    report["placed"] = placed

    # 16 appended rows a shard (gids are consecutive, the router hashes
    # them mod 4): each chip is sent those rows and its new view's gids
    engine.reset_stats()
    before = [s.deltas for s in snap.shards]
    idx.insert_batch(X[:64] + 0.25)
    after = [s.deltas for s in idx.snapshot().shards]
    serve(Q[:8])
    report["insert_counters"] = engine.stats()["span_counters"]
    report["appended"] = [a[0].length - b[0].length
                          for a, b in zip(after, before)]
    report["views_after_insert"] = [len(a) for a in after]
    report["expected_insert_upload"] = int(query_bytes + sum(
        (a[0].length - b[0].length) * (dim + 1) * 4 + a[0].gids.nbytes
        for a, b in zip(after, before)))
    try:
        idx.set_mesh(None)
        report["set_mesh_refused"] = False
    except ValueError:
        report["set_mesh_refused"] = True
    idx.close()

    # the benchmark's sharded system at a small scale of gist's layout
    import run
    bench, entry, config, mix = harness.load_cell("gist.serve4")
    config = dict(config, n=4336, segment_rows=256, delta_capacity=256)
    mix = dict(mix, rate_per_s=16.0, warm_queries=8)
    peak = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    layouts = []
    real_layout = harness.load_named("systems", "sharded").System.layout

    def keep_layout(self):
        out = real_layout(self)
        layouts.append(out)
        return out

    harness.load_named("systems", "sharded").System.layout = keep_layout
    res = run.run_cell(bench, entry, config, mix, seed=2 ** 31 + 99,
                       seconds=1.0, trace=False,
                       peak=peak["TPU v5 lite"], devices=jax.devices(),
                       t0=0.0, say=lambda *a: None)
    report["bench"] = {"correct": res["correct"], "failed": res["failed"],
                       "checks": res["checks"], "layout": layouts[-1]}
    print("PLACED_REPORT " + json.dumps(report))
    """
)


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    res = subprocess.run(
        [sys.executable, "-c", _BODY, os.path.join(ROOT, "bench")],
        env=env, capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    line = next(ln for ln in res.stdout.splitlines()
                if ln.startswith("PLACED_REPORT "))
    return json.loads(line.split(" ", 1)[1])


def test_layout_has_sealed_segments_and_a_live_delta(report):
    assert all(n >= 2 for n in report["segments"]), report["segments"]
    assert all(n > 0 for n in report["delta_live"]), report["delta_live"]
    # every sealed segment made resident once, at publish
    assert report["setup_tree_uploads"] == sum(report["segments"])


def test_answers_match_the_reference_on_the_live_set(report):
    assert report["routes"] == {"stacked": 1}
    assert report["ids_match"]
    assert report["dist_err"] < 1e-5


def test_mesh_launch_matches_single_device_and_resilient(report):
    assert report["single_device_equal"]
    assert report["resilient_equal"]


def test_every_shard_state_lives_on_its_device(report):
    for p in report["placed"]:
        assert p["stack"] == p["lane"] == p["trees"] == [p["device"]], p
    calls = report["delta_calls"]
    assert calls and all(dev != "None" and out == [dev]
                         for dev, out in calls), calls
    assert len({dev for dev, _ in calls}) == 4


def test_a_placed_index_refuses_another_mesh(report):
    assert report["set_mesh_refused"]


def test_a_served_batch_uploads_no_tree_bytes(report):
    c = report["counters"]
    assert c["exchange_upload_bytes"] == report["expected_upload"]
    assert "tree_uploads" not in c
    assert "delta_upload_bytes" not in c
    assert c["delta_mirror_hits"] == 4


def test_an_insert_sends_only_the_appended_rows_and_gids(report):
    assert report["appended"] == [16, 16, 16, 16]
    assert report["views_after_insert"] == [1, 1, 1, 1]
    c = report["insert_counters"]
    assert c["exchange_upload_bytes"] == report["expected_insert_upload"]
    assert c["delta_upload_bytes"] == (
        report["expected_insert_upload"] - 4 * 8 * 961 * 4)
    assert c["delta_mirror_rows"] == 64
    assert "delta_mirror_hits" not in c and "tree_uploads" not in c


def test_exchange_spans_are_recorded(report):
    assert report["spans"] == {"p2h.exchange.round1": 1,
                               "p2h.exchange.shard": 4,
                               "p2h.exchange.round2": 1,
                               "p2h.exchange.merge": 1}


def test_bench_system_builds_gist_layout_small(report):
    b = report["bench"]
    assert b["correct"] and b["failed"] == 0, b["checks"]
    per_shard = b["layout"]["per_shard"]
    assert [s["segments"] for s in per_shard] == [4, 4, 4, 4]
    assert b["layout"]["segments"] == 16
    assert b["layout"]["delta_live_rows"] > 0
    assert np.all([s["tiles"] > 0 for s in per_shard])
