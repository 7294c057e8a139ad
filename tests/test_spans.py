"""Host spans and counters (``repro.runtime.spans``): the recorder's
arithmetic, the spans the serving engine and the mutable index record
per micro-batch and per write, and their place in a profiler trace."""
import threading

import jax
import numpy as np
import pytest

from repro.runtime import spans
from repro.serve import DispatchPolicy, P2HEngine
from repro.stream import CompactionPolicy, MutableP2HIndex

DIM, N0, CAP, K = 12, 16, 64, 5


@pytest.fixture(autouse=True)
def _fresh_recorder():
    spans.reset()
    yield
    spans.reset()


class FakeClock:
    """The recorder's clock, moved by hand."""

    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans, "_clock", c)
    return c


# ------------------------------------------------------------- recorder
def test_nested_spans_split_self_time(clock):
    with spans.span("outer"):
        clock.now += 2.0
        with spans.span("inner"):
            clock.now += 3.0
        with spans.span("inner"):
            clock.now += 1.0
        clock.now += 0.5
    rec = spans.snapshot()["spans"]
    assert rec["outer"]["count"] == 1 and rec["inner"]["count"] == 2
    assert rec["outer"]["total_s"] == pytest.approx(6.5)
    assert rec["outer"]["self_s"] == pytest.approx(2.5)
    assert rec["inner"]["total_s"] == pytest.approx(4.0)
    assert rec["inner"]["self_s"] == pytest.approx(4.0)
    assert rec["inner"]["p50_ms"] == pytest.approx(1000.0)
    assert rec["inner"]["p95_ms"] == pytest.approx(3000.0)


def test_ring_keeps_the_last_durations_only(clock):
    slow = 904
    for i in range(slow + spans.RING):
        with spans.span("s"):
            clock.now += 1.0 if i < slow else 0.001
    rec = spans.snapshot()["spans"]["s"]
    assert rec["count"] == slow + spans.RING
    assert rec["total_s"] == pytest.approx(slow + spans.RING * 0.001)
    # the slow spans fell out of the ring: every percentile is 1 ms
    assert rec["p50_ms"] == pytest.approx(1.0)
    assert rec["p95_ms"] == pytest.approx(1.0)
    assert "never" not in spans.snapshot()["spans"]


def test_a_span_keeps_its_duration(clock):
    with spans.span("s") as s:
        clock.now += 0.25
    assert s.duration_s == pytest.approx(0.25)


def test_counters_and_reset():
    spans.count("bytes", 3)
    spans.count("bytes", 4)
    spans.count("events")
    with spans.span("s"):
        pass
    snap = spans.snapshot()
    assert snap["counters"] == {"bytes": 7, "events": 1}
    assert set(snap["spans"]) == {"s"}
    spans.reset()
    assert spans.snapshot() == {"spans": {}, "counters": {}}


def test_a_second_threads_spans_do_not_nest_in_this_ones():
    started, release = threading.Event(), threading.Event()

    def worker():
        with spans.span("worker.outer"):
            with spans.span("worker.inner"):
                started.set()
                release.wait(5.0)

    with spans.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        assert started.wait(5.0)
        release.set()
        t.join(5.0)
    rec = spans.snapshot()["spans"]
    assert {n: r["count"] for n, r in rec.items()} == {
        "main": 1, "worker.outer": 1, "worker.inner": 1}
    # the worker's spans are children of the worker's span alone
    assert rec["main"]["self_s"] == pytest.approx(rec["main"]["total_s"])
    assert rec["worker.outer"]["self_s"] == pytest.approx(
        rec["worker.outer"]["total_s"] - rec["worker.inner"]["total_s"])


def test_threads_that_ended_are_folded_not_lost(clock):
    def worker():
        with spans.span("w"):
            clock.now += 0.5
        spans.count("n", 2)

    for _ in range(20):
        t = threading.Thread(target=worker)
        t.start()
        t.join(5.0)
    with spans.span("w"):  # this thread's first table folds the dead
        clock.now += 0.5
    snap = spans.snapshot()
    assert snap["spans"]["w"]["count"] == 21
    assert snap["spans"]["w"]["total_s"] == pytest.approx(10.5)
    assert snap["counters"] == {"n": 40}
    # one table per live thread that recorded, the ended ones merged
    assert len(spans._tables) <= 2
    spans.reset()
    assert spans.snapshot() == {"spans": {}, "counters": {}}


# ------------------------------------------------- engine and write path
@pytest.fixture(scope="module")
def served():
    """Four sealed segments plus a live delta of ``CAP`` rows, served
    through the stacked route."""
    rng = np.random.default_rng(7)
    pts = rng.normal(size=(4 * CAP + 20, DIM)).astype(np.float32)
    idx = MutableP2HIndex.from_data(
        pts[:CAP], n0=N0, policy=CompactionPolicy(delta_capacity=CAP,
                                                  max_segments=8))
    idx.insert_batch(pts[CAP:])
    eng = P2HEngine(idx, slot_size=4,
                    policy=DispatchPolicy(stacked_min_fanout=2))
    q = rng.normal(size=(8, DIM + 1)).astype(np.float32)
    eng.query(q[:4], K)  # compile outside the tests' windows
    return idx, eng, q


def _flush(eng, queries):
    tickets = [eng.submit(row, K) for row in queries]
    n = eng.flush()
    for t in tickets:
        eng.result(t)
    return n


def test_flush_records_the_batch_spans(served):
    idx, eng, q = served
    snap = idx.snapshot()
    assert len(snap.segments) >= 2 and snap.delta_live > 0
    eng.reset_stats()
    batches = _flush(eng, q)
    assert batches == 2
    st = eng.stats()
    assert st["routes"] == {"stacked": batches}
    rec = st["spans"]
    for name in ("p2h.batch", "p2h.pin", "p2h.search", "p2h.delta.scan",
                 "p2h.delta.upload", "p2h.stacked.launch",
                 "p2h.device_wait", "p2h.cache.lookup",
                 "p2h.cache.update"):
        assert rec[name]["count"] == batches, name
    # the fixture's query synced every view: a batch on the same
    # snapshot sends no delta bytes, and each view scan is a mirror hit
    views = len(snap.deltas)
    counters = st["span_counters"]
    assert "delta_upload_bytes" not in counters
    assert "delta_mirror_rows" not in counters
    assert counters["delta_mirror_hits"] == batches * views
    # the nesting holds: a batch's children lie inside it
    b = rec["p2h.batch"]
    inner = sum(rec[n]["total_s"] for n in (
        "p2h.pin", "p2h.cache.lookup", "p2h.search", "p2h.cache.update"))
    assert b["self_s"] == pytest.approx(b["total_s"] - inner)
    assert rec["p2h.search"]["total_s"] >= (
        rec["p2h.delta.scan"]["total_s"] + rec["p2h.device_wait"]["total_s"])


def test_latency_percentiles_read_the_search_span(served):
    _, eng, q = served
    eng.reset_stats()
    assert np.isnan(eng.stats()["latency_p50_ms"])
    _flush(eng, q)
    _flush(eng, q[:3])
    st = eng.stats()
    assert st["latency_p50_ms"] == st["spans"]["p2h.search"]["p50_ms"]
    # under 11 batches both the p95 and the p99 are the slowest one
    assert st["latency_p99_ms"] == st["spans"]["p2h.search"]["p95_ms"]
    assert st["latency_p99_ms"] >= st["latency_p50_ms"] > 0
    eng.reset_stats()
    assert eng.stats()["spans"] == {}


def test_two_engines_keep_their_own_latency(served):
    idx, eng, q = served
    other = P2HEngine(idx, slot_size=4,
                      policy=DispatchPolicy(stacked_min_fanout=2))
    other.query(q[:4], K)
    eng.reset_stats()
    other.reset_stats()
    _flush(eng, q)
    _flush(other, q[:4])
    mine, theirs = eng.stats(), other.stats()
    assert (mine["batches"], theirs["batches"]) == (2, 1)
    # each engine's percentiles read its own batches' p2h.search spans;
    # the recorder under "spans" is the process's, so both see all three
    assert theirs["latency_p50_ms"] == theirs["latency_p99_ms"]
    assert mine["spans"]["p2h.search"]["count"] == 3
    assert theirs["spans"] == mine["spans"]
    other.reset_stats()  # clears the recorder, not this engine's latency
    assert eng.stats()["spans"] == {}
    assert eng.stats()["latency_p50_ms"] == mine["latency_p50_ms"]


def test_delete_then_query_rewrites_the_ids_plane_once(served):
    idx, eng, q = served
    seg = idx.snapshot().segments[0]
    gid = int(next(g for g in np.asarray(seg.gids) if g >= 0))
    eng.reset_stats()
    assert idx.delete(gid)
    st = eng.stats()
    assert st["spans"]["p2h.write.delete"]["count"] == 1
    assert st["spans"]["p2h.publish"]["count"] == 1
    assert st["span_counters"]["publishes"] == 1
    assert "p2h.stacked.ids_rewrite" not in st["spans"]  # applied lazily
    _flush(eng, q)
    st = eng.stats()
    assert st["spans"]["p2h.stacked.ids_rewrite"]["count"] == 1
    assert st["span_counters"]["ids_rewrites"] == 1
    _, ids = eng.query(q[:4], K)
    assert gid not in ids
    # the memo holds: a second batch on the same snapshot rewrites nothing
    assert eng.stats()["span_counters"]["ids_rewrites"] == 1


def test_insert_batch_records_a_write_and_a_publish(served):
    idx, eng, _ = served
    eng.reset_stats()
    gids = idx.insert_batch(np.zeros((3, DIM), np.float32))
    st = eng.stats()
    assert st["spans"]["p2h.write.insert"]["count"] == 1
    assert st["span_counters"]["publishes"] == 1
    for g in gids:
        assert idx.delete(int(g))
    assert eng.stats()["spans"]["p2h.write.delete"]["count"] == 3


def _events(path, prefix):
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns)
                        for ev in line.events if ev.name.startswith(prefix)]
    return out


def test_profiler_trace_holds_the_spans_on_its_clock(served, tmp_path):
    import glob
    import os

    _, eng, q = served
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("outer.flush"):
        _flush(eng, q[:4])
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    outer = _events(found[0], "outer.")
    p2h = _events(found[0], "p2h.")
    assert len(outer) == 1
    _, lo, hi = outer[0]
    names = {n for n, _, _ in p2h}
    # the batch annotation carries its attrs in the event's name
    assert any(n.startswith("p2h.batch") for n in names), names
    assert {"p2h.search", "p2h.delta.upload",
            "p2h.device_wait"} <= names, names
    assert all(lo <= s <= e <= hi for _, s, e in p2h)
    search = next((s, e) for n, s, e in p2h if n == "p2h.search")
    upload = next((s, e) for n, s, e in p2h if n == "p2h.delta.upload")
    assert search[0] <= upload[0] <= upload[1] <= search[1]
