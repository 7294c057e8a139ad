"""The program-span readers: device-idle time given to the innermost
host span on a synthetic trace, and the stats readers on a stub
context."""
import types

import pytest

import harness
import program_spans as ps

MS = 1_000_000  # ns


def synthetic():
    dev = [(1 * MS, 2 * MS),      # in the first flush, before the upload
           (5 * MS, 7 * MS),      # device wait of the first batch
           (13 * MS, 14 * MS),
           (19 * MS, 25 * MS)]    # runs past the window
    spans = [("bench.window", 0, 20 * MS),
             ("bench.flush", 0, 8 * MS),
             ("p2h.batch", 1 * MS, 7 * MS),
             ("p2h.search", 2 * MS, 7 * MS),
             ("p2h.delta.upload", 2 * MS, 4 * MS),
             ("p2h.device_wait", 5 * MS, 7 * MS),
             ("bench.delete", 9 * MS, 11 * MS),
             ("p2h.write.delete", 9 * MS, 10 * MS),
             ("bench.flush", 12 * MS, 16 * MS),
             ("p2h.batch", 12 * MS, 15 * MS)]
    transfers = [(int(2.5 * MS), int(5.5 * MS)),  # the block's re-layout
                 (int(5.6 * MS), int(5.8 * MS)),  # its transfer's end
                 (13 * MS, int(13.5 * MS))]       # a batch with no upload
    return {"devices": {"/device:TPU:0": dev}, "spans": spans,
            "transfers": transfers}


def test_idle_goes_to_the_innermost_span():
    got = ps.idle_by_span(synthetic())
    want = {
        "bench.flush": 0.001 + 0.001 + 0.001,  # [0,1], [7,8], [15,16]
        "p2h.delta.upload": 0.002,            # [2,4]
        "p2h.search": 0.001,                  # [4,5]
        "p2h.write.delete": 0.001,            # [9,10]
        "bench.delete": 0.001,                # [10,11]
        "p2h.batch": 0.001 + 0.001,           # [12,13], [14,15]
        ps.NO_SPAN: 0.001 + 0.001 + 0.003,    # [8,9], [11,12], [16,19]
    }
    assert set(got) == set(want)
    for name, v in want.items():
        assert got[name] == pytest.approx(v), name
    # every idle moment of the window, counted once: 20 - 5 busy ms
    assert sum(got.values()) == pytest.approx(0.015)
    assert list(got)[0] == ps.NO_SPAN  # largest first


def test_idle_within_one_span_name():
    got = ps.idle_by_span(synthetic(), "bench.flush")
    assert set(got) == {"bench.flush", "p2h.delta.upload", "p2h.search",
                        "p2h.batch"}
    assert sum(got.values()) == pytest.approx(0.008)
    assert got["p2h.delta.upload"] == pytest.approx(0.002)


def test_devices_are_averaged_and_nothing_reads_none():
    t = synthetic()
    t["devices"]["/device:TPU:1"] = [(0, 20 * MS)]  # never idle
    assert sum(ps.idle_by_span(t).values()) == pytest.approx(0.015 / 2)
    assert ps.idle_by_span(dict(t, devices={})) is None
    no_window = [s for s in t["spans"] if s[0] != "bench.window"]
    assert ps.idle_by_span(dict(t, spans=no_window)) is None


def test_an_upload_lasts_until_its_transfer_ends():
    assert ps.upload_intervals(synthetic()) == [(2 * MS, int(5.8 * MS))]


def test_two_uploads_in_one_batch_split_the_transfers():
    t = synthetic()
    t["spans"] = [sp for sp in t["spans"] if sp[0] != "p2h.delta.upload"]
    t["spans"] += [("p2h.delta.upload", 2 * MS, 3 * MS),
                   ("p2h.delta.upload", int(3.5 * MS), 4 * MS)]
    t["transfers"] = [(1 * MS, int(1.5 * MS)),        # before: not its own
                      (int(2.2 * MS), int(3.2 * MS)),
                      (int(3.6 * MS), int(4.5 * MS)),
                      (int(4.6 * MS), int(4.7 * MS))]
    assert ps.upload_intervals(t) == [(2 * MS, int(3.2 * MS)),
                                      (int(3.5 * MS), int(4.7 * MS))]


def test_an_upload_without_transfer_events_is_its_call_alone():
    t = synthetic()
    t["transfers"] = [(13 * MS, int(13.5 * MS))]
    assert ps.upload_intervals(t) == [(2 * MS, 4 * MS)]
    assert ps.upload_intervals(dict(t, transfers=[])) is None


def traced_ctx(monkeypatch, trace, counters=None):
    monkeypatch.setattr(ps, "last_trace", lambda: trace)
    stats = {"spans": {"p2h.delta.upload": {
        "count": 1, "total_s": 0.0006, "self_s": 0.0006, "p50_ms": 0.6,
        "p95_ms": 0.6}}, "span_counters": counters or {}}
    return types.SimpleNamespace(trace={"busy_s": 0.004}, stats=stats)


TRACE_METRICS = ["delta_upload_ms.serve", "delta_upload_ms.al",
                 "delta_upload_gbps.serve", "idle_in_upload_pct.serve"]


def test_upload_readers_read_the_last_trace(monkeypatch):
    ctx = traced_ctx(monkeypatch, synthetic(),
                     {"delta_upload_bytes": 3_800_000})
    got = {m: harness.load_named("metrics", m).read(ctx)
           for m in TRACE_METRICS}
    # idle in the flushes 8 ms, of it [2, 5] while the block was coming
    assert got == pytest.approx({
        "delta_upload_ms.serve": 3.8, "delta_upload_ms.al": 3.8,
        "delta_upload_gbps.serve": 1.0,
        "idle_in_upload_pct.serve": 100 * 3 / 8})


@pytest.mark.parametrize("metric", TRACE_METRICS)
def test_upload_readers_read_none_without_their_events(monkeypatch,
                                                         metric):
    read = harness.load_named("metrics", metric).read
    t = synthetic()
    no_upload = [sp for sp in t["spans"] if sp[0] != "p2h.delta.upload"]
    ctx = traced_ctx(monkeypatch, dict(t, spans=no_upload),
                     {"delta_upload_bytes": 1})
    assert read(ctx) is None                      # a program without it
    ctx = traced_ctx(monkeypatch, dict(t, transfers=[]),
                     {"delta_upload_bytes": 1})
    assert read(ctx) is None                      # no transfer events
    assert read(types.SimpleNamespace(trace=None, stats={})) is None
    ctx = traced_ctx(monkeypatch, None)
    assert read(ctx) is None                      # no trace file


def stats_ctx(spans=None, counters=None, batches=4):
    stats = {"batches": batches}
    if spans is not None:
        stats["spans"] = {
            n: {"count": c, "total_s": t, "self_s": t, "p50_ms": p,
                "p95_ms": p} for n, (c, t, p) in spans.items()}
        stats["span_counters"] = counters or {}
    return types.SimpleNamespace(stats=stats, trace=None)


STATS_METRICS = ["host_self_ms.serve", "host_self_ms.al",
                 "ids_rewrite_ms.al", "publish_share_pct.al"]


def test_stats_readers():
    ctx = stats_ctx({"p2h.batch": (4, 0.400, 100.0),
                     "p2h.device_wait": (4, 0.200, 50.0),
                     "p2h.stacked.ids_rewrite": (2, 0.006, 3.0),
                     "p2h.write.delete": (100, 0.004, 0.04),
                     "p2h.write.insert": (1, 0.001, 1.0),
                     "p2h.publish": (101, 0.002, 0.02)})
    got = {m: harness.load_named("metrics", m).read(ctx)
           for m in STATS_METRICS}
    assert got == pytest.approx({
        "host_self_ms.serve": 50.0, "host_self_ms.al": 50.0,
        "ids_rewrite_ms.al": 1.5, "publish_share_pct.al": 40.0})


@pytest.mark.parametrize("metric", STATS_METRICS)
def test_stats_readers_read_none_without_their_span(metric):
    read = harness.load_named("metrics", metric).read
    assert read(stats_ctx()) is None  # a program that records no spans
    assert read(stats_ctx({"p2h.other": (1, 0.001, 1.0)})) is None
