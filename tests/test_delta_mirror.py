"""The delta buffer's device mirror: a scan sends only the rows appended
since the last sync, and answers stay bit-identical to a scan of the
whole block uploaded afresh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.balltree import normalize_query
from repro.runtime import spans
from repro.stream import CompactionPolicy, MutableP2HIndex
from repro.stream.delta import (DeltaBuffer, _delta_topk, delta_topk,
                                sync_chunks)

C, D, K = 100, 6, 5


@pytest.fixture(params=["default", "explicit"])
def device(request):
    return None if request.param == "default" else jax.devices()[0]


def _queries(n=3, seed=1):
    q = np.random.default_rng(seed).normal(size=(n, D)).astype(np.float32)
    return jnp.asarray(q / np.linalg.norm(q, axis=1, keepdims=True))


def _rows(m, seed):
    return np.random.default_rng(seed).normal(size=(m, D)).astype(np.float32)


def _whole_block(view, q, k=K):
    """The same view's answers from a fresh upload of the whole block."""
    return _delta_topk(jnp.asarray(view.points), jnp.asarray(view.gids), q, k)


def _same(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def test_sync_chunks_are_fixed_powers_of_four_under_the_capacity():
    assert sync_chunks(C) == (16, 64)
    assert sync_chunks(16) == ()
    assert sync_chunks(240_000) == (16, 64, 256, 1024, 4096, 16384, 65536)


def test_interleaved_writes_scan_like_a_whole_block_upload(device):
    buf, q, gid = DeltaBuffer(C, D), _queries(), 0
    steps = [("extend", 10), ("append", 1), ("tombstone", 3),
             ("extend", 40),   # gap 41: a 64-row chunk from the synced row
             ("extend", 44),   # gap 44: the chunk is clamped to end at C
             ("tombstone", 90), ("extend", 4),  # gap 4: clamped again
             ("append", 1)]    # the buffer is full
    for i, (op, n) in enumerate(steps):
        if op == "extend":
            buf.extend(_rows(n, i), np.arange(gid, gid + n))
            gid += n
        elif op == "append":
            buf.append(_rows(1, i)[0], gid)
            gid += 1
        else:
            buf.tombstone(n)
        view = buf.frozen_view()
        for k in (K, 200):  # 200 > C: the scan pads with invalid slots
            assert _same(delta_topk(view, q, k, device=device),
                         _whole_block(view, q, k)), (i, k)
        assert buf.mirror.synced(device) == buf.length
    assert buf.full


def test_a_gap_past_the_largest_chunk_sends_the_whole_block(device):
    buf, q = DeltaBuffer(C, D), _queries()
    buf.extend(_rows(2, 0), np.arange(2))
    delta_topk(buf.frozen_view(), q, K, device=device)
    buf.extend(_rows(80, 1), np.arange(2, 82))
    view = buf.frozen_view()
    spans.reset()
    out = delta_topk(view, q, K, device=device)
    counters = spans.snapshot()["counters"]
    assert counters["delta_upload_bytes"] == (view.points.nbytes
                                              + view.gids.nbytes)
    assert counters["delta_mirror_rows"] == 80
    assert _same(out, _whole_block(view, q))


def test_a_pinned_view_keeps_its_own_answers(device):
    buf, q = DeltaBuffer(C, D), _queries()
    buf.extend(_rows(20, 0), np.arange(20))
    old = buf.frozen_view()
    first = delta_topk(old, q, K, device=device)
    # rows that lie on every hyperplane: each would top a view that sees it
    buf.extend(np.zeros((30, D), np.float32), np.arange(100, 130))
    new = buf.frozen_view()
    newer = delta_topk(new, q, K, device=device)
    assert buf.mirror.synced(device) == 50
    assert (np.asarray(newer[1]) >= 100).all()
    again = delta_topk(old, q, K, device=device)
    assert _same(again, first) and _same(again, _whole_block(old, q))
    assert (np.asarray(again[1]) < 100).all()


def test_counters_of_a_hit_and_of_appended_rows(device):
    buf, q = DeltaBuffer(C, D), _queries()
    buf.extend(_rows(30, 0), np.arange(30))
    view = buf.frozen_view()
    spans.reset()
    delta_topk(view, q, K, device=device)
    c0 = dict(spans.snapshot()["counters"])
    assert c0["delta_upload_bytes"] == view.points.nbytes + view.gids.nbytes
    assert c0["delta_mirror_rows"] == 30 and "delta_mirror_hits" not in c0
    delta_topk(view, q, K, device=device)  # unchanged view: nothing sent
    c1 = dict(spans.snapshot()["counters"])
    assert c1["delta_upload_bytes"] == c0["delta_upload_bytes"]
    assert c1["delta_mirror_hits"] == 1
    assert c1["delta_mirror_rows"] == 30
    buf.extend(_rows(7, 1), np.arange(30, 37))
    delta_topk(buf.frozen_view(), q, K, device=device)
    c2 = spans.snapshot()["counters"]
    assert c2["delta_mirror_rows"] == 37
    assert c2["delta_upload_bytes"] - c1["delta_upload_bytes"] == (
        16 * D * 4 + C * 4)  # the 16-row chunk and the new view's gids
    assert c2["delta_mirror_hits"] == 1
    assert spans.snapshot()["spans"]["p2h.delta.upload"]["count"] == 3
    if device is None:
        assert "exchange_upload_bytes" not in c2
    else:
        assert c2["exchange_upload_bytes"] == c2["delta_upload_bytes"]


def test_a_sealed_view_answers_exactly_and_the_fresh_buffer_starts_empty(
        device):
    sealed, q = DeltaBuffer(C, D), _queries()
    sealed.extend(_rows(C, 0), np.arange(C))
    delta_topk(sealed.frozen_view(), q, K, device=device)
    sealed.tombstone(7)
    fresh = DeltaBuffer(C, D)
    assert fresh.mirror is not sealed.mirror
    assert fresh.mirror.synced(device) == 0
    fresh.extend(_rows(5, 1), np.arange(C, C + 5))
    for buf in (sealed, fresh):
        view = buf.frozen_view()
        assert _same(delta_topk(view, q, K, device=device),
                     _whole_block(view, q))
    assert fresh.mirror.synced(device) == 5
    assert sealed.mirror.synced(device) == C


def test_an_index_compaction_starts_a_fresh_mirror(device):
    rng = np.random.default_rng(3)
    pts = rng.normal(size=(240, D - 1)).astype(np.float32)
    m = MutableP2HIndex.from_data(
        pts[:160], n0=16, policy=CompactionPolicy(delta_capacity=32),
        device=device)
    q = rng.normal(size=(4, D)).astype(np.float32)
    qn = normalize_query(q)

    def exact(snap):
        P, G = snap.live_points()
        dist = np.abs(P.astype(np.float64) @ np.asarray(qn, np.float64).T)
        return G[np.argsort(dist, axis=0)[:K].T]

    m.insert_batch(pts[160:180])
    snap = m.snapshot()
    _, ids = snap.query(qn, K)
    assert np.array_equal(ids, exact(snap))
    assert snap.deltas[0].mirror.synced(device) == 20
    m.insert_batch(pts[180:200])  # the delta fills and is compacted
    snap = m.snapshot()
    assert snap.deltas[0].length == 8
    assert snap.deltas[0].mirror.synced(device) == 0
    _, ids = snap.query(qn, K)
    assert np.array_equal(ids, exact(snap))
    assert snap.deltas[0].mirror.synced(device) == 8
    m.close()
