"""Mutable LSM-style index tests: write path, snapshot isolation,
compaction (inline, forced, background), persistence, and the core
acceptance property -- an arbitrary interleaving of inserts / deletes /
queries is exact vs a brute-force oracle on the live point set, across
all four backends and across compaction boundaries."""
import functools
import threading

import numpy as np
import pytest
import jax.numpy as jnp

from _hyp import given_int_seed
from repro.core import exact_search
from repro.core.balltree import normalize_query
from repro.stream import (CompactionPolicy, DeltaBuffer, MutableP2HIndex,
                          Snapshot)

DIM = 8
BACKENDS = ["dfs", "sweep", "pallas", "beam"]  # beam at frac=1.0 is exact


def _mkdata(n, seed=0, dim=DIM):
    return np.random.default_rng(seed).normal(size=(n, dim)).astype(np.float32)


def _oracle(snap: Snapshot, q, k):
    """Brute force over the snapshot's live set; (dists, global ids)."""
    X, G = snap.live_points()
    if len(X) == 0:
        B = np.atleast_2d(q).shape[0]
        return (np.full((B, k), np.inf, np.float32),
                np.full((B, k), -1, np.int32))
    ed, ei = exact_search(jnp.asarray(X),
                          jnp.asarray(normalize_query(np.atleast_2d(q))), k=k)
    ed, ei = np.asarray(ed), np.asarray(ei)
    return ed, np.where(ei >= 0, G[np.clip(ei, 0, len(G) - 1)], -1)


def _live_points(snap: Snapshot):
    """gid -> point over the snapshot's live set."""
    out = {}
    for v in snap.deltas:
        for row in range(v.length):
            if v.gids[row] >= 0:
                out[int(v.gids[row])] = v.points[row]
    for s in snap.segments:
        p, g = s.live_rows()
        for i, gid in enumerate(g):
            out[int(gid)] = p[i]
    return out


def _assert_matches_oracle(m, q, k, method, tag=""):
    kw = dict(frac=1.0) if method == "beam" else {}
    snap = m.snapshot()
    bd, bi = m.query(q, k=k, method=method, **kw)
    ed, eg = _oracle(snap, q, k)
    np.testing.assert_allclose(bd, ed, rtol=1e-4, atol=1e-5,
                               err_msg=f"{method} {tag}")
    # id disagreements must be ties: the returned id must be live and its
    # true distance must equal the oracle's at that rank (f32 tolerance)
    tie_tol = 1e-4 * np.abs(ed) + 1e-6
    qn = normalize_query(np.atleast_2d(q)).astype(np.float32)
    live = None
    for r in range(len(eg)):
        mism = bi[r] != eg[r]
        if not mism.any():
            continue
        assert (np.abs(bd[r][mism] - ed[r][mism])
                <= tie_tol[r][mism]).all(), (method, tag, r)
        live = _live_points(snap) if live is None else live
        for j in np.nonzero(mism)[0]:
            gid = int(bi[r][j])
            assert gid in live, (method, tag, r, gid)
            true_d = abs(float(live[gid] @ qn[r]))
            assert abs(true_d - ed[r][j]) <= tie_tol[r][j], (
                method, tag, r, gid, true_d, ed[r][j])


# --------------------------------------------------------------- delta
def test_delta_buffer_append_tombstone_live_rows():
    b = DeltaBuffer(4, 3)
    assert not b.full and b.live == 0
    b.append(np.array([1, 2, 3], np.float32), gid=7)
    b.append(np.array([4, 5, 6], np.float32), gid=8)
    assert b.live == 2
    b.tombstone(0)
    pts, gids = b.live_rows()
    assert gids.tolist() == [8] and pts.shape == (1, 3)
    # frozen view is immune to later appends/tombstones
    view = b.frozen_view()
    b.append(np.zeros(3, np.float32), gid=9)
    b.tombstone(1)
    assert view.gids.tolist()[:2] == [-1, 8] and view.length == 2
    b.append(np.zeros(3, np.float32), gid=10)
    assert b.full
    with pytest.raises(AssertionError):
        b.append(np.zeros(3, np.float32), gid=11)


# ----------------------------------------------------- snapshot semantics
def test_snapshot_pinned_view_is_immutable():
    m = MutableP2HIndex.from_data(_mkdata(300),
                                  n0=64,
                                  policy=CompactionPolicy(delta_capacity=16))
    q = _mkdata(2, seed=5, dim=DIM + 1)
    pinned = m.snapshot()
    d0, i0 = pinned.query(normalize_query(q), k=5, return_counters=False)
    # mutate heavily: inserts past a compaction boundary + deletes
    for i in range(40):
        m.insert(_mkdata(1, seed=100 + i)[0])
    for g in range(0, 60, 3):
        m.delete(g)
    assert m.epoch > pinned.epoch
    d1, i1 = pinned.query(normalize_query(q), k=5)
    assert np.array_equal(d0, d1) and np.array_equal(i0, i1)
    # while the new snapshot reflects the deletes
    live_gids = {int(g) for s in m.snapshot().segments
                 for g in s.live_rows()[1]}
    assert not ({g for g in range(0, 60, 3)} & live_gids)


def test_epoch_monotone_and_delete_tracking():
    m = MutableP2HIndex(DIM, n0=64,
                        policy=CompactionPolicy(delta_capacity=8))
    e0 = m.epoch
    g = m.insert(np.zeros(DIM, np.float32))
    assert m.epoch > e0
    assert m.snapshot().last_delete_epoch == 0  # inserts don't invalidate
    m.delete(g)
    assert m.snapshot().last_delete_epoch == m.epoch
    assert not m.delete(g)  # double delete
    assert m.live_count == 0


def test_insert_batch_bulk_path():
    m = MutableP2HIndex(DIM, n0=32,
                        policy=CompactionPolicy(delta_capacity=64))
    e0 = m.epoch
    gids = m.insert_batch(_mkdata(10, seed=21))
    assert len({int(g) for g in gids}) == 10
    assert m.epoch == e0 + 1  # one publish for the whole batch
    assert m.live_count == 10
    _assert_matches_oracle(m, _mkdata(2, seed=22, dim=DIM + 1), 3, "sweep")
    # batches larger than the delta capacity compact mid-batch
    m.insert_batch(_mkdata(100, seed=23))
    assert m.live_count == 110 and len(m.compaction_log) >= 1
    _assert_matches_oracle(m, _mkdata(2, seed=22, dim=DIM + 1), 3, "sweep")


def test_compaction_policy_plans():
    pol = CompactionPolicy(delta_capacity=8, tombstone_frac=0.5,
                           max_segments=2)

    class S:  # stub segment
        def __init__(self, uid, live, dead):
            self.uid, self.live, self.dead = uid, live, dead

        @property
        def tombstone_frac(self):
            return self.dead / (self.live + self.dead)

    assert not pol.plan(delta_full=False, delta_live=3, segments=())
    p = pol.plan(delta_full=True, delta_live=8, segments=())
    assert p and p.include_delta and not p.segment_uids
    p = pol.plan(delta_full=False, delta_live=0,
                 segments=(S(1, 1, 3),))
    assert p.segment_uids == (1,) and not p.include_delta
    p = pol.plan(delta_full=False, delta_live=4,
                 segments=(S(1, 5, 0), S(2, 5, 0), S(3, 5, 0)))
    assert set(p.segment_uids) == {1, 2, 3}  # fan-out merge


def test_forced_compaction_merges_everything():
    m = MutableP2HIndex.from_data(_mkdata(200), n0=64,
                                  policy=CompactionPolicy(delta_capacity=16))
    for i in range(20):
        m.insert(_mkdata(1, seed=200 + i)[0])
    for g in range(0, 50, 5):
        m.delete(g)
    q = _mkdata(3, seed=6, dim=DIM + 1)
    before_d, before_i = m.query(q, k=8)
    assert m.compact(force=True)
    snap = m.snapshot()
    assert len(snap.segments) == 1 and snap.delta_live == 0
    assert snap.segments[0].dead == 0  # tombstones reclaimed
    after_d, after_i = m.query(q, k=8)
    np.testing.assert_allclose(before_d, after_d, rtol=1e-4, atol=1e-6)
    assert np.array_equal(np.sort(before_i), np.sort(after_i))
    assert not m.compact()  # nothing left to do


# ------------------------------------------------------------ persistence
def test_save_load_roundtrip(tmp_path):
    m = MutableP2HIndex.from_data(_mkdata(400), n0=64,
                                  policy=CompactionPolicy(delta_capacity=32))
    for i in range(50):
        m.insert(_mkdata(1, seed=300 + i)[0])
    for g in range(0, 100, 7):
        m.delete(g)
    q = _mkdata(4, seed=8, dim=DIM + 1)
    d1, i1 = m.query(q, k=6)
    step = m.save(str(tmp_path / "ckpt"))
    m2 = MutableP2HIndex.load(str(tmp_path / "ckpt"))
    assert m2.epoch == m.epoch
    assert m2.live_count == m.live_count
    assert m2.snapshot().last_delete_epoch == m.snapshot().last_delete_epoch
    d2, i2 = m2.query(q, k=6)
    assert np.array_equal(i1, i2)
    np.testing.assert_allclose(d1, d2, rtol=1e-6)
    # the restored index keeps mutating correctly: fresh gids, working
    # deletes, oracle parity
    g = m2.insert(np.zeros(DIM, np.float32))
    assert g >= m.live_count  # never reuses a gid
    assert m2.delete(int(i2[0, 0]))
    _assert_matches_oracle(m2, q, 6, "sweep", "post-restore")
    assert step == m.epoch


# ------------------------------------------- the acceptance property test
def _stream_property(seed):
    rng = np.random.default_rng(seed)
    m = MutableP2HIndex.from_data(
        _mkdata(150, seed=seed), n0=32,
        policy=CompactionPolicy(delta_capacity=24, tombstone_frac=0.3,
                                max_segments=3))
    live = list(range(150))
    k = 5
    q = rng.normal(size=(3, DIM + 1)).astype(np.float32)
    compactions_before = len(m.compaction_log)
    for step in range(80):
        op = rng.random()
        if op < 0.5 or not live:
            gid = m.insert(rng.normal(size=DIM).astype(np.float32))
            live.append(gid)
        elif op < 0.8:
            victim = live.pop(int(rng.integers(len(live))))
            assert m.delete(victim)
        else:
            meth = BACKENDS[int(rng.integers(len(BACKENDS)))]
            _assert_matches_oracle(m, q, k, meth, f"step{step}")
    # the workload must have crossed at least one compaction boundary
    assert len(m.compaction_log) > compactions_before
    assert m.live_count == len(live)
    for meth in BACKENDS:
        _assert_matches_oracle(m, q, k, meth, "final")
    # and again across a forced full compaction
    m.compact(force=True)
    for meth in BACKENDS:
        _assert_matches_oracle(m, q, k, meth, "post-compact")


@given_int_seed(max_examples=8, hi=2**31 - 1, fallback_seeds=(0, 1, 2))
def test_stream_interleaving_exact_vs_oracle(seed):
    """Acceptance property: any interleaving of inserts/deletes/queries
    is exact vs brute force on the live set, for all four backends,
    across compaction boundaries."""
    _stream_property(seed)


# -------------------------------------------------- background compaction
def test_background_compaction_exact_under_concurrent_writes():
    m = MutableP2HIndex.from_data(
        _mkdata(200, seed=9), n0=32, background=True,
        policy=CompactionPolicy(delta_capacity=16))
    try:
        rng = np.random.default_rng(9)
        q = rng.normal(size=(2, DIM + 1)).astype(np.float32)
        errs = []

        def writer():
            try:
                for i in range(150):
                    m.insert(rng.normal(size=DIM).astype(np.float32))
                    if i % 4 == 0:
                        m.delete(int(i))
            except BaseException as e:  # surfaced in the main thread
                errs.append(e)

        t = threading.Thread(target=writer)
        t.start()
        # queries race the writer + compactor: each pins a snapshot and
        # must be exact for that snapshot
        for _ in range(10):
            snap = m.snapshot()
            bd, bi = snap.query(normalize_query(q), 4,
                                return_counters=False)
            ed, eg = _oracle(snap, q, 4)
            np.testing.assert_allclose(bd, ed, rtol=1e-4, atol=1e-5)
        t.join()
        assert not errs, errs
        m.wait_compaction()
        assert len(m.compaction_log) >= 1
        _assert_matches_oracle(m, q, 4, "sweep", "after-join")
    finally:
        m.close()


def test_background_compactor_failure_surfaces_and_recovers(monkeypatch):
    """A crashing background build must not wedge writers: the error
    surfaces at the next wait point, the sealed delta stays queryable,
    and the next (healthy) compaction folds its rows into a segment."""
    import repro.stream.mutable as mutable_mod

    m = MutableP2HIndex.from_data(
        _mkdata(100, seed=13), n0=32, background=True,
        policy=CompactionPolicy(delta_capacity=8))
    try:
        q = _mkdata(2, seed=14, dim=DIM + 1)
        real_from_points = mutable_mod.Segment.from_points

        def boom(*a, **kw):
            raise RuntimeError("injected build failure")

        monkeypatch.setattr(mutable_mod.Segment, "from_points", boom)
        gids = [m.insert(_mkdata(1, seed=500 + i)[0]) for i in range(9)]
        with pytest.raises(RuntimeError, match="injected"):
            for _ in range(50):  # compactor fails asynchronously
                m.wait_compaction()
                import time as _t
                _t.sleep(0.05)
            raise AssertionError("compactor error never surfaced")
        # rows of the failed run are still live and queryable
        _assert_matches_oracle(m, q, 4, "sweep", "after-failure")
        assert m.snapshot().delta_live > 0 or m.snapshot().segments
        assert all(g in {int(x) for s in m.snapshot().segments
                         for x in s.live_rows()[1]}
                   | {int(x) for v in m.snapshot().deltas
                      for x in v.gids if x >= 0}
                   for g in gids)
        # heal the build path: compact() consumes the leftovers
        monkeypatch.setattr(mutable_mod.Segment, "from_points",
                            real_from_points)
        for _ in range(20):  # drain errors from straggler retries
            try:
                m.wait_compaction()
                break
            except RuntimeError:
                pass
        assert m.compact()
        assert not m._sealed
        _assert_matches_oracle(m, q, 4, "sweep", "after-recovery")
    finally:
        m.close()


def test_engine_over_mutable_index_pins_snapshots():
    from repro.serve import DispatchPolicy, P2HEngine

    m = MutableP2HIndex.from_data(_mkdata(500, seed=3), n0=64,
                                  policy=CompactionPolicy(delta_capacity=32))
    eng = P2HEngine(m, slot_size=4,
                    policy=DispatchPolicy(prefer_pallas=False))
    q = _mkdata(4, seed=11, dim=DIM + 1)
    d1, i1 = m.query(q, k=6, engine=eng)
    ed, eg = _oracle(m.snapshot(), q, 6)
    assert np.array_equal(i1, eg)
    for i in range(40):
        m.insert(_mkdata(1, seed=400 + i)[0])
    d2, i2, st = m.query(q, k=6, engine=eng, return_stats=True)
    ed2, eg2 = _oracle(m.snapshot(), q, 6)
    assert np.array_equal(i2, eg2)
    assert st["verified"] > 0
    # wrong-engine guard
    other = MutableP2HIndex(DIM, n0=64)
    with pytest.raises(AssertionError):
        other.query(q, k=6, engine=eng)


@pytest.mark.parametrize("max_segments", [5, 8])
def test_insert_batch_concurrent_flushes_match_inline_flushes(max_segments):
    """An insert_batch that fills the delta several times appends its rows
    a block per delta fill; the segments, uids, epochs, compaction
    decisions and max norm equal those of the same rows inserted one at a
    time, bit for bit -- including a fan-out merge (``max_segments`` 5)
    and deletes between batches."""
    import dataclasses

    X = _mkdata(9000, seed=7, dim=24)
    pol = CompactionPolicy(delta_capacity=1500, tombstone_frac=0.5,
                           max_segments=max_segments)

    def row_by_row(idx, pts):
        with idx._lock:
            for x in pts:
                idx._insert_one_locked(x)
            idx._publish()
            idx._maybe_compact_locked()

    def build(block):
        idx = MutableP2HIndex.from_data(X[:1500], n0=32, policy=pol,
                                        seed=11)
        insert = idx.insert_batch if block else functools.partial(
            row_by_row, idx)
        insert(X[1500:5000])
        assert idx.delete(7) and idx.delete(3000)
        insert(X[5000:])
        return idx

    a, b = build(True), build(False)
    sa, sb = a.snapshot(), b.snapshot()
    assert sa.epoch == sb.epoch
    assert [s.uid for s in sa.segments] == [s.uid for s in sb.segments]
    for x, y in zip(sa.segments, sb.segments):
        assert np.array_equal(x.gids, y.gids)
        for f in dataclasses.fields(x.tree):
            u, v = getattr(x.tree, f.name), getattr(y.tree, f.name)
            if isinstance(u, np.ndarray):
                assert u.tobytes() == v.tobytes(), f.name
            else:
                assert u == v, f.name
    assert ([c["reason"] for c in a.compaction_log]
            == [c["reason"] for c in b.compaction_log])
    assert a._max_norm == b._max_norm
    assert a.live_count == b.live_count == len(X) - 2
    a.close()
    b.close()
