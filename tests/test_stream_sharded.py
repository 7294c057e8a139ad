"""Cross-shard chaos suite for the sharded mutable index.

Covers the PR's acceptance surface:

  * chaos/property -- arbitrary interleavings of routed inserts, deletes
    and queries across 2-4 shards, with forced compactions (whole-index
    and single-shard) injected at random points, bit-exact vs the
    brute-force oracle on the union live set, across all four backends;
  * snapshot pinning -- an epoch-vector pin keeps answering identically
    through forced mid-query compaction on another thread's schedule;
  * fault injection -- a shard's background compactor is killed
    mid-build (poisoned ``Segment.from_points``): published snapshots
    are never torn (epoch vector monotone, no duplicate/lost gids), and
    ``runtime.fault_tolerance.run_with_restarts`` drives the heal;
  * raced deletes -- a delete landing while its shard's build is blocked
    mid-flight is re-applied at publish time;
  * lambda-exchange invariant -- round-1 per-shard caps upper-bound the
    true global k-th distance (the exchange's validity proof), including
    against a mid-compaction shard state;
  * per-shard lambda-cache invalidation -- one shard's delete drops one
    component, not the whole entry, and warm stays bit-exact;
  * persistence -- per-shard checkpoints + manifest roundtrip.
"""
import threading

import numpy as np
import pytest

from _hyp import given_int_seed
from repro.runtime.fault_tolerance import RetryPolicy, run_with_restarts
from repro.stream import (CompactionPolicy, HashRouter,
                         ShardedMutableP2HIndex)
from test_stream import (BACKENDS, DIM, _assert_matches_oracle, _mkdata,
                         _oracle)


def _mk(n, num_shards, seed=0, *, delta_capacity=16, background=False,
        tombstone_frac=0.3, max_segments=3):
    return ShardedMutableP2HIndex.from_data(
        _mkdata(n, seed=seed), num_shards, n0=32, seed=seed,
        background=background,
        policy=CompactionPolicy(delta_capacity=delta_capacity,
                                tombstone_frac=tombstone_frac,
                                max_segments=max_segments))


def _epoch_leq(a, b):
    return len(a) == len(b) and all(x <= y for x, y in zip(a, b))


# ------------------------------------------------------------------ router
def test_hash_router_deterministic_and_balanced():
    r = HashRouter(4)
    owner = np.array([r.shard_of(g) for g in range(4000)])
    assert np.array_equal(owner, [HashRouter.from_spec(r.spec()).shard_of(g)
                                  for g in range(4000)])
    counts = np.bincount(owner, minlength=4)
    assert counts.min() > 500, counts  # no starved shard

    class EvenOdd:  # custom router: pluggability surface
        def shard_of(self, gid):
            return int(gid) % 2

        def spec(self):
            return {"kind": "evenodd"}

    m = ShardedMutableP2HIndex(DIM, 2, n0=32, router=EvenOdd())
    g0 = m.insert(np.zeros(DIM, np.float32))
    g1 = m.insert(np.ones(DIM, np.float32))
    assert m.shards[g0 % 2].live_count + m.shards[g1 % 2].live_count == 2
    assert m.shards[0].live_count == 1 and m.shards[1].live_count == 1


def test_routed_writes_land_on_owning_shard():
    m = _mk(120, 3, seed=4)
    gid = m.insert(_mkdata(1, seed=99)[0])
    owner = m.router.shard_of(gid)
    assert any(int(g) == gid
               for v in m.shards[owner].snapshot().deltas
               for g in v.gids if g >= 0)
    assert m.delete(gid)
    assert not m.delete(gid)  # double delete, still routed
    # delete of a bulk-loaded point reaches its segment's shard
    assert m.delete(7)
    assert 7 not in set(m.snapshot().live_points()[1].tolist())


# ------------------------------------------------- chaos / property suite
def _sharded_chaos(seed):
    rng = np.random.default_rng(seed)
    num_shards = 2 + seed % 3  # 2..4: acceptance needs >= 2 shard counts
    m = _mk(150, num_shards, seed=seed, delta_capacity=12)
    live = list(range(150))
    k = 5
    q = rng.normal(size=(3, DIM + 1)).astype(np.float32)
    forced = 0
    for step in range(60):
        op = rng.random()
        if step == 30 and not forced:
            # a draw may force no compaction (seed 1322705291): hold one
            # mid-churn anyway
            op = 0.75
        if op < 0.45 or not live:
            live.append(m.insert(rng.normal(size=DIM).astype(np.float32)))
        elif op < 0.72:
            victim = live.pop(int(rng.integers(len(live))))
            assert m.delete(victim)
        elif op < 0.82:  # forced compaction at a random point
            if rng.random() < 0.5:
                m.compact(force=True,
                          shard=int(rng.integers(num_shards)))
            else:
                m.compact(force=True)
            forced += 1
        else:
            meth = BACKENDS[int(rng.integers(len(BACKENDS)))]
            _assert_matches_oracle(m, q, k, meth, f"step{step}")
    assert forced > 0
    assert m.live_count == len(live)
    # heterogeneous shard states (delta-only vs multi-segment) must all
    # serve: every backend, bit-exact vs the union-live-set oracle
    for meth in BACKENDS:
        _assert_matches_oracle(m, q, k, meth, f"final-S{num_shards}")
    m.compact(force=True)
    for meth in BACKENDS:
        _assert_matches_oracle(m, q, k, meth, "post-compact")


@given_int_seed(max_examples=6, hi=2**31 - 1,
                fallback_seeds=(0, 1, 2, 1_322_705_291),
                examples=(1_322_705_291,))
def test_sharded_chaos_interleaving_exact_vs_oracle(seed):
    """Acceptance property: arbitrary insert/delete/query interleavings
    across 2-4 shards with forced compactions at random points are
    bit-exact vs brute force on the union live set, all four backends."""
    _sharded_chaos(seed)


@pytest.mark.parametrize("num_shards", [2, 4])
def test_pinned_epoch_vector_survives_mid_query_compaction(num_shards):
    """A pinned ShardedSnapshot answers identically while shards compact
    and churn underneath it -- the cross-shard forced-mid-query case."""
    from repro.core.balltree import normalize_query

    m = _mk(200, num_shards, seed=7, delta_capacity=8)
    for i in range(30):
        m.insert(_mkdata(1, seed=700 + i)[0])
    q = normalize_query(_mkdata(2, seed=71, dim=DIM + 1)).astype(np.float32)
    pinned = m.snapshot()
    d0, i0 = pinned.query(q, k=5)
    # churn + force a compaction on every shard mid-"query stream"
    for i in range(40):
        m.insert(_mkdata(1, seed=800 + i)[0])
    for g in range(0, 120, 3):
        m.delete(g)
    m.compact(force=True)
    assert not _epoch_leq(m.epoch, pinned.epoch)
    assert _epoch_leq(pinned.epoch, m.epoch)  # vector moved forward only
    d1, i1 = pinned.query(q, k=5)
    assert np.array_equal(d0, d1) and np.array_equal(i0, i1)
    # and the *new* pin reflects the deletes exactly
    _assert_matches_oracle(m, _mkdata(2, seed=71, dim=DIM + 1), 5, "sweep",
                           "fresh-pin")
    dead = {g for g in range(0, 120, 3)}
    assert not (dead & set(m.snapshot().live_points()[1].tolist()))


# ---------------------------------------------- fault injection / races
def test_compactor_kill_mid_build_never_tears_published_state(monkeypatch):
    """Kill shard 0's background compactor mid-build (twice): every
    snapshot published while the failure is in flight is consistent
    (epoch vector monotone, no duplicated/lost gids, oracle-exact), and
    ``run_with_restarts`` supervises the heal exactly like a restarted
    job restoring state."""
    import repro.stream.mutable as mutable_mod

    m = _mk(80, 2, seed=13, delta_capacity=8, background=True)
    try:
        real = mutable_mod.Segment.from_points
        poison = {"left": 2}

        def flaky(uid, points, gids, **kw):
            owners = {m.router.shard_of(int(g)) for g in np.asarray(gids)}
            if owners == {0} and poison["left"] > 0:
                poison["left"] -= 1
                raise RuntimeError("injected compactor kill (shard 0)")
            return real(uid, points, gids, **kw)

        monkeypatch.setattr(mutable_mod.Segment, "from_points", flaky)
        inserted = []
        surfaced = 0
        prev_epoch = m.epoch
        q = _mkdata(2, seed=14, dim=DIM + 1)
        for i in range(40):  # enough routed inserts to trip shard-0 builds
            x = _mkdata(1, seed=900 + i)[0]
            while True:
                try:
                    inserted.append(m.insert(x))
                    break
                except RuntimeError as e:
                    # a parked compactor error may legally surface at an
                    # insert that finds the delta full (documented wait
                    # point); the row was NOT inserted -- retry it
                    assert "injected" in str(e)
                    surfaced += 1
            snap = m.snapshot()
            # never torn: epochs only move forward, and the union live
            # set has no duplicated or phantom ids
            assert _epoch_leq(prev_epoch, snap.epoch), (prev_epoch,
                                                        snap.epoch)
            prev_epoch = snap.epoch
            gids = snap.live_points()[1]
            assert len(set(gids.tolist())) == len(gids)
            assert set(inserted) <= set(gids.tolist())
        # rows pinned by the killed builds are still live + queryable
        _assert_matches_oracle(m, q, 4, "sweep", "failure-in-flight")

        # supervised heal: wait_compaction re-raises the parked error,
        # the restart rebuilds "state" (re-pins the same index) and
        # retries until the poison budget is exhausted
        def heal(idx):
            idx.wait_compaction()
            idx.compact(force=True)
            return idx

        _, restarts = run_with_restarts(
            lambda: m, heal, policy=RetryPolicy(max_restarts=5))
        assert poison["left"] == 0  # both kills actually fired
        # every injected failure surfaced somewhere (insert wait point or
        # the supervised heal) and the index survived all of them
        assert surfaced + restarts >= 1
        for sh in m.shards:
            assert not sh._sealed  # no failure leftovers after heal
        assert set(inserted) <= set(m.snapshot().live_points()[1].tolist())
        _assert_matches_oracle(m, q, 4, "sweep", "post-heal")
    finally:
        m.close()


def test_raced_delete_reapplied_at_publish(monkeypatch):
    """A delete that lands while its shard's compactor is blocked
    mid-build must be re-applied to the built segment before it becomes
    visible -- the published snapshot never resurrects the row."""
    import repro.stream.mutable as mutable_mod

    m = _mk(60, 2, seed=17, delta_capacity=8, background=True)
    try:
        real = mutable_mod.Segment.from_points
        started = threading.Event()
        release = threading.Event()

        def slow(uid, points, gids, **kw):
            started.set()
            assert release.wait(timeout=30), "build never released"
            return real(uid, points, gids, **kw)

        monkeypatch.setattr(mutable_mod.Segment, "from_points", slow)
        inserted = []
        while not started.is_set():  # fill deltas until a build starts
            inserted.append(m.insert(_mkdata(1, seed=600
                                             + len(inserted))[0]))
            assert len(inserted) < 100, "no compaction ever started"
        # the build is pinned and blocked; delete rows it already copied
        victims = inserted[:3] + [1, 2]  # delta rows + bulk-loaded rows
        for v in victims:
            assert m.delete(v)
        release.set()
        m.wait_compaction()
        m.compact(force=True)  # fold everything (runs through slow too)
        m.wait_compaction()
        live = set(m.snapshot().live_points()[1].tolist())
        assert not (set(victims) & live), "raced delete resurrected"
        assert m.live_count == len(live)
        _assert_matches_oracle(m, _mkdata(2, seed=18, dim=DIM + 1), 4,
                               "sweep", "post-race")
    finally:
        release.set()
        m.close()


# ------------------------------------------- lambda-exchange invariant
def _exchange_invariant(seed):
    from repro.core.balltree import normalize_query

    rng = np.random.default_rng(seed)
    num_shards = 2 + seed % 3
    m = _mk(180, num_shards, seed=seed, delta_capacity=10)
    for i in range(40):  # churn: deltas + extra segments + tombstones
        m.insert(rng.normal(size=DIM).astype(np.float32))
    for g in range(0, 90, 4):
        m.delete(g)
    q = normalize_query(rng.normal(size=(4, DIM + 1))).astype(np.float32)
    snap = m.snapshot()
    for k in (1, 5):
        ed, _ = _oracle(snap, q, k)
        bd, bi, _, info = snap.query(q, k, return_counters=True,
                                     return_info=True)
        kth = ed[:, k - 1]
        tol = 1e-4 * np.abs(kth) + 1e-6
        # the validity proof: every shard's round-1 k-th is the distance
        # of k real points of that shard, so it upper-bounds the global
        # k-th; lambda0 (their min) therefore does too
        assert (info["round1_kth"] >= kth[None, :] - tol).all(), seed
        assert (info["lambda0"] >= kth - tol).all(), seed
        # and the round-2 merge under that cap is still exact
        np.testing.assert_allclose(bd, ed, rtol=1e-4, atol=1e-5)


@given_int_seed(max_examples=6, hi=2**31 - 1, fallback_seeds=(0, 1, 2))
def test_round1_caps_upper_bound_global_kth(seed):
    """Regression fence for the exchange generalization: per-shard
    round-1 caps are always >= the true global k-th distance."""
    _exchange_invariant(seed)


def test_stacked_round2_identical_to_sequential():
    """Regression fence for the segment-parallel exchange: round 2 run
    as one stacked launch under lambda0 returns the same ids (and
    distances at f32 matmul-association tolerance) as the sequential
    per-shard loop, and the exchange diagnostics (lambda0, round-1 caps)
    stay valid."""
    rng = np.random.default_rng(29)
    m = _mk(240, 3, seed=29, delta_capacity=10, max_segments=32)
    for i in range(80):  # churn: several segments per shard + tombstones
        m.insert(rng.normal(size=DIM).astype(np.float32))
    for g in range(0, 120, 4):
        m.delete(g)
    snap = m.snapshot()
    assert sum(len(s.segments) for s in snap.shards) >= 4
    q = rng.normal(size=(4, DIM + 1)).astype(np.float32)
    for k in (1, 6):
        sd, si, sinfo = m.query(q, k=k, stacked=False, return_info=True)
        td, ti, tinfo = m.query(q, k=k, stacked=True, return_info=True)
        # auto resolves by fan-out *and* grid density -- either schedule
        # may win on this state, but the answer must match one of them
        ad, ai = m.query(q, k=k)
        assert np.array_equal(ai, ti) or np.array_equal(ai, si)
        np.testing.assert_allclose(td, sd, rtol=1e-5, atol=1e-6)
        mism = ti != si
        if mism.any():
            # id disagreements must be rank-order ties: both schedules
            # computed the same candidate set, distances within one
            # matmul-association ulp of each other
            tol = 1e-5 * np.abs(sd) + 1e-6
            assert (np.abs(td - sd)[mism] <= tol[mism]).all(), (k, ti, si)
            for r in np.nonzero(mism.any(axis=1))[0]:
                assert (sorted(ti[r][mism[r]].tolist())
                        == sorted(si[r][mism[r]].tolist())), (k, ti, si)
        # round 1 is untouched by the round-2 schedule
        np.testing.assert_array_equal(tinfo["round1_kth"],
                                      sinfo["round1_kth"])
        np.testing.assert_array_equal(tinfo["lambda0"], sinfo["lambda0"])
        # per-shard k-th diagnostics (the lambda cache's per-shard
        # component) agree across schedules
        np.testing.assert_allclose(tinfo["shard_kth"], sinfo["shard_kth"],
                                   rtol=1e-5, atol=1e-6)
        # and both are exact vs the union oracle
        ed, _ = _oracle(snap, q, k)
        np.testing.assert_allclose(td, ed, rtol=1e-4, atol=1e-5)


def test_stacked_round1_caps_valid_mid_compaction(monkeypatch):
    """The round-1-cap >= global-kth invariant must hold when the shards
    are swept in one stacked launch while one of them is mid-compaction
    (serving from a sealed delta view)."""
    import repro.stream.mutable as mutable_mod

    from repro.core.balltree import normalize_query

    m = _mk(140, 2, seed=37, delta_capacity=8, background=True,
            max_segments=32)
    try:
        real = mutable_mod.Segment.from_points
        started = threading.Event()
        release = threading.Event()

        def slow(uid, points, gids, **kw):
            started.set()
            assert release.wait(timeout=30)
            return real(uid, points, gids, **kw)

        monkeypatch.setattr(mutable_mod.Segment, "from_points", slow)
        n = 0
        while not started.is_set():
            m.insert(_mkdata(1, seed=3000 + n)[0])
            n += 1
            assert n < 120
        comp = next(s for s, sh in enumerate(m.shards) if sh._compacting)
        m.shards[comp].insert(_mkdata(1, seed=3999)[0], gid=10**6)
        snap = m.snapshot()  # one shard mid-compaction right now
        assert any(len(s.deltas) > 1 for s in snap.shards)
        q = normalize_query(_mkdata(3, seed=38, dim=DIM + 1)).astype(
            np.float32)
        ed, _ = _oracle(snap, q, 4)
        bd, bi, _, info = snap.query(q, 4, stacked=True,
                                     return_counters=True,
                                     return_info=True)
        kth = ed[:, 3]
        assert (info["round1_kth"] >= kth[None, :] - 1e-5).all()
        assert (info["lambda0"] >= kth - 1e-5).all()
        np.testing.assert_allclose(bd, ed, rtol=1e-4, atol=1e-5)
        # identical to the sequential round 2 on the same pin
        sd, si, _ = snap.query(q, 4, stacked=False, return_counters=True)
        assert np.array_equal(bi, si)
        np.testing.assert_allclose(bd, sd, rtol=1e-5, atol=1e-6)
    finally:
        release.set()
        m.close()


def test_round1_caps_valid_against_mid_compaction_shard(monkeypatch):
    """The invariant must also hold when a shard is mid-compaction (its
    pinned snapshot serving from a sealed delta view)."""
    import repro.stream.mutable as mutable_mod

    from repro.core.balltree import normalize_query

    m = _mk(100, 2, seed=23, delta_capacity=8, background=True)
    try:
        real = mutable_mod.Segment.from_points
        started = threading.Event()
        release = threading.Event()

        def slow(uid, points, gids, **kw):
            started.set()
            assert release.wait(timeout=30)
            return real(uid, points, gids, **kw)

        monkeypatch.setattr(mutable_mod.Segment, "from_points", slow)
        n = 0
        while not started.is_set():
            m.insert(_mkdata(1, seed=1000 + n)[0])
            n += 1
            assert n < 100
        # the pin seals the delta without publishing; one write on the
        # compacting shard (fresh empty delta: cannot block) publishes
        # the sealed mid-compaction view into the next snapshot
        comp = next(s for s, sh in enumerate(m.shards) if sh._compacting)
        m.shards[comp].insert(_mkdata(1, seed=1999)[0], gid=10**6)
        snap = m.snapshot()  # one shard is mid-compaction right now
        assert any(len(s.deltas) > 1 for s in snap.shards), \
            "expected a sealed (mid-compaction) delta view"
        q = normalize_query(_mkdata(3, seed=24, dim=DIM + 1)).astype(
            np.float32)
        ed, _ = _oracle(snap, q, 3)
        bd, _, _, info = snap.query(q, 3, return_counters=True,
                                    return_info=True)
        kth = ed[:, 2]
        assert (info["round1_kth"] >= kth[None, :] - 1e-5).all()
        assert (info["lambda0"] >= kth - 1e-5).all()
        np.testing.assert_allclose(bd, ed, rtol=1e-4, atol=1e-5)
    finally:
        release.set()
        m.close()


# --------------------------------------------- serving / lambda cache
def test_engine_warm_bit_identical_and_per_shard_invalidation():
    from repro.serve import DispatchPolicy, P2HEngine

    m = _mk(400, 2, seed=31, delta_capacity=32)
    eng = P2HEngine(m, slot_size=4,
                    policy=DispatchPolicy(prefer_pallas=False))
    q = _mkdata(4, seed=32, dim=DIM + 1)
    d1, i1 = m.query(q, k=5, engine=eng)
    ed, eg = _oracle(m.snapshot(), q, 5)
    assert np.array_equal(i1, eg)
    d2, i2 = m.query(q, k=5, engine=eng)  # warm: bit-identical
    assert np.array_equal(i2, i1) and np.array_equal(d2, d1)
    assert eng.cache.stats()["hits"] >= 4
    # delete the current global top-1: its shard's component goes stale,
    # but the entry survives on the other shard's bound -- no whole-cache
    # eviction, and the warm answer stays exact (promoted neighbor found)
    victim = int(i2[0, 0])
    assert m.delete(victim)
    d3, i3 = m.query(q, k=5, engine=eng)
    ed3, eg3 = _oracle(m.snapshot(), q, 5)
    assert np.array_equal(i3, eg3)
    assert victim not in set(i3[0].tolist())
    st = eng.cache.stats()
    assert st["stale_evictions"] == 0, \
        "one shard's delete must not evict whole entries"
    assert st["hits"] >= 8


def test_lambda_cache_epoch_vector_semantics():
    from repro.serve.lambda_cache import LambdaCache, epoch_is_stale

    assert not epoch_is_stale(3, 3)
    assert epoch_is_stale(2, 3)
    assert not epoch_is_stale((4, 7), (4, 6))
    assert epoch_is_stale((4, 5), (4, 6))  # one stale component
    assert epoch_is_stale((4, 7), (4, 6, 1))  # shard layout changed
    assert epoch_is_stale(4, (4, 6))  # scalar vs vector

    cache = LambdaCache(DIM + 1, max_norm=2.0, n_bits=8)
    q = np.zeros((1, DIM + 1), np.float32)
    q[0, 0] = 1.0
    cache.update_sharded(q, 3, np.array([[0.5, 0.2]], np.float32),
                         epoch=(4, 7))
    # both components valid: cap uses the tighter shard bound
    cap = cache.lookup(q, 3, min_epoch=(0, 0))[0]
    assert 0.2 <= cap <= 0.21
    # delete in shard 1 (the tight one): cap falls back to shard 0's
    cap = cache.lookup(q, 3, min_epoch=(0, 8))[0]
    assert 0.5 <= cap <= 0.51
    assert cache.stats()["stale_evictions"] == 0
    # delete in both shards: the entry dies
    assert not np.isfinite(cache.lookup(q, 3, min_epoch=(5, 8))[0])
    assert cache.stats()["stale_evictions"] == 1
    # +inf components (fully-pruned far shard) never produce a bound
    cache.update_sharded(q, 3, np.array([[np.inf, 0.3]], np.float32),
                         epoch=(9, 9))
    cap = cache.lookup(q, 3, min_epoch=(9, 0))[0]
    assert 0.3 <= cap <= 0.31
    assert not np.isfinite(cache.lookup(q, 3, min_epoch=(9, 10))[0])


# ------------------------------------------------------------ persistence
def test_sharded_save_load_roundtrip(tmp_path):
    m = _mk(300, 3, seed=41, delta_capacity=16)
    for i in range(30):
        m.insert(_mkdata(1, seed=1100 + i)[0])
    for g in range(0, 80, 5):
        m.delete(g)
    q = _mkdata(3, seed=42, dim=DIM + 1)
    d1, i1 = m.query(q, k=6)
    steps = m.save(str(tmp_path / "ckpt"))
    assert len(steps) == 3
    m2 = ShardedMutableP2HIndex.load(str(tmp_path / "ckpt"))
    assert m2.num_shards == 3 and m2.epoch == m.epoch
    assert m2.live_count == m.live_count
    d2, i2 = m2.query(q, k=6)
    assert np.array_equal(i1, i2)
    np.testing.assert_allclose(d1, d2, rtol=1e-6)
    # id space survives: fresh inserts never collide, routing unchanged
    g = m2.insert(np.zeros(DIM, np.float32))
    assert g not in set(i1.ravel().tolist())
    assert m2.router.shard_of(g) == m.router.shard_of(g)
    assert m2.delete(int(i2[0, 0]))
    _assert_matches_oracle(m2, q, 6, "sweep", "post-restore")
    # future manifest versions are rejected
    from repro.checkpoint import read_json, write_json_atomic
    path = str(tmp_path / "ckpt" / "MANIFEST.json")
    manifest = read_json(path)
    manifest["version"] = 99
    write_json_atomic(path, manifest)
    with pytest.raises(ValueError, match="newer"):
        ShardedMutableP2HIndex.load(str(tmp_path / "ckpt"))
