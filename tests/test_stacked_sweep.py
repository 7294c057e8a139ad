"""Segment-parallel (stacked) sweep fence.

The stacked launch trades the sequentially-threaded per-segment lambda
cap for one device-side program under a single entry cap -- the headline
risk is correctness under that looser cap, and this suite is the fence:

  * kernel parity -- the stacked Pallas kernel (interpret=True) against
    its vmapped pure-jnp oracle, results *and* block-granular skip
    counters, across bound toggles and ragged padding edges (empty
    segment, single-point segment, all-tombstone segment);
  * exactness -- stacked results bit-exact (ids; distances at f32
    matmul tolerance) vs the sequential ``Snapshot.query`` walk and vs
    the brute-force oracle, across random insert/delete/compaction
    states of 1-8 ragged segments (hypothesis property with seeded
    fallback; a deterministic smoke subset runs in the fast lane, the
    property sweep in the ``stacked`` marker lane);
  * skip-counter parity -- the stacked launch's per-segment skip counts
    sum to >= the sequential path's on the same snapshot: its common
    padded grid force-skips every pad/dead tile it covers, which is what
    pays for the looser per-tile threshold (fewer *live*-tile skips) --
    the tradeoff is documented by the counters instead of silently
    regressing;
  * cache semantics -- the per-snapshot ``StackedLeaves`` memo is built
    once, reused across delta-only publishes, updated ids-plane-only on
    tombstone publishes (geometry shared), rebuilt after compaction;
  * dispatch -- ``DispatchPolicy`` folds segment fan-out and
    delta/tombstone density into the stacked crossover.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from _hyp import given_int_seed
from repro.core import exact_search
from repro.core.balltree import (append_ones, build_tree, built_leaves,
                                 normalize_query)
from repro.core.search import C_TILE_SKIP, merge_topk
from repro.kernels.ref import stacked_sweep_ref
from repro.kernels.stacked_sweep import (StackedLeaves,
                                         prepare_stacked_operands,
                                         stacked_sweep,
                                         stacked_sweep_search)
from repro.stream import CompactionPolicy, MutableP2HIndex
from test_stream import DIM, _assert_matches_oracle, _mkdata, _oracle


class _Seg:
    """Minimal segment stand-in (uid/tree/gids) for kernel-level tests."""

    def __init__(self, uid, raw, gids, *, n0=16, tombstone_all=False):
        self.uid = uid
        pts = append_ones(np.asarray(raw, np.float32))
        self.tree = build_tree(pts, n0=n0, append_one=False)
        if tombstone_all:
            import dataclasses

            pid = np.full_like(np.asarray(self.tree.point_ids), -1)
            self.tree = dataclasses.replace(self.tree, point_ids=pid)
        self.gids = np.asarray(gids, np.int32)
        self._raw = pts


def _ragged_segments(seed=0, *, n0=16):
    """Every padding edge in one stack: large, ragged, single-point,
    and all-tombstone segments."""
    rng = np.random.default_rng(seed)
    sizes = [200, 57, 1, 90, 40]
    segs, gid = [], 0
    for u, n in enumerate(sizes):
        raw = rng.normal(size=(n, DIM)).astype(np.float32)
        segs.append(_Seg(u, raw, np.arange(gid, gid + n), n0=n0,
                         tombstone_all=(u == len(sizes) - 1)))
        gid += n
    return segs


def _live_union(segs):
    pts, gids = [], []
    for s in segs:
        pid = np.asarray(s.tree.point_ids)
        rows = np.nonzero(pid >= 0)[0]
        pts.append(np.asarray(s.tree.points)[rows])
        gids.append(s.gids[pid[rows]])
    return np.concatenate(pts), np.concatenate(gids)


def _merged(bd, bi, k):
    N, B, _ = bd.shape
    return merge_topk(jnp.moveaxis(jnp.asarray(bd), 0, 1).reshape(B, N * k),
                      jnp.moveaxis(jnp.asarray(bi), 0, 1).reshape(B, N * k),
                      k)


# ------------------------------------------------- kernel-level parity
@pytest.mark.parametrize("use_ball,use_cone", [
    (False, False), (True, False), (False, True), (True, True)])
def test_stacked_kernel_matches_ref_with_padding_edges(use_ball, use_cone):
    """Kernel vs vmapped jnp oracle: same top-k, same per-segment
    block-granular skip counters, over a stack hitting every padding
    edge (ragged tile counts, single-point segment, all-tombstone
    segment -> every tile force-skipped)."""
    segs = _ragged_segments(seed=3)
    stk = StackedLeaves.from_segments(segs)
    q = normalize_query(_mkdata(9, seed=4, dim=DIM + 1))  # 9: pad path
    ops, B0 = prepare_stacked_operands(stk, jnp.asarray(q), bq=8,
                                       lane_pad=True)  # the TPU shape
    kd, ki, ks, _ = stacked_sweep(**ops, k=5, use_ball=use_ball,
                                  use_cone=use_cone, interpret=True)
    rd, ri, rs = stacked_sweep_ref(**ops, k=5, use_ball=use_ball,
                                   use_cone=use_cone)
    np.testing.assert_allclose(np.sort(np.asarray(kd), axis=2),
                               np.sort(np.asarray(rd), axis=2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ks), np.asarray(rs))
    # the all-tombstone segment's tiles are all force-skipped
    dead = len(segs) - 1
    assert (np.asarray(ks)[dead] == stk.num_tiles).all()


def test_stacked_search_exact_vs_bruteforce_and_entry_cap():
    """Merged stacked top-k == brute force on the live union, on both
    implementations; a valid external entry cap must not change it."""
    segs = _ragged_segments(seed=5)
    stk = StackedLeaves.from_segments(segs)
    X, G = _live_union(segs)
    q = normalize_query(_mkdata(6, seed=6, dim=DIM + 1))
    k = 7
    ed, ei = exact_search(jnp.asarray(X), jnp.asarray(q), k=k)
    ed, eg = np.asarray(ed), G[np.asarray(ei)]
    for use_kernel in (False, True):
        bd, bi, cnt, seg_skips = stacked_sweep_search(
            stk, jnp.asarray(q), k, use_kernel=use_kernel)
        fd, fi = _merged(bd, bi, k)
        np.testing.assert_allclose(np.asarray(fd), ed, rtol=1e-4,
                                   atol=1e-5)
        assert np.array_equal(np.asarray(fi), eg)
        assert int(np.asarray(seg_skips).sum()) == int(
            np.asarray(cnt)[C_TILE_SKIP])
        # valid entry cap (1.5x the true k-th): same answers, more skips
        cap = jnp.asarray(ed[:, -1] * 1.5 + 1e-3)
        cd, ci, ccnt, _ = stacked_sweep_search(
            stk, jnp.asarray(q), k, lambda_cap=cap, use_kernel=use_kernel)
        fcd, fci = _merged(cd, ci, k)
        np.testing.assert_allclose(np.asarray(fcd), ed, rtol=1e-4,
                                   atol=1e-5)
        assert np.array_equal(np.asarray(fci), eg)
        assert (np.asarray(ccnt)[C_TILE_SKIP]
                >= np.asarray(cnt)[C_TILE_SKIP])


# ------------------------------------- the insertion loop's trip count
def _insert_case_segments(case, seed):
    """A stack for one edge case of the kernel's top-k insertion loop."""
    if case == "dead_tiles":  # ragged pad tiles, an all-tombstone segment
        return _ragged_segments(seed=seed)
    rng = np.random.default_rng(seed)
    if case == "ties":  # every point four times: ties at every rank
        raws = [np.repeat(rng.normal(size=(n, DIM)), 4, axis=0)
                for n in (40, 25)]
    else:
        raws = [rng.normal(size=(n, DIM)) for n in (150, 110, 90)]
    if case == "short_tiles":  # tiles with fewer live points than k
        raws.append(rng.normal(size=(1, DIM)))
    segs, gid = [], 0
    for u, raw in enumerate(raws):
        n = len(raw)
        segs.append(_Seg(u, raw.astype(np.float32), np.arange(gid, gid + n)))
        gid += n
    if case == "short_tiles":  # all but every 7th point of one segment
        import dataclasses

        t = segs[1].tree
        pid = np.asarray(t.point_ids)
        segs[1].tree = dataclasses.replace(
            t, point_ids=np.where(pid % 7 == 0, pid, -1))
    return segs


@pytest.mark.parametrize("case", ["cold", "short_tiles", "dead_tiles",
                                  "ties", "refed"])
@pytest.mark.parametrize("k", [1, 10, 50])
def test_insert_loop_runs_only_the_steps_that_can_change_the_topk(
        monkeypatch, k, case):
    """The kernel's insertion loop runs ``_insert_trips`` steps instead of
    k.  Per-segment planes and skip counts are bit-identical to the
    fixed k-step loop's, the merged answers equal brute force, and the
    loop runs at most k steps per scanned tile -- none when the seed
    already holds each segment's top-k and its points are gone from the
    tiles (pass B after a probe that found every winner)."""
    from repro.kernels import stacked_sweep as sweep_mod

    segs = _insert_case_segments(case, seed=11)
    stk = StackedLeaves.from_segments(segs)
    q = normalize_query(_mkdata(9, seed=12, dim=DIM + 1))
    ops, B0 = prepare_stacked_operands(stk, jnp.asarray(q), bq=8,
                                       lane_pad=True)
    seed = {}
    if case == "refed":
        d0, i0, _, _ = stacked_sweep(**ops, k=k, interpret=True)
        held = np.isin(np.asarray(ops["ids_tiles"]), np.asarray(i0))
        ops = dict(ops, ids_tiles=jnp.where(held, -1, ops["ids_tiles"]))
        seed = dict(seed_d=d0, seed_i=i0)

    def sweep():
        return [np.asarray(a) for a in stacked_sweep(
            **ops, k=k, interpret=True, **seed)]

    d, i, skips, steps = sweep()
    with monkeypatch.context() as mp:  # the fixed k-step loop
        mp.setattr(sweep_mod, "_insert_trips", lambda cand, kth, k: k)
        fd, fi, fskips, fsteps = sweep()
    np.testing.assert_array_equal(d, fd)
    np.testing.assert_array_equal(i, fi)
    np.testing.assert_array_equal(skips, fskips)
    scan, ins = steps.sum(axis=(0, 1))
    fscan, fins = fsteps.sum(axis=(0, 1))
    assert scan == fscan > 0
    assert fins == k * fscan  # the fixed loop did run k steps a tile
    assert 0 <= ins <= k * scan
    if case == "refed":
        assert ins == 0
        np.testing.assert_array_equal(d, np.asarray(d0))
        np.testing.assert_array_equal(i, np.asarray(i0))
    X, G = _live_union(segs)
    ed, _ = exact_search(jnp.asarray(X), jnp.asarray(q), k=k)
    md, mi = (np.asarray(a) for a in _merged(d[:, :B0], i[:, :B0], k))
    np.testing.assert_allclose(md, np.asarray(ed), rtol=1e-4, atol=1e-5)
    # returned ids are live, distinct, and carry their own distances
    row_of = {int(g): r for r, g in enumerate(G)}
    for r in range(B0):
        assert len(set(mi[r].tolist())) == k
        true = np.abs(X[[row_of[int(g)] for g in mi[r]]] @ q[r])
        np.testing.assert_allclose(md[r], true, rtol=1e-4, atol=1e-5)


def test_stacked_query_records_scan_and_insert_steps():
    """The launch's scan and insertion counts reach the span counters on
    the kernel path, through the read the launch already makes; the jnp
    twin counts nothing."""
    from repro.kernels.stacked_sweep import stacked_sweep_query
    from repro.runtime import spans

    segs = _insert_case_segments("cold", seed=13)
    stk = StackedLeaves.from_segments(segs)
    q = jnp.asarray(normalize_query(_mkdata(9, seed=14, dim=DIM + 1)))
    k = 10
    spans.reset()
    try:
        stacked_sweep_query(stk, q, k, use_kernel=False)
        assert "stacked_scan_steps" not in spans.snapshot()["counters"]
        stacked_sweep_query(stk, q, k, use_kernel=True, interpret=True)
        c = spans.snapshot()["counters"]
    finally:
        spans.reset()
    assert c["stacked_scan_steps"] > 0
    assert 0 < c["stacked_insert_steps"] <= k * c["stacked_scan_steps"]


def test_stacked_concat_repads_mixed_tile_grids():
    """Cross-shard round 2 concatenates stacks with different tile
    counts; the smaller grid is re-padded and answers stay exact."""
    rng = np.random.default_rng(11)
    a = [_Seg(0, rng.normal(size=(40, DIM)), np.arange(0, 40)),
         _Seg(1, rng.normal(size=(30, DIM)), np.arange(40, 70))]
    b = [_Seg(2, rng.normal(size=(220, DIM)), np.arange(70, 290))]
    sa, sb = StackedLeaves.from_segments(a), StackedLeaves.from_segments(b)
    assert sa.num_tiles != sb.num_tiles  # genuinely mixed grids
    comb = StackedLeaves.concat([sa, sb])
    assert comb.num_segments == 3
    assert comb.num_tiles == max(sa.num_tiles, sb.num_tiles)
    assert comb.uids == (0, 1, 2)
    X, G = _live_union(a + b)
    q = normalize_query(_mkdata(4, seed=12, dim=DIM + 1))
    ed, ei = exact_search(jnp.asarray(X), jnp.asarray(q), k=5)
    bd, bi, _, _ = stacked_sweep_search(comb, jnp.asarray(q), 5,
                                        use_kernel=False)
    fd, fi = _merged(bd, bi, 5)
    np.testing.assert_allclose(np.asarray(fd), np.asarray(ed), rtol=1e-4,
                               atol=1e-5)
    assert np.array_equal(np.asarray(fi), G[np.asarray(ei)])


# ------------------------------------------ snapshot-level smoke fence
def _mk_fanned(seed, *, chunks=6, chunk=40):
    """A mutable index with ``chunks`` roughly even sealed segments
    (chunked bulk loads -> a dense stacked grid the policy promotes)
    plus a few live delta rows and light tombstones."""
    rng = np.random.default_rng(seed)
    data = _mkdata(chunks * chunk, seed=seed)
    m = MutableP2HIndex.from_data(
        data[:chunk], n0=16,
        policy=CompactionPolicy(delta_capacity=chunk, tombstone_frac=0.95,
                                max_segments=64))
    for c in range(1, chunks):  # each full delta flushes into a segment
        m.insert_batch(data[c * chunk:(c + 1) * chunk])
    for _ in range(5):
        m.insert(rng.normal(size=DIM).astype(np.float32))
    for g in range(0, chunks * chunk, 9):
        m.delete(g)
    return m


def _check_stacked_matches_sequential(m, q, k, tag=""):
    """Stacked vs sequential vs oracle on the current snapshot: same
    ids (ties resolved identically through merge_topk's id-primary
    ordering), distances at f32 matmul-association tolerance."""
    snap = m.snapshot()
    sd, si = m.query(q, k=k, stacked=False)
    td, ti = m.query(q, k=k, stacked=True)
    np.testing.assert_allclose(td, sd, rtol=1e-5, atol=1e-6,
                               err_msg=f"stacked-vs-seq {tag}")
    if not np.array_equal(ti, si):
        # id disagreements must be exact-distance ties
        mism = ti != si
        tol = 1e-5 * np.abs(sd) + 1e-6
        assert (np.abs(td - sd)[mism] <= tol[mism]).all(), (tag, ti, si)
    _assert_matches_oracle(m, q, k, "sweep", f"{tag}-seq")
    # and the stacked path itself against the oracle
    ed, eg = _oracle(snap, q, k)
    np.testing.assert_allclose(td, ed, rtol=1e-4, atol=1e-5,
                               err_msg=f"stacked-vs-oracle {tag}")


def test_stacked_smoke_deterministic():
    """Fast-lane smoke: one churned multi-segment state, stacked ==
    sequential == oracle for k in {1, 5}, plus the method="stacked" and
    auto-promotion spellings."""
    m = _mk_fanned(17)
    assert len(m.snapshot().segments) >= 4
    q = _mkdata(4, seed=18, dim=DIM + 1)
    for k in (1, 5):
        _check_stacked_matches_sequential(m, q, k, f"smoke-k{k}")
    d1, i1 = m.query(q, k=5, method="stacked")
    d2, i2 = m.query(q, k=5)  # fan-out >= 4: auto-promoted
    d3, i3 = m.query(q, k=5, stacked=True)
    assert np.array_equal(i1, i3) and np.array_equal(i2, i3)
    np.testing.assert_allclose(d1, d3, rtol=1e-6)
    np.testing.assert_allclose(d2, d3, rtol=1e-6)


# ------------------------------------------------ the property fence
def _stacked_property(seed):
    rng = np.random.default_rng(seed)
    m = MutableP2HIndex.from_data(
        _mkdata(100, seed=seed), n0=32,
        policy=CompactionPolicy(delta_capacity=6 + seed % 7,
                                tombstone_frac=0.95, max_segments=64))
    live = list(range(100))
    q = rng.normal(size=(3, DIM + 1)).astype(np.float32)
    k = 5
    checks = 0
    for step in range(50):
        op = rng.random()
        snap = m.snapshot()
        if op < 0.4 or not live:
            live.append(m.insert(rng.normal(size=DIM).astype(np.float32)))
        elif op < 0.6:
            victim = live.pop(int(rng.integers(len(live))))
            assert m.delete(victim)
        elif op < 0.7 and snap.segments:
            # tombstone an entire random segment -> empty-segment edge
            seg = snap.segments[int(rng.integers(len(snap.segments)))]
            pid = np.asarray(seg.tree.point_ids)
            for gid in seg.gids[pid[pid >= 0]]:
                if m.delete(int(gid)):
                    live.remove(int(gid))
        elif op < 0.78:
            m.compact(force=True)  # collapse to one segment
        else:
            _check_stacked_matches_sequential(m, q, k, f"step{step}")
            checks += 1
    # churn may tombstone every segment away (seed 3): an empty segment
    # list is a legal state, and the final checks must hold there too
    assert 0 <= len(m.snapshot().segments) <= 64
    for k2 in (1, 5):
        _check_stacked_matches_sequential(m, q, k2, f"final-k{k2}")
    m.compact(force=True)
    _check_stacked_matches_sequential(m, q, k, "post-compact")


@pytest.mark.stacked
@given_int_seed(max_examples=6, hi=2**31 - 1, fallback_seeds=(0, 1, 2, 3),
                examples=(3,))
def test_stacked_property_exact_vs_sequential_and_oracle(seed):
    """Acceptance property (stacked lane): random insert / delete /
    whole-segment-tombstone / compaction interleavings leave the stacked
    sweep exact vs the sequential walk and the brute-force oracle."""
    _stacked_property(seed)


# ------------------------------------------------- skip-count fences
def _clustered(n, seed, dim=DIM, n_clusters=12, scale=3.0):
    """Clustered base data: tight leaf balls -> node bounds that
    actually prune, so live-tile skips are non-trivial on both
    schedules (pure isotropic noise skips ~nothing either way)."""
    rng = np.random.default_rng(seed)
    c = rng.normal(size=(n_clusters, dim)) * scale
    return (c[rng.integers(0, n_clusters, n)]
            + rng.normal(size=(n, dim))).astype(np.float32)


def _mk_churned_clustered(seed, *, chunks=6, chunk=120, n0=16):
    """A property-suite-shaped churn state (several sealed segments +
    live delta rows + tombstones) over clustered data."""
    rng = np.random.default_rng(seed)
    data = _clustered(chunks * chunk, seed)
    m = MutableP2HIndex.from_data(
        data[:chunk], n0=n0,
        policy=CompactionPolicy(delta_capacity=chunk, tombstone_frac=0.95,
                                max_segments=64))
    for c in range(1, chunks):
        m.insert_batch(data[c * chunk:(c + 1) * chunk])
    for _ in range(5):
        m.insert(rng.normal(size=DIM).astype(np.float32))
    for g in range(0, chunks * chunk, 9):
        m.delete(g)
    return m


def _live_skip_stats(snap, q, k, probe_tiles):
    """Two-pass stacked live-tile skips at per-query granularity (bq=1),
    on the serving route's exact state (delta entry cap + extra
    candidates seeding the in-launch global top-k, via the same
    ``Snapshot.delta_candidates`` the serving path uses)."""
    from repro.kernels.stacked_sweep import stacked_sweep_query

    bd, bi, _ = snap.delta_candidates(jnp.asarray(q), k)
    fd, fi, cnt, info = stacked_sweep_query(
        snap.stacked_leaves(), jnp.asarray(q), k, bq=1,
        lambda_cap=bd[:, k - 1], probe_tiles=probe_tiles,
        extra_d=bd, extra_i=bi)
    live = int(np.asarray(info["seg_skips"]).sum()
               - np.asarray(info["forced_skips"]).sum())
    return live, (np.asarray(fd), np.asarray(fi)), info


@pytest.mark.parametrize("seed", [0, 2, 17, 41])
def test_two_pass_live_skips_dominate_sequential(seed):
    """Regression fence (the inverted PR-4 dominance tradeoff): the
    two-pass stacked program's *live*-tile skips -- forced pad/dead
    skips excluded -- are >= the sequential cap-threaded walk's skips on
    property-suite-shaped churn states, at matching per-query
    granularity.  The probe pass + the in-launch global top-k are what
    buy this: seed 17 is a state where the single-pass (probe_tiles=0)
    form still loses to sequential, so the fence pins the two-pass
    default, not a structural pad-tile artifact."""
    m = _mk_churned_clustered(seed)
    snap = m.snapshot()
    assert sum(1 for s in snap.segments if s.live) >= 4
    q = normalize_query(
        np.random.default_rng(seed + 100)
        .normal(size=(6, DIM + 1)).astype(np.float32))
    k = 5
    _, _, seq_cnt = snap.query(q, k, stacked=False, return_counters=True)
    seq_skips = int(np.asarray(seq_cnt)[C_TILE_SKIP])
    live, (fd, fi), _ = _live_skip_stats(snap, q, k, probe_tiles=None)
    assert live >= seq_skips, (live, seq_skips)
    # and the two-pass answers stay exact vs the sequential route
    sd, si = snap.query(q, k, stacked=False)
    np.testing.assert_allclose(fd, sd, rtol=1e-5, atol=1e-6)
    mism = fi != si
    if mism.any():  # id disagreements must be exact-distance ties
        tol = 1e-5 * np.abs(sd) + 1e-6
        assert (np.abs(fd - sd)[mism] <= tol[mism]).all()


def test_stacked_total_skips_account_every_tile():
    """The stacked launch covers a common padded tile grid: per-segment
    skip counts sum to the total counter, pad/dead tiles are always
    force-skipped (they are part of the launch), and raggedness (empty +
    single-point segments) makes the forced share dominate here."""
    segs = _ragged_segments(seed=21)
    stk = StackedLeaves.from_segments(segs)
    q = normalize_query(_mkdata(8, seed=22, dim=DIM + 1))
    k = 5
    td, ti, cnt_stk, seg_skips = stacked_sweep_search(
        stk, jnp.asarray(q), k, use_kernel=True)
    stacked_skips = int(np.asarray(seg_skips).sum())
    assert stacked_skips == int(np.asarray(cnt_stk)[C_TILE_SKIP])
    # every invalid (pad/dead) tile is skipped for every query block
    n_invalid = int((~np.asarray(stk.valid)).sum())
    assert stacked_skips >= n_invalid  # 8 queries = one block
    dead = len(segs) - 1  # the all-tombstone segment: all tiles forced
    assert (np.asarray(seg_skips)[dead] == stk.num_tiles).all()


# ------------------------------------------- device merge_topk parity
def test_merge_topk_planes_device_matches_host():
    """The in-launch merge and the host exchange share one function:
    jitted ``merge_topk_planes`` must be bit-identical to an eager
    ``merge_topk`` over the flattened planes, including the id-primary
    tiebreak and duplicate-id masking (repeats keep their smallest
    distance) and the extra-candidate path."""
    import jax

    from repro.core.search import merge_topk_planes

    rng = np.random.default_rng(81)
    N, B, k = 4, 5, 6
    dists = rng.uniform(0.1, 3.0, (N, B, k)).astype(np.float32)
    ids = rng.integers(0, 40, (N, B, k)).astype(np.int32)  # many dups
    # inject exact distance ties across sources + invalid slots
    dists[1] = dists[0]
    ids[1, :, :3] = ids[0, :, :3]  # dup ids with equal dists
    ids[2, :, 0] = -1
    dists[2, :, 0] = np.inf
    extra_d = rng.uniform(0.1, 3.0, (B, 3)).astype(np.float32)
    extra_i = rng.integers(0, 40, (B, 3)).astype(np.int32)
    flat_d = np.moveaxis(dists, 0, 1).reshape(B, N * k)
    flat_i = np.moveaxis(ids, 0, 1).reshape(B, N * k)
    hd, hi = merge_topk(jnp.asarray(np.concatenate([flat_d, extra_d], 1)),
                        jnp.asarray(np.concatenate([flat_i, extra_i], 1)),
                        k)
    dd, di = jax.jit(merge_topk_planes, static_argnames=("k",))(
        jnp.asarray(dists), jnp.asarray(ids), k=k,
        extra_d=jnp.asarray(extra_d), extra_i=jnp.asarray(extra_i))
    np.testing.assert_array_equal(np.asarray(dd), np.asarray(hd))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(hi))
    # a repeated id must keep only its smallest distance
    best = {}
    for src in range(N):
        for col in range(k):
            i_, d_ = int(ids[src, 0, col]), float(dists[src, 0, col])
            if i_ >= 0:
                best[i_] = min(best.get(i_, np.inf), d_)
    for col in range(3):
        best[int(extra_i[0, col])] = min(
            best.get(int(extra_i[0, col]), np.inf),
            float(extra_d[0, col]))
    for rank in range(k):
        if int(di[0, rank]) >= 0:
            assert float(dd[0, rank]) == best[int(di[0, rank])]


def test_stacked_query_shard_bounds_kths():
    """``shard_bounds`` reduces per-shard merged k-ths inside the device
    program: each row must equal the host-side merge of that shard's
    plane slice, and upper-bound the shard's true local k-th."""
    from repro.core.search import merge_topk_planes
    from repro.kernels.stacked_sweep import stacked_sweep_query

    segs = _ragged_segments(seed=77)
    stk = StackedLeaves.from_segments(segs)
    q = normalize_query(_mkdata(4, seed=78, dim=DIM + 1))
    k = 5
    bounds = (2, 3)  # segments per "shard", in stack order
    _, _, _, info = stacked_sweep_query(stk, jnp.asarray(q), k,
                                        shard_bounds=bounds,
                                        use_kernel=False)
    sd, sg, _, _ = stacked_sweep_search(stk, jnp.asarray(q), k,
                                        use_kernel=False)
    off = 0
    for row, ns in enumerate(bounds):
        hd, _ = merge_topk_planes(sd[off:off + ns], sg[off:off + ns], k)
        np.testing.assert_allclose(
            np.asarray(info["shard_kth"])[row], np.asarray(hd)[:, k - 1],
            rtol=1e-6, atol=1e-7)
        X, G = _live_union(segs[off:off + ns])
        kk = min(k, len(X))
        if kk:
            ed, _ = exact_search(jnp.asarray(X), jnp.asarray(q), k=kk)
            assert (np.asarray(info["shard_kth"])[row]
                    >= np.asarray(ed)[:, kk - 1] - 1e-5).all()
        off += ns


def test_padded_pts_cache_shared_across_tombstone_update():
    """The stack's derived probe operands (the lane-padded points plane)
    are cached and survive ids-plane-only updates -- geometry is shared,
    so the pad copy is paid once per compaction, not per query."""
    segs = _ragged_segments(seed=79)
    stk = StackedLeaves.from_segments(segs)
    padded = stk.padded_pts()
    assert padded is stk.padded_pts()  # memoized
    assert padded.shape[-1] % 128 == 0
    stk2 = stk.with_updated_ids({0: segs[0]})
    assert stk2.padded_pts() is padded  # derived cache rides along
    # concat builds a fresh grid: fresh cache, same pad invariant
    comb = StackedLeaves.concat([stk, stk])
    assert comb.padded_pts().shape[-1] % 128 == 0


# -------------------------------------------- density signal freshness
def test_tile_density_reads_current_ids_planes():
    """Stale-density regression fence: ``tile_density`` must be
    computed from the segments' *current* ids planes, not build-time
    geometry -- an ids-plane-only tombstone publish (geometry shared)
    degrades the dispatch signal exactly like build-time raggedness."""
    from repro.kernels.stacked_sweep import tile_density

    # tombstone_frac > 1: a fully-dead segment must NOT trigger a
    # rewrite, so the publish stays ids-plane-only (the stale path)
    data = _mkdata(6 * 40, seed=91)
    m = MutableP2HIndex.from_data(
        data[:40], n0=16,
        policy=CompactionPolicy(delta_capacity=40, tombstone_frac=2.0,
                                max_segments=64))
    for c in range(1, 6):
        m.insert_batch(data[c * 40:(c + 1) * 40])
    snap0 = m.snapshot()
    stk0 = snap0.stacked_leaves()
    d0 = tile_density(snap0.segments)
    # tombstone one entire segment (ids-plane-only publish)
    seg = max(snap0.segments, key=lambda s: s.live)
    pid = np.asarray(seg.tree.point_ids)
    for gid in seg.gids[pid[pid >= 0]]:
        assert m.delete(int(gid))
    snap1 = m.snapshot()
    # geometry is shared (the adopt path swapped only ids planes) ...
    stk1 = snap1.stacked_leaves()
    assert stk1.pts is stk0.pts
    # ... yet the density signal must drop: a whole segment's tiles are
    # now dead weight the stacked launch force-skips like pad tiles
    d1 = tile_density(snap1.segments)
    assert d1 < d0, (d1, d0)
    live_tiles = sum((np.asarray(s.tree.point_ids).reshape(
        s.tree.num_leaves, s.tree.n0) >= 0).any(axis=1).sum()
        for s in snap1.segments)
    # denominator excludes pad_tree_leaves quantization pads: they are
    # compile-shape waste, not raggedness (see tile_density docstring)
    grid = (len(snap1.segments)
            * max(built_leaves(s.tree) for s in snap1.segments))
    assert d1 == pytest.approx(live_tiles / grid)


def test_dispatch_policy_probe_tiles_knob():
    """The policy's probe_tiles knob rides the stacked route."""
    from repro.serve import DispatchPolicy

    pol = DispatchPolicy(prefer_pallas=False, probe_tiles=7)
    r = pol.route(8, 5, segments=5, stackable=4)
    assert r.method == "stacked" and r.probe_tiles == 7
    # default: the library resolves None to STACKED_PROBE_TILES_DEFAULT
    r2 = DispatchPolicy(prefer_pallas=False).route(8, 5, segments=5,
                                                   stackable=4)
    assert r2.method == "stacked" and r2.probe_tiles is None
    from repro.kernels.stacked_sweep import (STACKED_PROBE_TILES_DEFAULT,
                                             resolve_probe_tiles)

    assert resolve_probe_tiles(None, 100) == STACKED_PROBE_TILES_DEFAULT
    assert resolve_probe_tiles(None, 2) == 2  # clamped to the visit list
    assert resolve_probe_tiles(9, 4) == 4
    assert resolve_probe_tiles(0, 4) == 0


# -------------------------------------------------- cache semantics
def test_stacked_cache_adopted_updated_and_rebuilt():
    m = _mk_fanned(31)
    snap0 = m.snapshot()
    stk0 = snap0.stacked_leaves()
    assert stk0 is snap0.stacked_leaves()  # memoized
    # delta-only publish: the very same stack object is carried forward
    m.insert(np.zeros(DIM, np.float32))
    snap1 = m.snapshot()
    assert snap1.__dict__.get("_stacked") is stk0
    # tombstone publish: the ids-plane swap is DEFERRED to the first
    # read (the delete path is O(tombstone flip); no device dispatch
    # under the writer lock) -- geometry arrays shared once applied
    seg = next(s for s in snap1.segments if s.live)
    pid = np.asarray(seg.tree.point_ids)
    victim = int(seg.gids[pid[pid >= 0][0]])
    seg_uids = tuple(s.uid for s in snap1.segments)
    assert m.delete(victim)
    snap2 = m.snapshot()
    assert snap2.__dict__.get("_stacked") is None  # lazy: not yet built
    assert snap2.__dict__.get("_stacked_base") is stk0
    stk2 = snap2.stacked_leaves()
    assert stk2 is snap2.stacked_leaves()  # memoized once applied
    assert stk2 is not stk0
    assert stk2.pts is stk0.pts and stk2.rx is stk0.rx
    assert stk2.uids == seg_uids
    assert victim not in set(np.asarray(stk2.ids).ravel().tolist())
    # compaction changes the segment set: memo dropped, rebuilt lazily
    m.compact(force=True)
    snap3 = m.snapshot()
    assert snap3.__dict__.get("_stacked") is None
    stk3 = snap3.stacked_leaves()
    assert stk3.num_segments == len(snap3.segments) == 1
    # the adopted/updated stack answers exactly
    q = _mkdata(3, seed=32, dim=DIM + 1)
    _check_stacked_matches_sequential(m, q, 4, "post-rebuild")


# ------------------------------------------------------- dispatch
def test_dispatch_policy_stacked_crossover():
    from repro.serve import DispatchPolicy

    pol = DispatchPolicy(prefer_pallas=False)
    # fan-out below threshold: unchanged routing
    assert pol.route(8, 5, segments=3, stackable=2).method == "sweep"
    assert pol.route(1, 5, segments=2, stackable=1).method == "dfs"
    # fan-out at/above threshold: stacked
    assert pol.route(8, 5, segments=5, stackable=4).method == "stacked"
    assert pol.route(1, 5, segments=9, stackable=8).method == "stacked"
    # tombstone-heavy snapshots cross over one segment earlier
    assert pol.route(8, 5, segments=4, stackable=3,
                     tombstone_frac=0.5).method == "stacked"
    # delta-heavy snapshots cross over later
    assert pol.route(8, 5, segments=5, stackable=4,
                     delta_frac=0.8).method != "stacked"
    assert pol.route(8, 5, segments=7, stackable=6,
                     delta_frac=0.8).method == "stacked"
    # recall / sharded routes still take precedence
    assert pol.route(8, 5, 0.9, stackable=8).method == "beam"
    assert pol.route(8, 5, sharded=True, stackable=8).method == "sharded"


def test_engine_policy_overrides_library_auto_promotion():
    """The policy owns the stacked decision on the engine path: a
    policy whose knobs resolve to a sequential route must actually get
    the sequential schedule (the engine forwards stacked=False, so the
    snapshot's own fan-out default cannot silently override it) -- and
    stay exact."""
    from repro.serve import DispatchPolicy, P2HEngine

    m = _mk_fanned(51)  # fan-out 6: the library default would stack
    eng = P2HEngine(m, slot_size=4,
                    policy=DispatchPolicy(prefer_pallas=False,
                                          stacked_min_fanout=99))
    q = _mkdata(4, seed=52, dim=DIM + 1)
    d1, i1 = m.query(q, k=5, engine=eng)
    assert "stacked" not in eng.stats()["routes"], eng.stats()["routes"]
    ed, eg = _oracle(m.snapshot(), q, 5)
    assert np.array_equal(i1, eg)


# ------------------------------------------- two-pass probe exactness
@pytest.mark.parametrize("use_kernel", [False, True])
def test_probe_degenerate_endpoints(use_kernel):
    """``probe_tiles=0`` is the single-pass sweep (PR-4's schedule --
    answers identical; only the in-launch global threading's skip
    counters improved on it) and ``probe_tiles >= L`` makes the probe
    pass the full sweep: both endpoints must produce identical planes
    and skip counts, and exact merged answers."""
    segs = _ragged_segments(seed=51)
    stk = StackedLeaves.from_segments(segs)
    X, G = _live_union(segs)
    q = normalize_query(_mkdata(5, seed=52, dim=DIM + 1))
    k = 6
    ed, ei = exact_search(jnp.asarray(X), jnp.asarray(q), k=k)
    ed, eg = np.asarray(ed), G[np.asarray(ei)]
    d0, i0, c0, s0 = stacked_sweep_search(stk, jnp.asarray(q), k,
                                          probe_tiles=0,
                                          use_kernel=use_kernel)
    dL, iL, cL, sL = stacked_sweep_search(stk, jnp.asarray(q), k,
                                          probe_tiles=10 ** 6,
                                          use_kernel=use_kernel)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(dL))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(iL))
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(sL))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(cL))
    for dd, ii in ((d0, i0), (dL, iL)):
        fd, fi = _merged(dd, ii, k)
        np.testing.assert_allclose(np.asarray(fd), ed, rtol=1e-4,
                                   atol=1e-5)
        assert np.array_equal(np.asarray(fi), eg)


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("probe", [1, 3])
def test_probe_seeded_pass_never_rescans(use_kernel, probe):
    """No-rescan invariant of the seeded handoff: pass B resumes from
    pass A's per-segment top-k over a *disjoint* visit suffix, so no
    per-(segment, query) plane may hold a duplicate live id (the kernel
    has no dedup -- a rescan of a probed tile would surface its points
    twice) -- and the two-pass result stays exact."""
    segs = _ragged_segments(seed=61)
    stk = StackedLeaves.from_segments(segs)
    X, G = _live_union(segs)
    q = normalize_query(_mkdata(4, seed=62, dim=DIM + 1))
    k = 6
    bd, bi, cnt, _ = stacked_sweep_search(stk, jnp.asarray(q), k,
                                          probe_tiles=probe,
                                          use_kernel=use_kernel)
    ids = np.asarray(bi)  # (N, B, k)
    for s in range(ids.shape[0]):
        for b in range(ids.shape[1]):
            row = ids[s, b][ids[s, b] >= 0]
            assert len(set(row.tolist())) == len(row), (s, b, row)
    ed, ei = exact_search(jnp.asarray(X), jnp.asarray(q), k=k)
    fd, fi = _merged(bd, bi, k)
    np.testing.assert_allclose(np.asarray(fd), np.asarray(ed),
                               rtol=1e-4, atol=1e-5)
    assert np.array_equal(np.asarray(fi), G[np.asarray(ei)])
    # probe accounting: the probe pass covers exactly p tiles per
    # (segment, block) -- scanned + skipped must add up
    from repro.kernels.stacked_sweep import stacked_sweep_query

    _, _, _, info = stacked_sweep_query(stk, jnp.asarray(q), k,
                                        probe_tiles=probe,
                                        use_kernel=use_kernel)
    nqb = -(-q.shape[0] // 8)
    pr = info["probe"]
    assert pr["tiles"] == probe
    assert pr["scanned"] + pr["skipped"] == stk.num_segments * nqb * probe


def test_fused_query_matches_host_merge_bit_exactly():
    """The in-launch global merge is ``core.search.merge_topk`` run
    inside the device program: fusing must be a pure code motion --
    ``stacked_sweep_query`` output equals planes API + host-side
    ``merge_topk_planes`` bit for bit, extra candidates included."""
    from repro.core.search import merge_topk_planes
    from repro.kernels.stacked_sweep import stacked_sweep_query

    segs = _ragged_segments(seed=71)
    stk = StackedLeaves.from_segments(segs)
    q = normalize_query(_mkdata(6, seed=72, dim=DIM + 1))
    k = 5
    rng = np.random.default_rng(73)
    # empty extras (all +inf/-1): the fused path's global seed is a
    # no-op, so planes are identical and the equality is a pure
    # code-motion check (including the -1-slot dedup convention);
    # finite extras (fake "delta" rows, fresh ids) also tighten the
    # fused path's thresholds -- the merged top-k must still agree on
    # this state (both are exact, same candidates survive)
    empty_d = np.full((6, k), np.inf, np.float32)
    empty_i = np.full((6, k), -1, np.int32)
    fin_d = np.sort(rng.uniform(0.2, 2.0, (6, k))).astype(np.float32)
    fin_i = (1000 + np.arange(6 * k).reshape(6, k)).astype(np.int32)
    for extra_d, extra_i in ((empty_d, empty_i), (fin_d, fin_i)):
        for p in (0, 3):
            fd, fi, cnt, _ = stacked_sweep_query(
                stk, jnp.asarray(q), k, probe_tiles=p,
                extra_d=extra_d, extra_i=extra_i, use_kernel=False)
            sd, sg, cnt2, _ = stacked_sweep_search(
                stk, jnp.asarray(q), k, probe_tiles=p, use_kernel=False,
                lambda_cap=jnp.asarray(extra_d[:, k - 1]))
            hd, hi = merge_topk_planes(sd, sg, k, extra_d=extra_d,
                                       extra_i=extra_i)
            np.testing.assert_array_equal(np.asarray(fd), np.asarray(hd))
            np.testing.assert_array_equal(np.asarray(fi), np.asarray(hi))


def test_engine_routes_stacked_and_stays_exact():
    """The engine auto-routes high-fan-out snapshots to the stacked
    launch; warm answers stay bit-identical and oracle-exact."""
    from repro.serve import DispatchPolicy, P2HEngine

    m = _mk_fanned(41, chunks=8)
    assert sum(1 for s in m.snapshot().segments if s.live) >= 4
    eng = P2HEngine(m, slot_size=4,
                    policy=DispatchPolicy(prefer_pallas=False))
    q = _mkdata(4, seed=42, dim=DIM + 1)
    d1, i1 = m.query(q, k=5, engine=eng)
    assert eng.stats()["routes"].get("stacked", 0) > 0, \
        eng.stats()["routes"]
    ed, eg = _oracle(m.snapshot(), q, 5)
    assert np.array_equal(i1, eg)
    d2, i2 = m.query(q, k=5, engine=eng)  # warm: bit-identical
    assert np.array_equal(i2, i1) and np.array_equal(d2, d1)
    assert eng.cache.stats()["hits"] >= 4


def test_engine_forwards_probe_tiles_and_stays_exact():
    """The policy's probe_tiles knob reaches the device program through
    the engine path, and any probe width serves exact answers."""
    from repro.serve import DispatchPolicy, P2HEngine

    m = _mk_fanned(61, chunks=8)
    q = _mkdata(4, seed=62, dim=DIM + 1)
    ed, eg = _oracle(m.snapshot(), q, 5)
    outs = []
    for probe in (0, 1, None):
        eng = P2HEngine(m, slot_size=4,
                        policy=DispatchPolicy(prefer_pallas=False,
                                              probe_tiles=probe))
        d, i = m.query(q, k=5, engine=eng)
        assert eng.stats()["routes"].get("stacked", 0) > 0
        np.testing.assert_allclose(d, ed, rtol=1e-4, atol=1e-5,
                                   err_msg=f"probe={probe}")
        mism = i != eg  # id disagreements must be exact-distance ties
        if mism.any():
            tol = 1e-5 * np.abs(ed) + 1e-6
            assert (np.abs(d - ed)[mism] <= tol[mism]).all(), probe
            for r in np.nonzero(mism.any(axis=1))[0]:
                assert (sorted(i[r][mism[r]].tolist())
                        == sorted(eg[r][mism[r]].tolist())), probe
        outs.append((d, i))
    d0, i0 = outs[0]  # probe width never changes the answer
    for d, i in outs[1:]:
        np.testing.assert_allclose(d, d0, rtol=1e-6, atol=1e-7)
