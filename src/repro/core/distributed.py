"""Distributed P2HNNS: the index sharded over a mesh axis via shard_map.

The paper motivates Ball-Tree partly because "we can leverage it to split
massive data sets into fine granularities for scalable and distributed
P2HNNS" (Section III-A, point 4).  This module is that scale-out story:

  * the database is partitioned into ``S`` shards along the ``data`` mesh
    axis (composed with the ``pod`` axis on multi-pod meshes);
  * each device builds/holds an independent local BC-Tree over its shard
    (flat arrays padded to common shapes and stacked with a leading shard
    dimension, so the stacked index is an ordinary sharded pytree);
  * a query is answered with a **two-round lambda exchange**:

      round 1:  every shard sweeps a small prefix (``frac1``) of its most
                promising leaves -> local top-k -> ``pmin`` over shards
                gives lambda0, a *valid upper bound on the global k-th
                distance* (the union of shards contains >= k candidates
                below any shard's local k-th);
      round 2:  every shard runs the full exact sweep with
                ``lambda_cap=lambda0`` -- distant shards prune almost all
                of their tiles immediately;

    followed by an ``all_gather`` of the per-shard top-k and a replicated
    merge.  Exact: round-2 pruning only ever discards candidates whose
    lower bound exceeds an upper bound on the global k-th distance.

This is a beyond-paper distributed optimization; its pruning win is
measured in ``benchmarks/bench_distributed.py``.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import search
from repro.core.balltree import FlatTree, build_tree
from repro.parallel.sharding import mesh_signature
from repro.runtime import spans

__all__ = ["ShardedP2HIndex", "two_round_exchange", "warm_round1"]

# ---------------------------------------------------------------------------
# Round-1 template registry.
#
# Round 1 of the exchange runs ``method="beam"`` per shard, which bottoms
# out in :func:`repro.core.search.sweep_search` -- a ``lax.scan`` program
# whose jit cache is keyed on each segment tree's shapes (num_leaves, n0,
# d) plus (B, k, n_visit).  A compaction mints a brand-new tree shape, so
# without warmup the first post-publish exchange pays that compile on the
# query path (the residual seconds-scale p99 spike after the stacked
# program is warmed).  ``two_round_exchange`` records the (B, k, frac1)
# templates it actually serves; the background compactor replays them
# against the freshly built tree via :func:`warm_round1` *before* the
# publish flips the epoch.
#
# Templates are keyed by the recording process's device-topology
# signature (:func:`repro.parallel.sharding.mesh_signature`): a template
# recorded while serving on one topology describes an executable shaped
# for that topology, and replaying it after the visible device set
# changed (restored checkpoint on different hardware, forked worker)
# would warm -- or worse, poison -- the wrong jit cache entries.
# ``warm_round1`` only replays templates whose signature matches the
# current topology.
_ROUND1_LOCK = threading.Lock()
_ROUND1_TEMPLATES: "collections.OrderedDict[tuple, None]" = (
    collections.OrderedDict())
_ROUND1_MAX_TEMPLATES = 8


def _record_round1(B: int, k: int, frac1: float) -> None:
    key = (int(B), int(k), float(frac1), mesh_signature())
    with _ROUND1_LOCK:
        _ROUND1_TEMPLATES[key] = None
        _ROUND1_TEMPLATES.move_to_end(key)
        while len(_ROUND1_TEMPLATES) > _ROUND1_MAX_TEMPLATES:
            _ROUND1_TEMPLATES.popitem(last=False)


def warm_round1(tree, *, is_bc: bool = True, templates=None) -> int:
    """Pre-compile the per-segment exchange sweeps for ``tree``'s shapes.

    Replays every recorded (B, k, frac1) exchange template against
    ``tree`` with dummy queries so both per-segment ``sweep_search``
    forms are in the jit cache before the segment is ever published:

      * the round-1 beam form (``frac=frac1``, capless), and
      * the round-2 / sequential exact form (``frac=1.0`` with a
        ``lambda_cap`` operand) -- the one a below-stacked-fan-out
        round 2 (or a per-shard sequential fallback) runs on path.

    Templates recorded against a *different* device topology are
    skipped (see the registry note above).  Explicitly-passed
    ``templates`` are trusted as bare ``(B, k, frac1)`` tuples.

    Returns the number of programs replayed (0 when none recorded).
    """
    if templates is not None:
        tpls = [tuple(t)[:3] for t in templates]
    else:
        sig = mesh_signature()
        with _ROUND1_LOCK:
            tpls = [key[:3] for key in _ROUND1_TEMPLATES
                    if key[3] == sig]
    warmed = 0
    for B, k, frac1 in tpls:
        q = jnp.ones((B, tree.d), jnp.float32)
        cap = jnp.ones((B,), jnp.float32)
        for kw in ({"frac": frac1},
                   {"frac": 1.0, "lambda_cap": cap}):
            try:
                bd, bi, _ = search.sweep_search(
                    tree, q, k, use_ball=is_bc, use_cone=is_bc, **kw)
                np.asarray(bd), np.asarray(bi)  # force compile + execute
                warmed += 1
            except Exception as e:  # best-effort: serving stays correct
                from repro.kernels.stacked_sweep import record_warm_failure
                record_warm_failure("warm_round1", e)
    return warmed

_ARRAY_FIELDS = [
    f.name for f in dataclasses.fields(FlatTree) if not f.metadata.get("static", False)
]
_STATIC_FIELDS = [
    f.name for f in dataclasses.fields(FlatTree) if f.metadata.get("static", False)
]


def _pad_tree(t: FlatTree, m: int, L: int, n0: int) -> FlatTree:
    """Pad node arrays to m nodes and leaf/point arrays to L leaves.

    Pad leaves replicate leaf 0's geometry but contain no valid points
    (point_ids == -1), so every search scheme treats them as empty tiles.
    """
    pn = m - t.num_nodes
    pl = L - t.num_leaves

    def padn(a):  # node arrays
        w = [(0, pn)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(np.asarray(a), w)

    def padl(a):  # leaf arrays: replicate row 0 geometry
        if pl == 0:
            return np.asarray(a)
        rep = np.broadcast_to(np.asarray(a)[:1], (pl,) + a.shape[1:])
        return np.concatenate([np.asarray(a), rep], axis=0)

    def padp(a, fill):  # point arrays
        pad_rows = pl * n0
        w = [(0, pad_rows)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(np.asarray(a), w, constant_values=fill)

    return FlatTree(
        centers=padn(t.centers),
        radii=padn(t.radii),
        counts=padn(t.counts),
        left=padn(t.left),
        right=padn(t.right),
        node_leaf=padn(t.node_leaf),
        leaf_centers=padl(t.leaf_centers),
        leaf_radii=padl(t.leaf_radii),
        leaf_cnorm=padl(t.leaf_cnorm),
        points=padp(t.points, 0.0),
        point_ids=padp(t.point_ids, -1),
        rx=padp(t.rx, -1.0),
        xcos=padp(t.xcos, 0.0),
        xsin=padp(t.xsin, 0.0),
        n0=t.n0,
        n=t.n,
        d=t.d,
        num_nodes=m,
        num_leaves=L,
        max_depth=t.max_depth,
    )


def two_round_exchange(shards, queries, k: int = 1, *, frac1: float = 0.25,
                       method: str = "sweep", frac: float = 1.0,
                       lambda_cap=None, return_info: bool = False,
                       stacked: bool | None = None,
                       probe_tiles: int | None = None,
                       probe_dtype: str | None = None,
                       mesh=None, mesh_axis: str = "shard",
                       deadline=None, resilience=None):
    """Host-orchestrated two-round lambda exchange over *callable shard
    backends* -- the frozen forest's exchange generalized to heterogeneous
    per-shard states.

    ``shards`` is any sequence of backends with the ``Snapshot.query``
    signature::

        backend.query(q, k, method=..., frac=..., lambda_cap=...,
                      return_counters=True, include_deltas=...)
            -> (bd, bi, counters)

    answering with *global* ids over already-normalized ``(B, d)``
    queries.  In particular each element can be a
    :class:`repro.stream.Snapshot` pinned from one shard of a sharded
    mutable index -- delta-only, multi-segment, and mid-compaction shard
    states all serve through the same two rounds:

      round 1:  each shard runs its cheap budgeted prefix scan
                (``method="beam"`` at ``frac1``; delta rows are always
                scanned exactly).  A shard's returned k-th distance is
                the distance of k real points, hence an upper bound on
                that shard's true k-th and therefore on the global k-th
                (the union of shards holds >= k candidates below it).
                The min over shards -- tightened further by an
                externally-valid ``lambda_cap`` such as the serving
                engine's lambda cache -- is ``lambda0``.

      round 2:  each shard runs the full ``method`` backend over its
                *segments only* (``include_deltas=False`` -- round 1
                already scanned every delta exactly, and its candidates
                reach the final merge) with ``lambda_cap=lambda0``;
                distant shards prune almost all of their tiles
                immediately.  ``merge_topk`` de-duplicates and merges
                both rounds' candidates.  Exact for exact round-2
                methods: pruning only ever discards candidates whose
                lower bound exceeds an upper bound on the global k-th
                distance, and a delta point displaced from its round-1
                top-k was displaced by k closer real points, so it
                cannot be a global top-k member.

    ``method="beam"`` is budgeted and never consumes caps (the engine's
    rule): one capless round at ``frac``.  ``return_info=True`` appends a
    dict with ``lambda0`` (B,) and per-shard ``round1_kth`` (S, B) -- the
    regression surface for the exchange-validity invariant test.

    ``stacked`` controls round 2's *segment-parallel* form: shard
    backends that expose ``stacked_leaves()`` (snapshot pins of the
    mutable index) have their segment tile-sets concatenated and swept
    by **one** two-pass device program under ``lambda0``
    (:func:`repro.kernels.stacked_sweep.stacked_sweep_query`: probe
    pass tightens ``lambda0`` to ``lambda_probe`` on device, the main
    pass sweeps the remaining tiles, and the cross-shard global merge
    *and* per-shard k-th reductions run inside the same program -- the
    stacked round 2 returns from a single device program with no
    host-side per-segment merge; ``probe_tiles`` is the probe width and
    ``probe_dtype`` its precision -- the quantized probe widens its
    lambda by conservative slack and the f32 main pass rescans, so
    answers stay bit-exact).
    Backends without stacked leaves keep the sequential loop.  ``None``
    auto-promotes the exact ``sweep``/``pallas`` methods when the
    stackable shards' total live-segment fan-out reaches
    ``STACKED_FANOUT_DEFAULT``; ``True`` (or ``method="stacked"``)
    forces it, ``False`` forbids it (and is forwarded to stackable
    shards so nothing stacks per-shard either -- the pure-sequential
    reference the regression fence diffs against).  Exact either way:
    every segment is swept under valid caps; only tile-skip counts (and
    the heavily-pruned far-shard diagnostics beyond the true top-k)
    differ.

    ``mesh`` (optional ``jax.sharding.Mesh`` with axis ``mesh_axis``)
    runs the stacked round 2 *device-parallel*: the combined grid's
    segment axis is sharded across the mesh's devices and the
    sequential in-launch fold of the global top-k / per-shard k-th
    reductions is replaced by ``all_gather``/``psum`` collectives
    (:func:`repro.kernels.stacked_sweep.stacked_sweep_query` with
    ``mesh=``).  Round 1 stays a host loop -- shard backends are
    heterogeneous Python callables -- but its sequential *result* fold
    (the running ``min`` into ``lambda0``) is order-insensitive, so the
    collective replacement lives where the compute is: round 2.  Exact
    regardless of mesh: same candidates, same merge.

    ``deadline`` (a :class:`repro.serve.resilience.Deadline`) and/or
    ``resilience`` (a :class:`repro.serve.resilience.ShardSupervisor`)
    switch to the degraded-capable twin :func:`_resilient_exchange`:
    per-shard calls run supervised (timeouts, breakers, hedging) and a
    failing shard yields bounded degradation instead of an exception.
    Both ``None`` (the default) keeps this body byte-for-byte on the
    historical path -- the zero-overhead invariant the resilience bench
    fences.
    """
    shards = tuple(shards)  # iterated once per round: reject generators
    if resilience is not None or deadline is not None:
        return _resilient_exchange(
            shards, queries, k, frac1=frac1, method=method, frac=frac,
            return_info=return_info, stacked=stacked,
            probe_tiles=probe_tiles, probe_dtype=probe_dtype,
            mesh=mesh, mesh_axis=mesh_axis, deadline=deadline,
            sup=resilience)
    q_host = np.asarray(np.atleast_2d(np.asarray(queries)), np.float32)
    q = jnp.asarray(q_host)
    B = q.shape[0]
    counters = np.zeros((8,), np.int64)
    ext = (None if lambda_cap is None
           else np.asarray(lambda_cap, np.float32).reshape(-1))
    lam0 = None
    round1_kth = []
    parts_d, parts_i = [], []
    if method != "beam":
        _record_round1(B, k, frac1)  # template for pre-publish warmup
        with spans.span("p2h.exchange.round1"):
            # every shard's call is started before any answer is read:
            # placed shards run on their own devices at the same time
            pending = []
            for si, s in enumerate(shards):
                with spans.span("p2h.exchange.shard", shard=si):
                    pending.append(_round1_start(s, q, q_host, k, frac1))
            lam = (np.full((B,), np.inf, np.float32) if ext is None
                   else ext)
            for res in pending:
                bd1, bi1, c1 = res() if callable(res) else res
                counters += np.asarray(c1, np.int64)
                bd1, bi1 = np.asarray(bd1), np.asarray(bi1)
                round1_kth.append(bd1[:, k - 1])
                lam = np.minimum(lam, bd1[:, k - 1])
                # round-1 candidates (incl. the exact delta scan) feed
                # the final merge, so round 2 need not rescan the deltas
                parts_d.append(bd1)
                parts_i.append(bi1)
        lam0 = jnp.asarray(lam)
    base = "sweep" if method == "stacked" else method
    with spans.span("p2h.exchange.round2"):
        stk_merged, stk_kth, cnt_stk = _stacked_round2(
            shards, q, k, method=method, stacked=stacked, lam0=lam0,
            probe_tiles=probe_tiles, probe_dtype=probe_dtype,
            mesh=mesh, mesh_axis=mesh_axis)
        if stk_merged is not None:
            stk_merged = tuple(np.asarray(a) for a in stk_merged)
    if cnt_stk is not None:
        counters += cnt_stk
    if stk_merged is not None:
        # ONE device program (probe + main + merge) already merged every
        # stackable shard's segments and reduced the per-shard k-ths --
        # it contributes a single already-merged candidate list, never a
        # host-side per-segment merge loop
        parts_d.append(stk_merged[0])
        parts_i.append(stk_merged[1])
    round2_kth = []
    for si, s in enumerate(shards):
        if si in stk_kth:
            round2_kth.append(np.asarray(stk_kth[si]))
            continue
        kw = ({"stacked": stacked, "probe_dtype": probe_dtype}
              if hasattr(s, "stacked_leaves") else {})
        bd, bi, cnt = s.query(q, k, method=base, frac=frac,
                              lambda_cap=lam0, return_counters=True,
                              include_deltas=method == "beam", **kw)
        counters += np.asarray(cnt, np.int64)
        bd, bi = np.asarray(bd), np.asarray(bi)
        round2_kth.append(bd[:, k - 1])
        parts_d.append(bd)
        parts_i.append(bi)
    with spans.span("p2h.exchange.merge"):
        if parts_d:
            bd, bi = search.merge_topk(
                jnp.asarray(np.concatenate(parts_d, axis=1)),
                jnp.asarray(np.concatenate(parts_i, axis=1)), k)
            bd, bi = np.asarray(bd), np.asarray(bi)
        else:
            bd = np.full((B, k), np.inf, np.float32)
            bi = np.full((B, k), -1, np.int32)
    if return_info:
        r2 = (np.stack(round2_kth) if round2_kth
              else np.zeros((0, B), np.float32))
        r1 = (np.stack(round1_kth) if round1_kth
              else np.full_like(r2, np.inf))
        # per-shard local k-th upper bounds: round-1 beam k-ths are
        # always real-point distances; round-2 k-ths are too when finite
        # (a heavily-pruned far shard leaves +inf slots).  Their
        # elementwise min is each shard's tightest valid local bound --
        # the lambda cache's per-shard invalidation unit.
        info = {
            "lambda0": None if lam0 is None else np.asarray(lam0),
            "round1_kth": r1,
            "shard_kth": np.minimum(r1, r2) if len(r2) else r2,
        }
        return bd, bi, counters, info
    return bd, bi, counters


def _round1_start(s, q, q_host, k: int, frac1: float):
    """Start shard ``s``'s round-1 beam.  A snapshot pin runs on its own
    device and returns a callable that reads the answer later; any other
    backend answers now.  A placed shard gets the queries on its device
    (counted in ``exchange_upload_bytes``)."""
    dispatch = getattr(s, "dispatch", None)
    if dispatch is None:
        return s.query(q, k, method="beam", frac=frac1,
                       return_counters=True)
    dev = getattr(s, "device", None)
    if dev is not None:
        q = jax.device_put(q_host, dev)
        spans.count("exchange_upload_bytes", q_host.nbytes)
    return dispatch(q, k, method="beam", frac=frac1).result


def _placed(shards, mesh, mesh_axis) -> bool:
    """Whether shard ``s`` of ``shards`` lives on device ``s`` of the 1-D
    ``mesh`` for every ``s`` (a placed sharded index)."""
    if mesh is None or tuple(mesh.axis_names) != (mesh_axis,):
        return False
    devices = list(np.asarray(mesh.devices).flat)
    return len(devices) == len(shards) and all(
        getattr(s, "device", None) == dev
        for s, dev in zip(shards, devices))


def _stacked_round2(shards, q, k, *, method, stacked, lam0, probe_tiles,
                    probe_dtype=None, mesh=None, mesh_axis="shard"):
    """Resolve + run the segment-parallel round 2: every stackable
    shard's segment tile-sets concatenated and swept by ONE two-pass
    device program under ``lambda0`` (probe + main + in-launch merge +
    per-shard k-th reductions).  Returns ``((merged dists (B, k), merged
    global ids (B, k)), {shard index: per-shard k-th (B,)}, counters)``
    for the shards served by the program -- ``(None, {}, None)`` when
    the sequential loop should run instead."""
    if (lam0 is None or stacked is False
            or method not in ("sweep", "pallas", "stacked")):
        return None, {}, None
    stackable = [(si, s) for si, s in enumerate(shards)
                 if callable(getattr(s, "stacked_leaves", None))
                 and len(getattr(s, "segments", ())) > 0]
    if not stackable:
        return None, {}, None
    if stacked is None and method != "stacked":
        from repro.kernels.stacked_sweep import (STACKED_DENSITY_DEFAULT,
                                                 STACKED_FANOUT_DEFAULT,
                                                 tile_density)

        fanout = sum(1 for _, s in stackable
                     for seg in s.segments if seg.live)
        all_segs = [seg for _, s in stackable for seg in s.segments]
        # the concatenated grid re-pads every shard to the global max
        # tile count, so density is judged on the flattened segment set
        # (tile_density reads the *current* ids planes, so tombstoned
        # rows degrade the signal exactly like build-time raggedness)
        if (fanout < STACKED_FANOUT_DEFAULT
                or tile_density(all_segs) < STACKED_DENSITY_DEFAULT):
            return None, {}, None
    from repro.kernels.stacked_sweep import (PlacedStacks, concat_cached,
                                             stacked_sweep_query)

    is_bc = getattr(stackable[0][1], "variant", "bc") == "bc"
    if _placed(shards, mesh, mesh_axis):
        # each shard's stack is built on its own device, all to one tile
        # count, and the launch takes them as they lie
        tiles = _common_tiles(s for _, s in stackable)
        by_shard = dict(stackable)
        combined = PlacedStacks(
            tuple(by_shard[si].stacked_leaves(min_tiles=tiles)
                  if si in by_shard else None
                  for si in range(len(shards))),
            mesh, mesh_axis)
        fd, fi, cnt, info = stacked_sweep_query(
            combined, q, k, lambda_cap=lam0, probe_tiles=probe_tiles,
            probe_dtype=probe_dtype, probe_route="round2",
            use_ball=is_bc, use_cone=is_bc,
            use_kernel=True if method == "pallas" else None)
        shard_kth = np.asarray(info["shard_kth"])  # (S, B)
        kths = {si: shard_kth[si] for si, _ in stackable}
        return (fd, fi), kths, np.asarray(cnt, np.int64)
    stks = [s.stacked_leaves() for _, s in stackable]
    combined = concat_cached(stks)
    # probe_route="round2": the sweep enters with lambda0, the exchanged
    # round-1 k-th -- the same tightening the probe pass would recreate
    # -- so the route's default is single-pass (measured: the probe
    # yields ~0 extra live skips here and a 0.94x p50 regression)
    fd, fi, cnt, info = stacked_sweep_query(
        combined, q, k, lambda_cap=lam0, probe_tiles=probe_tiles,
        probe_dtype=probe_dtype, probe_route="round2",
        shard_bounds=tuple(stk.num_segments for stk in stks),
        use_ball=is_bc, use_cone=is_bc,
        use_kernel=True if method == "pallas" else None,
        mesh=mesh, mesh_axis=mesh_axis)
    shard_kth = np.asarray(info["shard_kth"])  # (S_stackable, B)
    kths = {si: shard_kth[row] for row, (si, _) in enumerate(stackable)}
    return (fd, fi), kths, np.asarray(cnt, np.int64)


def _common_tiles(snaps) -> int:
    """The tile count every placed shard's stack is built to: the
    stacked grid's quantized count over all shards' segments."""
    from repro.kernels.stacked_sweep import _ceil_to, _tile_quantum

    most = max(seg.tree.num_leaves for s in snaps for seg in s.segments)
    return _ceil_to(most, _tile_quantum(most))


def _resilient_exchange(shards, queries, k, *, frac1, method, frac,
                        return_info, stacked, probe_tiles, probe_dtype,
                        mesh, mesh_axis, deadline, sup):
    """Degraded-capable twin of the two-round exchange: every shard call
    runs through a :class:`~repro.serve.resilience.ShardSupervisor`
    (per-call budget clamped by ``deadline``, circuit breakers, one
    hedged duplicate for stragglers) and a failing shard produces
    **bounded degradation**, never an exception.

    Exactness contract: the returned neighbors are exactly the oracle's
    answers restricted to the live shards.  Three rules make that hold:

    * A shard missing from round 1 merely loosens ``lambda0`` -- the
      min over the *responding* shards' round-1 k-ths is still a valid
      upper bound for the surviving set (each responding shard's beam
      k-th is a real-point distance, and its round-1 candidates reach
      the merge, so >= k merged candidates sit at or below the min).
      The engine's external ``lambda_cap`` is deliberately **not**
      consumed here: it bounds the *full*-set k-th, which can undercut
      the live-shard-restricted k-th and would prune live answers.
    * A shard contributes fully-exact or not at all: when its round 2
      fails, its round-1 candidates are dropped too (a beam prefix is
      not the shard's exact answer), and the shard is reported in
      ``missing_shards``.
    * Dropping a shard can loosen ``lambda0`` after other shards
      already swept under the tighter cap, so the loop re-runs any
      surviving shard whose capped result still has pruned (+inf)
      slots under the stale cap.  Each pass either finishes cleanly or
      strictly grows the missing set, so it terminates in <= S passes;
      an exhausted deadline fast-fails the re-runs into the missing
      set, keeping latency bounded by the deadline.

    The stacked round 2 runs as ONE supervised multi-shard call (its
    failure falls back to per-shard sequential calls, isolating the
    culprit).  ``info`` gains ``missing_shards`` (sorted tuple),
    ``degraded`` and ``complete`` -- ``complete`` is False iff some
    missing shard *could* hold a closer point, i.e. iff it has (or is
    not known not to have) live points.
    """
    if sup is None:
        from repro.serve.resilience import ShardSupervisor

        sup = ShardSupervisor()
    q = jnp.asarray(np.atleast_2d(np.asarray(queries)), jnp.float32)
    B = q.shape[0]
    S = len(shards)
    counters = np.zeros((8,), np.int64)
    missing: set[int] = set()
    r1_d, r1_i, r1_kth = {}, {}, {}
    if method != "beam":
        _record_round1(B, k, frac1)  # template for pre-publish warmup

        def mk_r1(s):
            return lambda: s.query(q, k, method="beam", frac=frac1,
                                   return_counters=True)

        # parallel round 1: a straggler costs min(budget, straggler),
        # not the sum over shards; the min-fold is order-insensitive
        res1 = sup.call_parallel(
            [((si,), mk_r1(s)) for si, s in enumerate(shards)],
            deadline=deadline)
        for si, (ok, val, _why) in enumerate(res1):
            if not ok:
                # not missing yet: the shard gets a round-2 attempt with
                # include_deltas=True (a full exact scan under lam0 needs
                # no beam prefix; only a round-2 failure loses the shard)
                continue
            bd1, bi1, c1 = val
            counters += np.asarray(c1, np.int64)
            r1_d[si] = jnp.asarray(bd1)
            r1_i[si] = jnp.asarray(bi1)
            r1_kth[si] = np.asarray(r1_d[si][:, k - 1])
    base = "sweep" if method == "stacked" else method
    done2: dict[int, tuple] = {}   # si -> (bd, bi, kth (B,), gen)
    stk_units: list[tuple] = []    # (members, fd, fi, {si: kth}, gen)
    lam0 = None
    while True:
        gen = len(missing)
        lamk = [r1_kth[si] for si in sorted(r1_kth)]
        lam0 = (jnp.asarray(np.minimum.reduce(lamk), jnp.float32)
                if (method != "beam" and lamk) else None)
        # retire results computed under a now-stale (tighter) cap whose
        # pruned +inf slots a looser lambda0 could fill in
        for si in [si for si, (_, _, kth, g) in done2.items()
                   if g != gen and bool(np.isinf(kth).any())]:
            del done2[si]
        stk_units = [u for u in stk_units
                     if not (u[4] != gen
                             and any(bool(np.isinf(np.asarray(v)).any())
                                     for v in u[3].values()))]
        covered = set(done2) | {si for u in stk_units for si in u[0]}
        todo = [si for si in range(S)
                if si not in missing and si not in covered]
        if not todo:
            break
        failed = False
        # combined stacked unit: stackable todo shards with round-1
        # results (an r1-failed shard needs include_deltas=True, which
        # the stacked program does not do -- it goes sequential below)
        cand = [si for si in todo if si in r1_kth]
        if cand and lam0 is not None and stacked is not False:
            sub = tuple(shards[si] for si in cand)
            lam_stk = lam0

            def stk_fn(sub=sub, lam_stk=lam_stk):
                return _stacked_round2(
                    sub, q, k, method=method, stacked=stacked,
                    lam0=lam_stk, probe_tiles=probe_tiles,
                    probe_dtype=probe_dtype, mesh=mesh,
                    mesh_axis=mesh_axis)

            ok, val, _why = sup.call(tuple(cand), stk_fn,
                                     deadline=deadline)
            if ok:
                merged, kths_local, cnt = val
                if merged is not None:
                    kths = {cand[li]: v for li, v in kths_local.items()}
                    stk_units.append((tuple(sorted(kths)),
                                      jnp.asarray(merged[0]),
                                      jnp.asarray(merged[1]), kths, gen))
                    counters += cnt
                    todo = [si for si in todo if si not in kths]
            # on failure every cand member stays in todo: each gets an
            # individual supervised attempt (and verdict) below
        for si in todo:
            s = shards[si]
            kw = ({"stacked": stacked, "probe_dtype": probe_dtype}
                  if hasattr(s, "stacked_leaves") else {})
            inc = (method == "beam") or si not in r1_kth

            def fn(s=s, cap=lam0, inc=inc, kw=kw):
                return s.query(q, k, method=base, frac=frac,
                               lambda_cap=cap, return_counters=True,
                               include_deltas=inc, **kw)

            ok, val, _why = sup.call((si,), fn, deadline=deadline)
            if ok:
                bd, bi, cnt = val
                counters += np.asarray(cnt, np.int64)
                done2[si] = (jnp.asarray(bd), jnp.asarray(bi),
                             np.asarray(jnp.asarray(bd)[:, k - 1]), gen)
            else:
                # fully-exact or not at all: drop the beam prefix too
                missing.add(si)
                r1_d.pop(si, None)
                r1_i.pop(si, None)
                r1_kth.pop(si, None)
                failed = True
        if not failed:
            break
    parts_d = [r1_d[si] for si in range(S) if si in r1_d]
    parts_i = [r1_i[si] for si in range(S) if si in r1_i]
    for _mem, fd, fi, _kths, _g in stk_units:
        parts_d.append(fd)
        parts_i.append(fi)
    for si in sorted(done2):
        parts_d.append(done2[si][0])
        parts_i.append(done2[si][1])
    if parts_d:
        bd, bi = search.merge_topk(jnp.concatenate(parts_d, axis=1),
                                   jnp.concatenate(parts_i, axis=1), k)
        bd, bi = np.asarray(bd), np.asarray(bi)
    else:
        bd = np.full((B, k), np.inf, np.float32)
        bi = np.full((B, k), -1, np.int32)
    if missing:
        sup.count("degraded_batches")
    if not return_info:
        return bd, bi, counters
    complete = True
    for si in sorted(missing):
        live = getattr(shards[si], "live_count", None)
        if live is None or live > 0:  # unknown -> assume it could
            complete = False
            break
    r1 = np.full((S, B), np.inf, np.float32)
    for si, v in r1_kth.items():
        r1[si] = v
    r2 = np.full((S, B), np.inf, np.float32)
    for si in done2:
        r2[si] = done2[si][2]
    for _mem, _fd, _fi, kths, _g in stk_units:
        for si, v in kths.items():
            r2[si] = np.asarray(v)
    info = {
        "lambda0": None if lam0 is None else np.asarray(lam0),
        "round1_kth": r1,
        "shard_kth": np.minimum(r1, r2),
        "missing_shards": tuple(sorted(missing)),
        "complete": complete,
        "degraded": bool(missing),
    }
    return bd, bi, counters, info


@dataclasses.dataclass
class ShardedP2HIndex:
    """A BC-Tree forest sharded across devices."""

    stacked: FlatTree  # arrays have leading shard dim S; statics are common
    mesh: Mesh
    axes: tuple  # mesh axis name(s) the shard dim is mapped to
    num_shards: int
    shard_n: int  # points per shard (before leaf padding)
    true_n: int  # database size before shard padding

    @classmethod
    def build(
        cls,
        data: np.ndarray,
        mesh: Mesh,
        *,
        axes: Sequence[str] | str = ("data",),
        n0: int = 256,
        seed: int = 0,
        append_one: bool = True,
    ) -> "ShardedP2HIndex":
        axes = (axes,) if isinstance(axes, str) else tuple(axes)
        S = int(np.prod([mesh.shape[a] for a in axes]))
        n = data.shape[0]
        shard_n = -(-n // S)
        # pad the database by repeating row 0; duplicates are de-duplicated
        # at merge time by global id (pad ids map to id % n).
        pad = S * shard_n - n
        if pad:
            data = np.concatenate([data, data[:pad]], axis=0)
        trees = [
            build_tree(
                data[s * shard_n : (s + 1) * shard_n],
                n0=n0,
                seed=seed + s,
                append_one=append_one,
            )
            for s in range(S)
        ]
        m = max(t.num_nodes for t in trees)
        L = max(t.num_leaves for t in trees)
        depth = max(t.max_depth for t in trees)
        trees = [
            dataclasses.replace(_pad_tree(t, m, L, n0), max_depth=depth)
            for t in trees
        ]
        stacked_arrays = {
            f: np.stack([np.asarray(getattr(t, f)) for t in trees])
            for f in _ARRAY_FIELDS
        }
        statics = {f: getattr(trees[0], f) for f in _STATIC_FIELDS}
        stacked = FlatTree(**stacked_arrays, **statics)
        # place each shard's tree on its devices (replicated over other axes)
        spec = P(axes)
        sharding = NamedSharding(mesh, spec)
        stacked = jax.tree.map(
            lambda a: jax.device_put(
                a, NamedSharding(mesh, P(axes, *(None,) * (a.ndim - 1)))
            ),
            stacked,
        )
        del sharding, spec
        return cls(
            stacked=stacked,
            mesh=mesh,
            axes=axes,
            num_shards=S,
            shard_n=shard_n,
            true_n=n,
        )

    # ------------------------------------------------------------------
    def query(
        self, queries, k: int = 1, *, frac1: float = 0.02,
        normalize: bool = True, lambda_cap=None, engine=None, **kw
    ):
        """Exact distributed top-k with the two-round lambda exchange.

        ``lambda_cap`` (optional, (B,)): externally-known upper bounds on
        each query's *global* k-th distance (e.g. from a serving engine's
        lambda cache).  They tighten lambda0 in **both** rounds -- hot
        repeat traffic prunes distant shards' tiles before the round-1
        prefix sweep even finishes.  Exact for valid caps (same argument
        as round 2 itself).

        ``engine``: route through a :class:`repro.serve.P2HEngine` whose
        ``sharded`` index is this one -- micro-batching + lambda cache in
        front of the two-round exchange.  The engine derives ``lambda_cap``
        from its own cache (passing one here is an error) and uses its own
        batching/round-1 configuration; the returned stats dict has the
        same per-call counter shape as the direct path.
        """
        if engine is not None:
            assert engine.sharded is self, "engine serves a different index"
            if lambda_cap is not None:
                raise ValueError(
                    "lambda_cap is derived by the engine's cache; do not "
                    "pass both engine= and lambda_cap=")
            engine.flush()  # pending streaming work is not this call's
            before = np.array(engine.route_counters("sharded"))
            bd, bi = engine.query(queries, k, normalize=normalize)
            delta = np.array(engine.route_counters("sharded")) - before
            return bd, bi, search.SearchStats(delta)
        q = np.atleast_2d(queries)
        if normalize:
            from repro.core.balltree import normalize_query

            q = normalize_query(q)
        q = jnp.asarray(q, dtype=jnp.float32)
        if lambda_cap is None:
            lambda_cap = jnp.full((q.shape[0],), jnp.inf, jnp.float32)
        else:
            lambda_cap = jnp.asarray(lambda_cap, jnp.float32).reshape(-1)
        bd, bi, cnt = _sharded_query(
            self.stacked,
            q,
            lambda_cap,
            mesh=self.mesh,
            axes=self.axes,
            k=k,
            frac1=frac1,
            shard_n=self.shard_n,
            n=self.true_n,
            **kw,
        )
        return np.asarray(bd), np.asarray(bi), search.SearchStats(cnt)


@functools.partial(
    jax.jit, static_argnames=("mesh", "axes", "k", "frac1", "shard_n", "n")
)
def _sharded_query(stacked: FlatTree, queries, lambda_cap, *, mesh, axes, k,
                   frac1, shard_n, n):
    statics = {f: getattr(stacked, f) for f in _STATIC_FIELDS}

    def local(tree_arrays, q, cap):
        tree = FlatTree(**{f: a[0] for f, a in tree_arrays.items()}, **statics)
        sidx = jax.lax.axis_index(axes[0])
        if len(axes) > 1:
            for a in axes[1:]:
                sidx = sidx * mesh.shape[a] + jax.lax.axis_index(a)
        # round 1: cheap local prefix sweep -> global lambda0 (tightened
        # further by any externally-supplied valid cap, e.g. the serving
        # engine's lambda cache)
        bd1, _, cnt1 = search.sweep_search(tree, q, k, frac=frac1,
                                           lambda_cap=cap)
        lam0 = jnp.minimum(jax.lax.pmin(bd1[:, k - 1], axes), cap)
        # round 2: full exact sweep, pruned by lambda0
        bd, bi, cnt = search.sweep_search(tree, q, k, lambda_cap=lam0)
        gid = sidx * shard_n + bi
        gid = jnp.where(bi >= 0, gid % n, -1)  # pad duplicates -> true id
        all_d = jax.lax.all_gather(bd, axes, tiled=False)  # (S, B, k)
        all_i = jax.lax.all_gather(gid, axes, tiled=False)
        S = all_d.shape[0]
        B = q.shape[0]
        md = jnp.moveaxis(all_d, 0, 1).reshape(B, S * k)
        mi = jnp.moveaxis(all_i, 0, 1).reshape(B, S * k)
        # de-duplicate shard-padding copies by global id and merge
        fd, fi = search.merge_topk(md, mi, k)
        total_cnt = jax.lax.psum(cnt + cnt1, axes)
        return fd, fi, total_cnt

    arrays = {f: getattr(stacked, f) for f in _ARRAY_FIELDS}
    in_spec = jax.tree.map(lambda _: P(axes), arrays)
    out = jax.shard_map(
        lambda t, q, cap: local(t, q, cap),
        mesh=mesh,
        in_specs=(in_spec, P(), P()),
        out_specs=(P(), P(), P()), check_vma=False,
    )(arrays, queries, lambda_cap)
    return out
