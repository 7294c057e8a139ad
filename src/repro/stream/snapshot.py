"""Immutable, epoch-numbered views of the mutable index.

A :class:`Snapshot` is what queries run against: a tuple of sealed
:class:`Segment`\\ s (each an ordinary :class:`FlatTree` plus a local-id
-> global-id table) and a frozen view of the delta buffer.  Snapshots are
*published atomically* -- every mutation builds a new snapshot off-line
and swaps one reference -- so an in-flight query (or a serving engine
micro-batch that pinned the snapshot) always sees one consistent point
set, never a half-applied write.

Deletes never touch tree geometry.  A tombstoned point's row in the
segment's ``point_ids`` array is set to -1 -- the exact convention every
search backend (dfs / sweep / beam / pallas) already uses for leaf
padding, so masked points are excluded from candidates while all node
and point bounds stay valid (they bound a superset of the live points)
and the collaborative inner-product identity still holds for the stored
centers/counts.  This is what makes delete O(segment) instead of
O(rebuild).

``Snapshot.query`` fans a query batch across the delta and every segment
with any existing backend, threading a running lambda cap: the delta is
scanned first (cheap, exact), its k-th distance -- an upper bound on the
global k-th -- caps the first segment, and each segment's merged k-th
caps the next.  This is the serial-form of the sharded two-round
exchange in ``repro.core.distributed``, and the final merge is that
module's machinery (``repro.core.search.merge_topk``).

At segment fan-out >= ``STACKED_FANOUT_DEFAULT`` (or with
``method="stacked"`` / ``stacked=True``) the sequential segment walk is
replaced by **one** device-side program: the snapshot's sealed segments
are stacked into a cached :class:`repro.kernels.StackedLeaves` tile grid
(built lazily, carried forward across publishes because segments are
immutable -- tombstone republishes swap only the ids planes) and swept
by the two-pass stacked program -- a probe pass tightens the entry cap
(delta k-th / engine cache cap) to ``lambda_probe`` on device, the main
pass sweeps the remaining tiles under it, and the launch merges the
per-segment planes with the delta candidates itself, so the stacked
route returns from a single device program with no host-side
per-segment merge.  Exactness is unchanged; only tile-skip counts
differ (see ``repro.kernels.stacked_sweep``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import search
from repro.core.balltree import FlatTree
from repro.runtime import spans
from repro.stream.delta import DeltaView, delta_topk

__all__ = ["Segment", "Snapshot", "DeltaView", "Pending", "ShardedSnapshot"]


@dataclasses.dataclass(frozen=True)
class Segment:
    """A sealed FlatTree over a batch of points + global-id bookkeeping."""

    uid: int  # stable identity across tombstone rewrites
    tree: FlatTree
    gids: np.ndarray  # (n_seg,) i32 -- local point id -> global id
    row_of_local: np.ndarray  # (n_seg,) i32 -- local id -> tree.points row
    live: int
    dead: int

    @classmethod
    def from_points(cls, uid: int, points: np.ndarray, gids: np.ndarray,
                    *, n0: int, seed: int = 0) -> "Segment":
        """Seal a batch of already-appended (n, d) points into a tree.

        The leaf count is padded to a quantum so successive compactions
        (whose row counts drift by a few percent) land on already-
        compiled sweep/exchange program shapes instead of forcing a
        fresh XLA trace per republish -- background compiles next to
        the query path are what the p99 tail is made of."""
        from repro.core.balltree import (build_tree, leaf_pad_quantum,
                                         pad_tree_leaves)

        tree = build_tree(points, n0=n0, seed=seed, append_one=False)
        quantum = leaf_pad_quantum(tree.num_leaves)
        tree = pad_tree_leaves(
            tree, -(-tree.num_leaves // quantum) * quantum)
        pid = np.asarray(tree.point_ids)
        row_of_local = np.full((len(gids),), -1, np.int32)
        rows = np.nonzero(pid >= 0)[0]
        row_of_local[pid[rows]] = rows
        return cls(uid=uid, tree=tree, gids=np.asarray(gids, np.int32),
                   row_of_local=row_of_local, live=len(gids), dead=0)

    # ------------------------------------------------------------------
    @property
    def tombstone_frac(self) -> float:
        total = self.live + self.dead
        return self.dead / total if total else 0.0

    def with_tombstone(self, local_id: int) -> "Segment":
        """New segment with one point masked out (point_ids row -> -1)."""
        pid = np.array(self.tree.point_ids)  # host copy
        pid[self.row_of_local[local_id]] = -1
        return self._tombstoned(pid, 1)

    def with_tombstones(self, local_ids) -> "Segment":
        """Batch form of :meth:`with_tombstone` (one array copy total)."""
        local_ids = np.asarray(list(local_ids), np.int64)
        if local_ids.size == 0:
            return self
        pid = np.array(self.tree.point_ids)
        pid[self.row_of_local[local_ids]] = -1
        return self._tombstoned(pid, int(local_ids.size))

    def _tombstoned(self, pid: np.ndarray, n: int) -> "Segment":
        """This segment with ids plane ``pid`` and ``n`` more dead rows.
        A device-resident sweep view rides along: the geometry is shared
        and only the ids plane is placed again, on first use."""
        tree = dataclasses.replace(self.tree, point_ids=pid)
        out = dataclasses.replace(self, tree=tree, live=self.live - n,
                                  dead=self.dead + n)
        res = (self.__dict__.get("_resident")
               or self.__dict__.get("_resident_base"))
        if res is not None:
            object.__setattr__(out, "_resident_base", res)
        return out

    def resident_tree(self, device, *, on_path: bool = True):
        """The segment's sweep view on ``device``: ``(tree, gids)`` with
        the leaf and point arrays placed there once and kept for the
        segment's lifetime, so a query never re-sends them.  The node
        arrays, which no sweep reads, are one-row placeholders and the
        point count is left out of the static shape, so every segment of
        a leaf count shares one compiled sweep.

        A tombstoned copy of a placed segment re-sends only its ids
        plane.  Counters: ``tree_uploads`` per whole placement;
        ``exchange_upload_bytes`` the bytes placed when ``on_path`` (a
        query is waiting for them)."""
        hit = self.__dict__.get("_resident")
        if hit is not None and hit[0] == device:
            return hit[1], hit[2]
        base = self.__dict__.get("_resident_base")
        pid = np.asarray(self.tree.point_ids)
        if base is not None and base[0] == device:
            tree = dataclasses.replace(base[1],
                                       point_ids=jax.device_put(pid, device))
            gids, nbytes = base[2], pid.nbytes
        else:
            tree, gids, nbytes = _place_sweep_view(self.tree, self.gids,
                                                   device)
            spans.count("tree_uploads")
        if on_path:
            spans.count("exchange_upload_bytes", nbytes)
        object.__setattr__(self, "_resident", (device, tree, gids))
        return tree, gids

    def place(self, device) -> None:
        """Make the sweep view resident on ``device`` now (publish time)
        unless it already is, or rides along from a tombstoned ancestor
        (whose ids plane is then placed at first use)."""
        base = self.__dict__.get("_resident_base")
        if base is None or base[0] != device:
            self.resident_tree(device, on_path=False)

    def live_rows(self):
        """(points, gids) of live rows -- compaction input."""
        pid = np.asarray(self.tree.point_ids)
        rows = np.nonzero(pid >= 0)[0]
        pts = np.asarray(self.tree.points)[rows]
        return pts, self.gids[pid[rows]]


def _place_sweep_view(tree: FlatTree, gids, device):
    """``(tree, gids, bytes)``: ``tree``'s leaf and point arrays and
    ``gids`` placed on ``device``; node arrays one zero row each, and
    ``n`` / ``num_nodes`` / ``max_depth`` fixed, so the compiled sweep is
    keyed on the leaf count alone (``search.sweep_search`` reads no node
    array)."""
    leaf = {f: np.asarray(getattr(tree, f))
            for f in ("leaf_centers", "leaf_radii", "leaf_cnorm", "points",
                      "point_ids", "rx", "xcos", "xsin")}
    gids = np.asarray(gids, np.int32)
    placed, gids_dev = jax.device_put((leaf, gids), device)
    node = jax.device_put(dict(
        centers=np.zeros((1, tree.d), np.float32),
        radii=np.zeros((1,), np.float32), counts=np.zeros((1,), np.int32),
        left=np.full((1,), -1, np.int32), right=np.full((1,), -1, np.int32),
        node_leaf=np.zeros((1,), np.int32)), device)
    view = FlatTree(**placed, **node, n0=tree.n0, n=0, d=tree.d,
                    num_nodes=1, num_leaves=tree.num_leaves, max_depth=0)
    nbytes = sum(a.nbytes for a in leaf.values()) + gids.nbytes
    return view, gids_dev, nbytes


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """One consistent, immutable view of the live point set."""

    epoch: int
    #: epoch of the most recent delete; a lambda cap recorded at epoch e
    #: is valid for this snapshot iff e >= last_delete_epoch (inserts only
    #: shrink the true k-th distance, deletes can grow it).
    last_delete_epoch: int
    segments: tuple  # tuple[Segment, ...]
    deltas: tuple  # tuple[DeltaView, ...] -- active first, then sealed
    live_count: int
    max_norm: float  # >= max ||x|| over live points (monotone)
    variant: str  # "ball" | "bc"
    n0: int
    d: int
    #: the device this shard's device-side state lives on (the delta's
    #: device copy, the segments' sweep views and stacked planes); None =
    #: JAX's default placement.  Placement, not state.
    device: Any = dataclasses.field(default=None, compare=False)

    # ------------------------------------------------------------------
    @property
    def delta_live(self) -> int:
        return sum(v.live for v in self.deltas)

    @property
    def tombstone_frac(self) -> float:
        """Dead fraction over the snapshot's sealed rows (dispatch
        signal: tombstone-heavy segments waste sequential launches)."""
        live = sum(s.live for s in self.segments)
        dead = sum(s.dead for s in self.segments)
        return dead / (live + dead) if live + dead else 0.0

    # -- stacked-leaf cache (segment-parallel sweep) -------------------
    def stacked_leaves(self, min_tiles: int = 0):
        """The segments stacked into one padded tile grid
        (:class:`repro.kernels.StackedLeaves`), memoized on this
        snapshot: segments are immutable, so stacking is a one-time cost
        per compaction -- the mutable index carries the memo forward
        across publishes (:meth:`adopt_stacked_from`), and tombstone
        republishes rewrite only the changed ids planes.  The rewrite is
        applied **lazily** here, on first stacked access: a base stack
        plus pending ids-plane diffs travel through publishes as plain
        Python references, so the publish path (and in particular the
        delete path, which republishes per tombstone) never dispatches
        device work.  On a placed snapshot the planes are built on its
        :attr:`device`; ``min_tiles`` (a first build only) pads the tile
        axis up to a count other shards' stacks share."""
        stk = self.__dict__.get("_stacked")
        if stk is None and self.segments:
            base = self.__dict__.get("_stacked_base")
            if base is not None:
                # the deletes' cost paid on the query path
                with spans.span("p2h.stacked.ids_rewrite"):
                    stk = base.with_updated_ids(
                        self.__dict__.get("_stacked_pending") or {})
                spans.count("ids_rewrites")
                nbytes = stk.ids.nbytes
            else:
                from repro.kernels.stacked_sweep import StackedLeaves

                stk = StackedLeaves.from_segments(
                    self.segments, device=self.device, tiles=min_tiles)
                nbytes = stk.nbytes
            if self.device is not None:
                spans.count("exchange_upload_bytes", nbytes)
            object.__setattr__(self, "_stacked", stk)
        return stk

    def adopt_stacked_from(self, prev: "Snapshot") -> None:
        """Carry ``prev``'s stacked-leaf memo forward when the segment
        set allows it (publish-time hook of the mutable index): same
        uids + unchanged geometry means delta-only publishes reuse the
        stack as-is and tombstone publishes defer an ids-plane diff for
        :meth:`stacked_leaves` to apply on first access.  Pure Python --
        publish stays O(changed segments) bookkeeping."""
        if prev is None:
            return
        base = prev.__dict__.get("_stacked")
        pending = {}
        if base is None:
            base = prev.__dict__.get("_stacked_base")
            pending = dict(prev.__dict__.get("_stacked_pending") or {})
        if base is None or len(self.segments) != len(prev.segments):
            return
        if tuple(s.uid for s in self.segments) != base.uids:
            return  # compaction changed the set: rebuild lazily
        for i, (new, old) in enumerate(zip(self.segments, prev.segments)):
            if new is old:
                continue
            if new.tree.points is not old.tree.points:
                return  # geometry rewrite: rebuild lazily
            pending[i] = new  # latest plane wins over an older diff
        if pending:
            object.__setattr__(self, "_stacked_base", base)
            object.__setattr__(self, "_stacked_pending", pending)
        else:
            object.__setattr__(self, "_stacked", base)

    def adopt_prebuilt_stacked(self, stk, sources) -> bool:
        """Adopt a stack the background compactor built (and pre-warmed)
        *before* the publish flipped the epoch.  ``sources`` are the
        segments ``stk`` was stacked from; any segment that moved on
        since (a tombstone raced the prewarm) becomes a pending ids-plane
        diff, exactly like :meth:`adopt_stacked_from`.  Returns False --
        leaving the lazy-rebuild path in charge -- when the published
        segment set no longer matches the prebuilt stack."""
        if stk is None or len(sources) != len(self.segments):
            return False
        if tuple(s.uid for s in self.segments) != stk.uids:
            return False
        pending = {}
        for i, (new, old) in enumerate(zip(self.segments, sources)):
            if new is old:
                continue
            if new.tree.points is not old.tree.points:
                return False
            pending[i] = new
        if pending:
            object.__setattr__(self, "_stacked_base", stk)
            object.__setattr__(self, "_stacked_pending", pending)
        else:
            object.__setattr__(self, "_stacked", stk)
        return True

    def live_points(self):
        """The live set as ``(points (n, d), gids (n,))`` host arrays --
        the brute-force-oracle view (tests/benchmarks) and the input a
        from-scratch rebuild would consume."""
        pts, gids = [], []
        for v in self.deltas:
            mask = v.gids >= 0
            pts.append(v.points[mask])
            gids.append(v.gids[mask])
        for s in self.segments:
            p, g = s.live_rows()
            pts.append(p)
            gids.append(g)
        if not pts:
            return (np.zeros((0, self.d), np.float32),
                    np.zeros((0,), np.int32))
        return np.concatenate(pts), np.concatenate(gids)

    def query(self, queries, k: int = 1, *, method: str = "sweep",
              frac: float = 1.0, lambda_cap=None,
              return_counters: bool = False, include_deltas: bool = True,
              stacked: bool | None = None, probe_tiles: int | None = None,
              probe_dtype: str | None = None,
              mesh=None, mesh_axis: str = "shard"):
        """Exact (or beam-budgeted) top-k over the snapshot's live set.

        ``queries`` must already be normalized (B, d) float32.  Returned
        ids are *global* ids.  ``lambda_cap`` (B,) optional valid upper
        bounds on the true k-th distance (serving engine warm start);
        budgeted ``method="beam"`` never consumes caps (same rule as the
        engine) and is budgeted on segments only -- the delta is always
        scanned exactly.  ``include_deltas=False`` scans segments only:
        the two-round exchange's round 2 uses it because round 1 already
        scanned every delta exactly and its candidates reach the final
        merge (a delta point displaced from round-1's top-k was displaced
        by k closer real points, so it cannot be in the global top-k).

        ``stacked`` controls the segment-parallel sweep (one two-pass
        device program over all segments -- probe-tightened cap, main
        sweep, in-launch global merge of the per-segment planes *and*
        the delta candidates; no host-side per-segment merge -- instead
        of the sequential cap-threading walk): ``None`` auto-promotes
        the exact ``sweep``/``pallas`` methods at live-segment fan-out
        >= ``repro.kernels.stacked_sweep.STACKED_FANOUT_DEFAULT``,
        ``True`` forces it, ``False`` forbids it.  ``method="stacked"``
        is the explicit dispatch-route spelling of ``stacked=True``.
        ``probe_tiles`` is the probe-pass width (None = library default;
        0 = the single-pass entry-cap-only sweep) and ``probe_dtype``
        its precision ("f32"/"bf16"/"int8", None = f32: the quantized
        probe reads half/quarter the tile bytes, pass B rescans in f32,
        answers stay bit-exact).  ``mesh`` (a 1-D
        device mesh, see ``repro.launch.mesh.make_serving_mesh``) shards
        the stacked launch's segment axis over ``mesh_axis`` -- only the
        stacked route consumes it; the sequential walk ignores it.
        Answers are exact on every path; only tile-skip counters differ.
        """
        bd, bi, counters, pending = self._query(
            queries, k, method=method, frac=frac, lambda_cap=lambda_cap,
            include_deltas=include_deltas, stacked=stacked,
            probe_tiles=probe_tiles, probe_dtype=probe_dtype, mesh=mesh,
            mesh_axis=mesh_axis)
        bd, bi = np.asarray(bd), np.asarray(bi)
        for cnt in pending:
            counters += np.asarray(cnt, np.int64)
        if return_counters:
            return bd, bi, counters
        return bd, bi

    def dispatch(self, queries, k: int = 1, *, method: str = "sweep",
                 frac: float = 1.0, lambda_cap=None,
                 include_deltas: bool = True):
        """:meth:`query` without waiting for the device: the answer stays
        on the snapshot's device until :meth:`Pending.result` reads it, so
        a caller can start the same work on several shards' devices
        before it reads any of them (round 1 of the two-round
        exchange).  Sequential and beam methods only."""
        return Pending(*self._query(queries, k, method=method, frac=frac,
                                    lambda_cap=lambda_cap,
                                    include_deltas=include_deltas,
                                    stacked=False))

    def _query(self, queries, k, *, method, frac, lambda_cap,
               include_deltas, stacked, probe_tiles=None, probe_dtype=None,
               mesh=None, mesh_axis="shard"):
        """The body of :meth:`query`: ``(dists, ids)`` as device arrays,
        the host counters so far and the device counters still to add."""
        if isinstance(queries, jax.Array) and queries.ndim == 2:
            q = queries.astype(jnp.float32)  # stays where it was placed
        else:
            q = jnp.asarray(np.atleast_2d(queries), jnp.float32)
        B = q.shape[0]
        counters = np.zeros((8,), np.int64)
        pending = []

        if include_deltas:
            bd, bi, nver = self.delta_candidates(q, k)
            counters[search.C_VERIFIED] += nver
        else:
            bd = jnp.full((B, k), jnp.inf, jnp.float32)
            bi = jnp.full((B, k), -1, jnp.int32)
        exact = method != "beam"
        ext = (None if lambda_cap is None or not exact
               else jnp.asarray(lambda_cap, jnp.float32).reshape(-1))
        if self.segments and self._use_stacked(method, stacked):
            # entry cap for every segment: the delta scan's merged k-th,
            # tightened by any externally-valid cap; the probe pass then
            # tightens it further on device, and the launch merges the
            # per-segment planes with the delta candidates itself
            cap = bd[:, k - 1]
            if ext is not None:
                cap = jnp.minimum(cap, ext)
            bd, bi, cnt = self._stacked_query(
                q, k, method=method, cap=cap, probe_tiles=probe_tiles,
                probe_dtype=probe_dtype,
                extra_d=bd, extra_i=bi, mesh=mesh, mesh_axis=mesh_axis)
            counters += np.asarray(cnt, np.int64)
        else:
            for seg in self.segments:
                if seg.live == 0:
                    continue
                cap = None
                if exact:
                    cap = bd[:, k - 1]  # running merged k-th: a valid cap
                    if ext is not None:
                        cap = jnp.minimum(cap, ext)
                if self.device is None:
                    tree, gids = seg.tree, jnp.asarray(seg.gids)
                else:
                    tree, gids = seg.resident_tree(self.device)
                sd, si, cnt = _segment_query(tree, q, k, method=method,
                                             frac=frac,
                                             variant=self.variant,
                                             lambda_cap=cap)
                sg = jnp.where(si >= 0,
                               jnp.take(gids,
                                        jnp.clip(si, 0, len(seg.gids) - 1)),
                               -1)
                bd, bi = search.merge_topk(
                    jnp.concatenate([bd, sd], axis=1),
                    jnp.concatenate([bi, sg], axis=1), k)
                pending.append(cnt)
        return bd, bi, counters, pending

    def delta_candidates(self, q, k: int):
        """The delta scan's merged top-k over every delta view -- the
        exact entry state the stacked route caps and merges against.
        Returns ``(dists (B, k), global ids (B, k), rows verified)``.
        One definition shared by :meth:`query`, the benches' skip
        profiles and the live-skip regression fence, so every consumer
        measures the same entry state."""
        with spans.span("p2h.delta.scan"):
            q = jnp.asarray(q, jnp.float32)
            B = q.shape[0]
            bd = jnp.full((B, k), jnp.inf, jnp.float32)
            bi = jnp.full((B, k), -1, jnp.int32)
            verified = 0
            for view in self.deltas:
                dd, di = delta_topk(view, q, k, device=self.device)
                bd, bi = search.merge_topk(
                    jnp.concatenate([bd, dd], axis=1),
                    jnp.concatenate([bi, di], axis=1), k)
                verified += view.live * B
        return bd, bi, verified

    def _use_stacked(self, method: str, stacked: bool | None) -> bool:
        """Resolve the segment-parallel dispatch decision."""
        if method == "stacked":
            return True
        if method not in ("sweep", "pallas"):
            return False  # dfs walks trees, beam budgets per segment
        if stacked is not None:
            return bool(stacked)
        from repro.kernels.stacked_sweep import (STACKED_DENSITY_DEFAULT,
                                                 STACKED_FANOUT_DEFAULT,
                                                 tile_density)

        n_live = sum(1 for s in self.segments if s.live)
        # heavily ragged stacks spend the launch on pad tiles the jnp
        # path can only mask -- stay sequential below the density floor
        return (n_live >= STACKED_FANOUT_DEFAULT
                and tile_density(self.segments) >= STACKED_DENSITY_DEFAULT)

    def _stacked_query(self, q, k: int, *, method: str, cap,
                       probe_tiles=None, probe_dtype=None,
                       extra_d=None, extra_i=None,
                       mesh=None, mesh_axis: str = "shard"):
        """One two-pass stacked launch over all segments (probe + main +
        in-launch merge with the ``extra`` delta candidates); returns the
        merged ``(dists (B, k), global ids (B, k), counters)``."""
        from repro.kernels.stacked_sweep import stacked_sweep_query

        is_bc = self.variant == "bc"
        # method="pallas" pins the kernel (interpret-mode parity runs);
        # sweep/stacked auto-resolve: Mosaic on TPU, vmapped jnp ref off
        use_kernel = True if method == "pallas" else None
        fd, fi, cnt, _ = stacked_sweep_query(
            self.stacked_leaves(), q, k, lambda_cap=cap,
            probe_tiles=probe_tiles, probe_dtype=probe_dtype,
            extra_d=extra_d, extra_i=extra_i,
            use_ball=is_bc, use_cone=is_bc, use_kernel=use_kernel,
            mesh=mesh, mesh_axis=mesh_axis)
        return fd, fi, cnt


@dataclasses.dataclass(frozen=True)
class Pending:
    """A dispatched :meth:`Snapshot.dispatch`: device answers and the
    counters still on the device."""

    dists: Any
    ids: Any
    counters: np.ndarray
    device_counters: list

    def result(self):
        """``(dists, ids, counters)`` on the host (waits for the device)."""
        with spans.span("p2h.device_wait"):
            jax.block_until_ready((self.dists, self.ids,
                                   self.device_counters))
        counters = self.counters.copy()
        for cnt in self.device_counters:
            counters += np.asarray(cnt, np.int64)
        return np.asarray(self.dists), np.asarray(self.ids), counters


@dataclasses.dataclass(frozen=True)
class ShardedSnapshot:
    """A cross-shard snapshot pin: one per-shard :class:`Snapshot` each,
    plus the **epoch vector** (one epoch per shard).

    Each component is individually consistent (atomic per-shard publish);
    the vector pins the exact cross-shard state a query ran against while
    background compactors republish shards independently.  Validity of a
    lambda cap against this view is per-shard: a cap recorded at epoch
    vector ``E`` is valid iff ``E[s] >= last_delete_epoch[s]`` for every
    shard ``s`` -- one shard's delete must not (and with the vector form
    does not) invalidate caps recorded against the other shards' states.

    ``query`` runs the two-round lambda exchange
    (:func:`repro.core.distributed.two_round_exchange`) with each shard's
    pinned ``Snapshot`` as the round backend, so the exchange spans
    heterogeneous shard states: delta-only, multi-segment, mid-compaction
    (sealed delta views included) -- all valid round participants.
    """

    shards: tuple  # tuple[Snapshot, ...] -- index s = shard s's pin
    epoch: tuple  # per-shard epoch vector
    last_delete_epoch: tuple  # per-shard delete-epoch vector
    variant: str
    d: int
    #: router version this view was pinned under (0 = un-versioned hash
    #: router).  A split/merge changes the shard count, so the epoch
    #: *vector length* changes with it and the lambda cache's staleness
    #: check already invalidates caps across a resharding; this field
    #: makes the placement generation observable to the serving layer.
    router_version: int = 0
    #: serving device mesh (1-D, ``repro.launch.mesh.make_serving_mesh``)
    #: the stacked round-2 launch shards its segment axis over; ``None``
    #: = single-program placement.  Placement, not state -- excluded
    #: from snapshot identity.
    mesh: Any = dataclasses.field(default=None, compare=False)
    mesh_axis: str = dataclasses.field(default="shard", compare=False)

    # ------------------------------------------------------------------
    @property
    def num_shards(self) -> int:
        return len(self.shards)

    @property
    def live_count(self) -> int:
        return sum(s.live_count for s in self.shards)

    @property
    def max_norm(self) -> float:
        return max((s.max_norm for s in self.shards), default=0.0)

    @property
    def segments(self) -> tuple:
        """All shards' segments, flattened (fan-out accounting)."""
        return tuple(seg for s in self.shards for seg in s.segments)

    @property
    def deltas(self) -> tuple:
        """All shards' delta views, flattened."""
        return tuple(v for s in self.shards for v in s.deltas)

    @property
    def delta_live(self) -> int:
        return sum(s.delta_live for s in self.shards)

    def live_points(self):
        """Union of the shard live sets as ``(points, gids)`` host
        arrays -- the brute-force-oracle view."""
        parts = [s.live_points() for s in self.shards]
        pts = [p for p, _ in parts if len(p)]
        gids = [g for _, g in parts if len(g)]
        if not pts:
            return (np.zeros((0, self.d), np.float32),
                    np.zeros((0,), np.int32))
        return np.concatenate(pts), np.concatenate(gids)

    @property
    def tombstone_frac(self) -> float:
        """Dead fraction over all shards' sealed rows (dispatch signal)."""
        live = sum(seg.live for seg in self.segments)
        dead = sum(seg.dead for seg in self.segments)
        return dead / (live + dead) if live + dead else 0.0

    def query(self, queries, k: int = 1, *, method: str = "sweep",
              frac: float = 1.0, frac1: float = 0.25, lambda_cap=None,
              return_counters: bool = False, return_info: bool = False,
              stacked: bool | None = None, probe_tiles: int | None = None,
              probe_dtype: str | None = None, deadline=None,
              resilience=None):
        """Top-k over the cross-shard live set via the two-round lambda
        exchange; same contract as :meth:`Snapshot.query` (normalized
        queries in, global ids out) plus ``frac1``, the round-1 prefix
        fraction.  ``return_info`` also returns the exchange's
        ``lambda0`` / per-shard round-1 k-th distances (invariant-test
        surface).  ``stacked`` controls round 2's segment-parallel form
        (all shards' segments in one two-pass device program under
        lambda0 -- probe-tightened cap, in-launch merge, see
        :func:`repro.core.distributed.two_round_exchange`);
        ``probe_tiles`` is that program's probe-pass width and
        ``probe_dtype`` its precision (answers bit-exact either way).
        ``deadline`` / ``resilience`` route through the exchange's
        degraded-capable branch (supervised per-shard calls, bounded
        degradation -- see
        :func:`repro.core.distributed.two_round_exchange`)."""
        from repro.core.distributed import two_round_exchange

        out = two_round_exchange(self.shards, queries, k, frac1=frac1,
                                 method=method, frac=frac,
                                 lambda_cap=lambda_cap,
                                 return_info=return_info, stacked=stacked,
                                 probe_tiles=probe_tiles,
                                 probe_dtype=probe_dtype,
                                 mesh=self.mesh, mesh_axis=self.mesh_axis,
                                 deadline=deadline, resilience=resilience)
        if return_info:
            bd, bi, cnt, info = out
            return (bd, bi, cnt, info) if return_counters else (bd, bi, info)
        bd, bi, cnt = out
        return (bd, bi, cnt) if return_counters else (bd, bi)


def _segment_query(tree: FlatTree, q, k: int, *, method: str, frac: float,
                   variant: str, lambda_cap) -> Any:
    """One backend call over one segment tree (local ids returned)."""
    is_bc = variant == "bc"
    common = dict(use_ball=is_bc, use_cone=is_bc)
    if method == "dfs":
        return search.dfs_search(tree, q, k, use_collab=is_bc,
                                 lambda_cap=lambda_cap, **common)
    if method == "sweep":
        return search.sweep_search(tree, q, k, frac=1.0,
                                   lambda_cap=lambda_cap, **common)
    if method == "beam":
        return search.sweep_search(tree, q, k, frac=frac, **common)
    if method == "pallas":
        from repro.kernels import ops

        return ops.sweep_search_pallas(tree, q, k, frac=1.0,
                                       lambda_cap=lambda_cap, **common)
    raise ValueError(f"unknown method {method!r}")
