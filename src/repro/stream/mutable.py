"""MutableP2HIndex: streaming inserts/deletes over the Ball/BC-Tree.

The LSM-style composition (module layout mirrors the classic
memtable / sstable / compactor split):

  * writes (``insert`` / ``delete``) hit a fixed-capacity
    :class:`~repro.stream.delta.DeltaBuffer` and per-segment tombstone
    masks -- O(1) and O(segment-copy) respectively, never a tree rebuild
    on the write path;
  * a :class:`~repro.stream.compaction.CompactionPolicy` decides when to
    fold the delta (and tombstone-heavy segments) into fresh sealed
    :class:`~repro.stream.snapshot.Segment` trees via the paper's cheap
    ``build_tree`` path -- inline by default, or on a background thread
    (``background=True``) so the write path never stalls on a rebuild;
  * every mutation publishes a new epoch-numbered immutable
    :class:`~repro.stream.snapshot.Snapshot` by swapping one reference --
    queries (and serving-engine micro-batches, which pin a snapshot) are
    never torn.

Thread model: one re-entrant writer lock serializes mutations and
snapshot publishing; readers are lock-free (they read ``self._snapshot``
once).  Background compaction pins its inputs under the lock (sealing
the delta and swapping in a fresh one), builds trees outside the lock,
and republishes under the lock -- deletes that raced the build are
recorded and re-applied to the new segment before it becomes visible.

Durability: ``save``/``load`` persist every segment/delta through
:class:`repro.checkpoint.CheckpointManager` (atomic rename, per-leaf
checksums).  With a :class:`repro.stream.wal.ShardWal` attached
(:meth:`MutableP2HIndex.attach_wal`), every insert/delete is also
appended to the log before it is acknowledged, the checkpoint records
the ``(wal_offset, wal_seq)`` frontier it covers, and
``load(..., wal=...)`` replays the WAL tail idempotently -- recovery to
the last *acknowledged* write, not just the last checkpoint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import logging
import threading
import time
from typing import Any

import numpy as np

from repro.core import search
from repro.core.balltree import append_ones, normalize_query
from repro.runtime import spans
from repro.stream.compaction import CompactionPlan, CompactionPolicy
from repro.stream.delta import DeltaBuffer
from repro.stream.snapshot import DeltaView, Segment, Snapshot

__all__ = ["MutableP2HIndex"]

logger = logging.getLogger(__name__)

_STATE_FORMAT = "p2h-stream"
_STATE_VERSION = 1


def query_via_engine(index, engine, queries, k, *, method, normalize,
                     return_stats, kw):
    """Shared ``query(engine=...)`` delegation for the mutable index
    front-ends (single-host and sharded): flush pending streaming work,
    serve through the engine, report this call's counter delta."""
    assert engine.mutable is index, "engine serves a different index"
    engine.flush()
    before = engine.total_counters()
    bd, bi = engine.query(queries, k, normalize=normalize, method=method,
                          **kw)
    if return_stats:
        delta = engine.total_counters() - before
        return bd, bi, search.SearchStats(delta)
    return bd, bi


class MutableP2HIndex:
    """Read-write P2HNNS index with LSM-style segments + delta buffer."""

    def __init__(self, dim: int, *, n0: int = 128, variant: str = "bc",
                 policy: CompactionPolicy | None = None, seed: int = 0,
                 background: bool = False, device=None):
        assert variant in ("ball", "bc"), variant
        #: the device every device-side piece of this index is made on
        #: (sealed segments' sweep views, placed at publish; stacked
        #: planes; the delta upload); None = JAX's default placement
        self.device = device
        self.dim = int(dim)  # raw point dimensionality
        self.d = self.dim + 1  # with the appended 1-coordinate
        self.n0 = int(n0)
        self.variant = variant
        self.policy = policy or CompactionPolicy()
        self.seed = int(seed)

        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._delta = DeltaBuffer(self.policy.delta_capacity, self.d)
        self._sealed: list[DeltaBuffer] = []  # frozen inputs of an
        #                                       in-flight compaction
        self._segments: dict[int, Segment] = {}  # uid -> segment (ordered)
        self._locator: dict[int, tuple] = {}  # gid -> location
        self._next_gid = 0
        self._next_uid = 0
        self._epoch = 0
        self._last_delete_epoch = 0
        self._live_count = 0
        self._max_norm = 0.0
        self._compacting = False
        self._pending_tombstones: set[int] = set()
        self._compact_errors: list[BaseException] = []
        self.compaction_log: list[dict] = []  # wall/rows/reason per run
        self._tl = threading.local()  # delete-path compaction tripwire
        # write admission + close() leak tripwire
        self._admission = {"seals": 0, "stalls": 0, "compactor_leaked": 0}
        #: optional repro.stream.wal.ShardWal -- when attached, every
        #: insert/delete appends a record (under the writer lock, which
        #: also serializes the single-writer log) and the public write
        #: calls run the group commit before returning
        self._wal = None
        self.last_saved_wal = None  # (wal_offset, wal_seq) of last save
        self._wal_replayed_seq = 0  # highest seq wal_replay applied
        #: optional callable(prebuilt StackedLeaves) the compactor runs
        #: during pre-publish warmup -- the sharded front-end hooks this
        #: to also pre-compile the cross-shard round-2 program
        self._warmup_hook = None
        #: optional threading.Lock shared by every shard of a sharded
        #: front-end: held from pre-publish warmup through the epoch
        #: flip, it serializes concurrent shard publishes so each warmup
        #: predicts the cross-shard composition it will actually publish
        #: into (compactions overlap ~80% under heavy churn; without the
        #: gate, two racing publishes warm each other's stale state)
        self._publish_gate = None

        self._background = bool(background)
        self._stop = False
        self._compact_event = threading.Event()
        self._compactor: threading.Thread | None = None
        if self._background:
            self._compactor = threading.Thread(
                target=self._compactor_loop, daemon=True)
            self._compactor.start()

        self._snapshot = self._make_snapshot()

    # ------------------------------------------------------------------
    @classmethod
    def from_data(cls, data: np.ndarray, *, gids: np.ndarray | None = None,
                  **kw: Any) -> "MutableP2HIndex":
        """Bulk-load: seed with one sealed segment over ``data``.

        ``gids`` (optional): externally-allocated global ids, one per
        row -- the sharded front-end routes a globally-numbered dataset
        across shards, so each shard's segment must carry the caller's
        ids rather than a local 0..n-1 numbering.
        """
        data = np.asarray(data, np.float32)
        self = cls(data.shape[1], **kw)
        self.bulk_seed(data, gids=gids)
        return self

    def bulk_seed(self, data: np.ndarray, *,
                  gids: np.ndarray | None = None) -> None:
        """Seed an *empty* index with one sealed segment over ``data``
        (the bulk-load path of :meth:`from_data`, callable on a shard the
        sharded front-end already constructed)."""
        data = np.asarray(data, np.float32)
        pts = append_ones(data)
        if gids is None:
            gids = np.arange(len(pts), dtype=np.int32)
        else:
            gids = np.asarray(gids, np.int32)
            assert len(gids) == len(pts), (len(gids), len(pts))
        with self._lock:
            assert not self._segments and self._delta.length == 0, \
                "bulk_seed requires an empty index"
            if len(pts):
                seg = Segment.from_points(self._alloc_uid(), pts, gids,
                                          n0=self.n0, seed=self.seed)
                self._segments[seg.uid] = seg
                pid = np.asarray(seg.tree.point_ids)
                for local in pid[pid >= 0]:
                    self._locator[int(gids[local])] = (
                        "seg", seg.uid, int(local))
                self._max_norm = float(np.linalg.norm(pts, axis=1).max())
                self._next_gid = int(gids.max()) + 1
            self._live_count = len(pts)
            self._publish()

    # ------------------------------------------------------------------
    # write path
    # ------------------------------------------------------------------
    def insert(self, point: np.ndarray, *, gid: int | None = None) -> int:
        """Insert one raw (dim,) point; returns its stable global id.

        ``gid`` (optional): use an externally-allocated global id (the
        sharded front-end owns the id space); must be fresh."""
        x = np.asarray(point, np.float32).reshape(-1)
        assert x.shape == (self.dim,), (x.shape, self.dim)
        with spans.span("p2h.write.insert"):
            with self._lock:
                gid = self._insert_one_locked(x, gid=gid)
                self._publish()
                self._wal_log_insert(x, gid)
                self._maybe_compact_locked()
            self._wal_commit()
        return gid

    def insert_batch(self, points: np.ndarray,
                     gids: np.ndarray | None = None) -> np.ndarray:
        """Bulk insert: one lock hold, one snapshot publish at the end
        (readers only ever need the final state visible; mid-batch
        compactions still run when the delta fills).  ``gids``: optional
        externally-allocated ids, one per row."""
        pts = np.atleast_2d(np.asarray(points, np.float32))
        assert pts.shape[1] == self.dim, (pts.shape, self.dim)
        if gids is not None:
            assert len(gids) == len(pts), (len(gids), len(pts))
        out = np.empty((len(pts),), np.int32)
        with spans.span("p2h.write.insert"):
            with self._lock:
                # rows go in as one block per delta fill; a full delta is
                # flushed before the next block, as before each row
                i = 0
                while i < len(pts):
                    self._make_room_locked()
                    m = min(len(pts) - i,
                            self._delta.capacity - self._delta.length)
                    out[i:i + m] = self._append_rows_locked(
                        pts[i:i + m], None if gids is None else gids[i:i + m])
                    if self._wal is not None:
                        for j in range(i, i + m):
                            self._wal_log_insert(pts[j], int(out[j]))
                    i += m
                self._publish()
                self._maybe_compact_locked()
            self._wal_commit()
        return out

    def _insert_one_locked(self, x: np.ndarray, *,
                           gid: int | None = None) -> int:
        """Append one point to the delta (compacting if full); no
        publish -- callers publish once per API call."""
        x1 = np.concatenate([x, np.ones((1,), np.float32)])
        self._make_room_locked()
        if gid is None:
            gid = self._next_gid
            self._next_gid += 1
        else:
            gid = int(gid)
            assert gid not in self._locator, f"gid {gid} already live"
            self._next_gid = max(self._next_gid, gid + 1)
        row = self._delta.append(x1, gid)
        self._locator[gid] = ("delta", id(self._delta), row)
        self._live_count += 1
        self._max_norm = max(self._max_norm, float(np.linalg.norm(x1)))
        return gid

    def _append_rows_locked(self, x: np.ndarray, gids) -> np.ndarray:
        """:meth:`_insert_one_locked` for rows ``x`` that fit the delta's
        free rows, as one block; returns their gids.  The max norm is
        taken row by row (``sqrt(x1 . x1)``, as ``np.linalg.norm`` of one
        row), so it is the per-row insert's to the bit."""
        m = len(x)
        x1 = np.concatenate([x, np.ones((m, 1), np.float32)], axis=1)
        if gids is None:
            g = np.arange(self._next_gid, self._next_gid + m, dtype=np.int64)
            self._next_gid += m
        else:
            g = np.asarray(gids, np.int64)
            for gid in g.tolist():
                assert gid not in self._locator, f"gid {gid} already live"
            assert len(set(g.tolist())) == m, "gids repeat within a batch"
            self._next_gid = max(self._next_gid, int(g.max()) + 1)
        rows = self._delta.extend(x1, g)
        where = id(self._delta)
        self._locator.update(
            (gid, ("delta", where, r))
            for gid, r in zip(g.tolist(), rows.tolist()))
        self._live_count += m
        self._max_norm = max(self._max_norm, float(np.sqrt(max(
            r.dot(r) for r in x1))))
        return g

    def _make_room_locked(self) -> None:
        """Flush (or, in background mode, seal) a full delta so the next
        row has a place."""
        while self._delta.full:
            self._raise_compact_errors_locked()  # don't spin forever
            if self._background:
                self._compact_event.set()
                if len(self._sealed) < self.policy.max_pending_seals:
                    # admission control: seal the full delta and keep
                    # writing into a fresh one instead of stalling the
                    # acknowledged write behind the compactor.  Sealed
                    # buffers stay queryable (snapshot delta views) and
                    # deletable (the locator walks them); the compactor
                    # consumes them like failure leftovers.
                    self._sealed.append(self._delta)
                    self._delta = DeltaBuffer(self.policy.delta_capacity,
                                              self.d)
                    self._admission["seals"] += 1
                else:
                    self._admission["stalls"] += 1
                    self._cond.wait(timeout=1.0)  # compactor republishes
            else:
                self._compact_locked(self._plan_locked())

    def delete(self, gid: int, *, commit: bool = True) -> bool:
        """Delete by global id; returns False if the id is not live.

        O(tombstone flip) + one snapshot publish.  Compaction is *never*
        run on this thread (the old inline ``_maybe_compact_locked`` here
        was the delete-p99 cliff: one unlucky delete paid a full rebuild
        under the writer lock): background mode signals the compactor
        thread, inline mode defers to the next insert / ``compact()``
        call.  A tripwire in ``_pin_inputs_locked`` asserts the
        invariant.

        ``commit=False`` logs the op but defers the WAL group commit to
        the caller (the sharded front-end runs it outside its migration
        lock, so deletes on other shards never queue behind one shard's
        fsync); the op is not acknowledged until that commit covers
        it."""
        gid = int(gid)
        with spans.span("p2h.write.delete"):
            self._tl.in_delete = True
            try:
                with self._lock:
                    ok = self._delete_locked(gid)
                    if ok:
                        self._wal_log(2, gid)  # OP_DELETE
            finally:
                self._tl.in_delete = False
            if ok and commit:
                self._wal_commit()
        return ok

    def _delete_locked(self, gid: int) -> bool:
        loc = self._locator.pop(gid, None)
        if loc is None:
            return False
        if loc[0] == "delta":
            _, buf_id, row = loc
            for buf in [self._delta, *self._sealed]:
                if id(buf) == buf_id:
                    buf.tombstone(row)
                    break
        else:
            _, uid, local = loc
            self._segments[uid] = \
                self._segments[uid].with_tombstone(local)
        if self._compacting:
            # the in-flight compaction copied its input rows before this
            # delete; re-apply it to the output at publish time
            self._pending_tombstones.add(gid)
        self._live_count -= 1
        self._last_delete_epoch = self._epoch + 1  # post-publish
        self._publish()
        if (self._background and not self._compacting
                and self._plan_locked()):
            self._compact_event.set()
        return True

    # ------------------------------------------------------------------
    # write-ahead log (repro.stream.wal)
    # ------------------------------------------------------------------
    def attach_wal(self, wal) -> None:
        """Attach a :class:`repro.stream.wal.ShardWal`: subsequent
        inserts/deletes are logged (and group-committed) before the
        write call returns.  Attach *after* any replay -- replayed ops
        are already in the log and must not be re-appended."""
        with self._lock:
            self._wal = wal

    def _wal_log_insert(self, x_raw: np.ndarray, gid: int) -> None:
        """Log one insert (raw ``(dim,)`` row; caller holds the lock)."""
        if self._wal is not None:
            self._wal.append(1, gid, self._epoch,  # OP_INSERT
                             np.asarray(x_raw, np.float32).tobytes(),
                             token=("ins", int(gid)))

    def _wal_log(self, op: int, gid: int, blob: bytes = b"") -> None:
        if self._wal is not None:
            self._wal.append(op, gid, self._epoch, blob,
                             token=("del", int(gid)) if op == 2 else None)

    def _wal_commit(self) -> None:
        """Group commit (off the writer lock): the public write call's
        acknowledgment point.  Per :class:`repro.stream.wal.WalConfig`,
        either this call's fsync covers the op now, or a later group
        commit does and the ``on_ack`` callback reports it then."""
        if self._wal is not None:
            self._wal.commit()

    def wal_replay(self, wal, *, from_offset: int = 0,
                   min_seq: int = 0) -> dict:
        """Replay a WAL tail into this (just-restored) index.

        Idempotent: records at ``seq <= min_seq`` (already covered by
        the checkpoint) are skipped, an insert whose gid is already live
        is skipped, a delete of a non-live gid is skipped -- so replaying
        the same tail twice (double restore) applies each op at most
        once.  After replay the epoch is bumped past the largest epoch
        any replayed record carried, keeping the published epoch
        monotone across a crash (an acked op's epoch never goes
        backwards).  Returns ``{"applied", "skipped", "ops"}``."""
        applied = skipped = seen = 0
        with self._lock:
            # replaying the same log twice into one instance must be a
            # no-op: the gid-liveness guards alone would re-apply an
            # insert+delete *pair* (dead gid -> reinsert -> redelete),
            # converging to the same live set but churning epochs
            min_seq = max(min_seq, self._wal_replayed_seq)
            max_epoch = self._epoch
            for rec in wal.records(from_offset):
                if rec.op == 3:  # OP_ROUTER: placement, not data
                    continue
                seen += 1
                self._wal_replayed_seq = max(self._wal_replayed_seq,
                                             rec.seq)
                if rec.seq <= min_seq:
                    skipped += 1
                    continue
                max_epoch = max(max_epoch, rec.epoch)
                if rec.op == 1:  # OP_INSERT
                    if rec.gid in self._locator:
                        skipped += 1
                        continue
                    self._insert_one_locked(rec.point(), gid=rec.gid)
                    self._publish()
                    applied += 1
                elif rec.op == 2:  # OP_DELETE
                    if self._delete_locked(rec.gid):
                        applied += 1
                    else:
                        skipped += 1
            if max_epoch > self._epoch:
                # jump past the pre-crash epoch: _publish increments, so
                # the republished epoch is strictly greater than any
                # epoch an acked op ever observed
                self._epoch = max_epoch
                self._publish()
            self._maybe_compact_locked()
        return {"applied": applied, "skipped": skipped, "ops": seen}

    # ------------------------------------------------------------------
    # migration support (repro.stream.resharding)
    # ------------------------------------------------------------------
    def has_gid(self, gid: int) -> bool:
        with self._lock:
            return int(gid) in self._locator

    def live_gids(self) -> np.ndarray:
        """Snapshot of the live global ids (sorted, for determinism)."""
        with self._lock:
            out = np.fromiter(self._locator.keys(), np.int64,
                              len(self._locator))
        out.sort()
        return out

    def points_for(self, gids) -> tuple[np.ndarray, np.ndarray]:
        """Rows for the requested gids as ``(points (n, dim), found
        gids)`` -- raw rows without the appended 1-coordinate, ready for
        re-insertion into another shard.  Unknown (raced-away) gids are
        dropped, not errors: the migration copy loop re-checks liveness
        under its own lock."""
        pts, found = [], []
        with self._lock:
            for g in np.asarray(gids, np.int64):
                loc = self._locator.get(int(g))
                if loc is None:
                    continue
                if loc[0] == "delta":
                    _, buf_id, row = loc
                    for buf in [self._delta, *self._sealed]:
                        if id(buf) == buf_id:
                            pts.append(np.array(buf.points[row]))
                            found.append(int(g))
                            break
                else:
                    _, uid, local = loc
                    seg = self._segments[uid]
                    row = int(seg.row_of_local[local])
                    pts.append(np.asarray(seg.tree.points)[row])
                    found.append(int(g))
        if not pts:
            return (np.zeros((0, self.dim), np.float32),
                    np.zeros((0,), np.int64))
        # stored rows carry the appended 1-coordinate; strip it
        return (np.stack(pts)[:, :-1].astype(np.float32),
                np.asarray(found, np.int64))

    # ------------------------------------------------------------------
    # read path
    # ------------------------------------------------------------------
    def snapshot(self) -> Snapshot:
        """The current published snapshot (atomic reference read)."""
        return self._snapshot

    @property
    def epoch(self) -> int:
        return self._snapshot.epoch

    @property
    def live_count(self) -> int:
        return self._snapshot.live_count

    @property
    def max_norm(self) -> float:
        return self._snapshot.max_norm

    def admission_stats(self) -> dict:
        """Write-admission counters: ``seals`` (full deltas sealed
        without blocking the writer), ``stalls`` (writer had to wait for
        the compactor -- only once ``max_pending_seals`` sealed buffers
        piled up), ``pending_seals`` (current backlog), and
        ``compactor_leaked`` (close() timed out waiting for the
        compactor thread and abandoned it)."""
        with self._lock:
            return dict(self._admission,
                        pending_seals=len(self._sealed))

    def query(self, queries, k: int = 1, *, method: str | None = None,
              frac: float = 1.0, normalize: bool = True,
              return_stats: bool = False, engine: Any = None, **kw: Any):
        """Top-k over the live set; same contract as ``P2HIndex.query``.

        Pins one snapshot for the whole call.  ``method=None`` means
        ``"sweep"`` on the direct path; ``engine=`` routes through a
        :class:`repro.serve.P2HEngine` constructed over this index
        (micro-batching + epoch-tagged lambda warm start), where
        ``method=None`` means auto-dispatch and an explicit method forces
        that route.  ``stacked=`` / ``probe_tiles=`` / ``probe_dtype=``
        (forwarded to :meth:`Snapshot.query`) control the
        segment-parallel two-pass device program, its probe-pass width,
        and the probe's precision (f32/bf16/int8; answers bit-exact).
        """
        if engine is not None:
            return query_via_engine(self, engine, queries, k,
                                    method=method, normalize=normalize,
                                    return_stats=return_stats, kw=kw)
        q = np.atleast_2d(np.asarray(queries))
        if normalize:
            q = normalize_query(q)
        snap = self.snapshot()
        bd, bi, cnt = snap.query(q.astype(np.float32), k,
                                 method=method or "sweep",
                                 frac=frac, return_counters=True, **kw)
        if return_stats:
            return bd, bi, search.SearchStats(cnt)
        return bd, bi

    # ------------------------------------------------------------------
    # compaction
    # ------------------------------------------------------------------
    def compact(self, *, force: bool = False) -> bool:
        """Run one compaction now (inline, even in background mode).

        ``force=True`` merges everything (all segments + delta) into one
        fresh segment regardless of policy thresholds.  Returns whether a
        compaction ran.
        """
        with self._lock:
            # an in-flight background run owns _pending_tombstones and the
            # sealed delta; pinning on top of it would corrupt both
            while self._compacting:
                self._cond.wait(timeout=1.0)
            self._raise_compact_errors_locked()
            if force:
                plan = CompactionPlan(
                    include_delta=True,
                    segment_uids=tuple(self._segments),
                    reason="forced")
            else:
                plan = self._plan_locked()
            if not plan:
                return False
            self._compact_locked(plan)
        return True

    def wait_compaction(self) -> None:
        """Block until no background compaction is in flight; re-raises
        any error a background run died with."""
        with self._lock:
            while self._compacting:
                self._cond.wait(timeout=1.0)
            self._raise_compact_errors_locked()

    def _raise_compact_errors_locked(self) -> None:
        if self._compact_errors:
            raise self._compact_errors.pop(0)

    def close(self, *, timeout_s: float = 5.0) -> None:
        """Stop the background compactor (if any) and close the attached
        WAL (final group commit included); safe to call twice.

        A compactor that fails to stop within ``timeout_s`` (e.g. a
        wedged ``_warmup_hook``) is *leaked* -- it is a daemon thread,
        so the interpreter can still exit -- but no longer silently:
        the leak is logged and counted (``compactor_leaked`` in
        :meth:`admission_stats`)."""
        self._stop = True
        self._compact_event.set()
        if self._compactor is not None:
            self._compactor.join(timeout=timeout_s)
            if self._compactor.is_alive():
                with self._lock:
                    self._admission["compactor_leaked"] += 1
                logger.warning(
                    "compactor thread still alive %.1fs after close(); "
                    "leaking daemon thread %s", timeout_s,
                    self._compactor.name)
            self._compactor = None
        if self._wal is not None:
            self._wal.close()
            self._wal = None

    def _plan_locked(self) -> CompactionPlan:
        plan = self.policy.plan(delta_full=self._delta.full,
                                delta_live=self._delta.live,
                                segments=tuple(self._segments.values()))
        if not plan and self._sealed:
            # leftovers a failed background run never published: any
            # compaction consumes them (see _pin_inputs_locked), so force
            # one even though no policy threshold tripped
            plan = CompactionPlan(include_delta=True, segment_uids=(),
                                  reason="recover sealed delta")
        return plan

    def _maybe_compact_locked(self) -> None:
        if self._compacting:
            return
        if self._plan_locked():
            if self._background:
                self._compact_event.set()
            else:
                self._compact_locked(self._plan_locked())

    def _compactor_loop(self) -> None:
        while True:
            self._compact_event.wait()
            self._compact_event.clear()
            if self._stop:
                return
            try:
                with self._lock:
                    plan = self._plan_locked()
                    if not plan or self._compacting:
                        continue
                    pin = self._pin_inputs_locked(plan)
                # row copies, the tree build and the stacked-program
                # pre-compilation all run OFF the writer lock: raced
                # deletes land in _pending_tombstones (re-applied to the
                # built segment, by gid, at publish)
                self._collect_pinned_rows(pin)
                built = self._build_segment(pin)
                # the gate (shared across a sharded front-end's shards)
                # makes warm-then-flip atomic w.r.t. other shards'
                # publishes: the warmup's predicted cross-shard
                # composition IS the one this publish creates
                gate = self._publish_gate or contextlib.nullcontext()
                with gate:
                    prepub = self._prewarm_publish(pin, built)
                    with self._lock:
                        self._publish_compaction_locked(pin, built,
                                                        prepub=prepub)
                        if self._plan_locked():
                            # admission seals (or churn) accumulated
                            # while this run was in flight: keep draining
                            self._compact_event.set()
                        self._cond.notify_all()
                # post-publish re-warm (outside the gate): ungated
                # publishes -- deletes, seals -- may still have raced the
                # warmup; re-running the hook against the now-published
                # stack closes that window to publish-vs-first-query
                # (still on this thread, off the lock, best-effort)
                hook = self._warmup_hook
                if hook is not None and prepub is not None \
                        and prepub.get("stacked") is not None:
                    try:
                        hook(prepub["stacked"])
                    except Exception as e:
                        from repro.kernels.stacked_sweep import \
                            record_warm_failure
                        record_warm_failure("post-publish warm-up hook", e)
            except BaseException as e:
                # never die wedged: writers blocked on _compacting would
                # hang forever.  Pinned buffers stay in _sealed (still
                # queryable, rows not lost) and the next compaction
                # re-consumes them; the error surfaces at the next
                # wait_compaction()/compact()/save()/insert().
                with self._lock:
                    # keep the latest error only: retries of a persistent
                    # failure surface once, not once per attempt
                    self._compact_errors = [e]
                    self._compacting = False
                    self._pending_tombstones = set()
                    self._cond.notify_all()

    def _compact_locked(self, plan: CompactionPlan) -> None:
        """Inline compaction: pin + build + publish while holding the
        lock (the write-path pause that bench_stream measures)."""
        if not plan:
            return
        pin = self._pin_inputs_locked(plan)
        self._collect_pinned_rows(pin)
        built = self._build_segment(pin)
        self._publish_compaction_locked(pin, built)
        self._cond.notify_all()

    # -- compaction phases (pin/build/publish) --------------------------
    def _pin_inputs_locked(self, plan: CompactionPlan) -> dict:
        """Seal the delta (if consumed) and capture input *references*
        -- O(1) under the lock; the row copies happen in
        :meth:`_collect_pinned_rows`, outside it in background mode.

        Any buffers already in ``_sealed`` are admission seals or
        leftovers of a failed background run; every compaction
        re-consumes them so their rows eventually land in a segment."""
        assert not getattr(self._tl, "in_delete", False), \
            "compaction must never run on a delete caller's thread"
        t0 = time.perf_counter()
        pinned = list(self._sealed)
        if plan.include_delta:
            buf = self._delta
            self._sealed.append(buf)
            self._delta = DeltaBuffer(self.policy.delta_capacity, self.d)
            pinned.append(buf)
        # pinned segment objects, not uids: deletes that race the build
        # replace self._segments entries with re-tombstoned copies, and
        # those deletes are re-applied by gid at publish anyway
        segs = [self._segments[uid] for uid in plan.segment_uids]
        self._compacting = True
        self._pending_tombstones = set()
        return dict(plan=plan, bufs=pinned, segs=segs, t0=t0)

    def _collect_pinned_rows(self, pin: dict) -> None:
        """Copy the pinned inputs' live rows into ``pin`` -- safe off
        the lock once ``_compacting`` is set: pinned segments are
        immutable objects, pinned buffers only receive single-word
        tombstone writes, and any delete that races either lands in
        ``_pending_tombstones`` and is re-applied by gid at publish."""
        parts_p, parts_g = [], []
        for buf in pin["bufs"]:
            p, g = buf.live_rows()
            parts_p.append(p)
            parts_g.append(g)
        for seg in pin["segs"]:
            p, g = seg.live_rows()
            parts_p.append(p)
            parts_g.append(g)
        pin["points"] = (np.concatenate(parts_p) if parts_p
                         else np.zeros((0, self.d), np.float32))
        pin["gids"] = (np.concatenate(parts_g) if parts_g
                       else np.zeros((0,), np.int32))

    def _build_segment(self, pin: dict) -> Segment | None:
        """Tree build over the pinned rows -- runs outside the lock in
        background mode."""
        if len(pin["gids"]) == 0:
            return None
        return Segment.from_points(self._alloc_uid(), pin["points"],
                                   pin["gids"], n0=self.n0,
                                   seed=self.seed + self._epoch + 1)

    def _prewarm_publish(self, pin: dict, built: Segment | None):
        """Pre-compilation of the post-compaction stacked state, run by
        the *background* compactor off the lock, before the publish
        flips the epoch: predict the post-publish segment set, stack it,
        replay the recently-seen query templates against it
        (:func:`repro.kernels.stacked_sweep.warm_stacked`), and prebuild
        the new segment's locator entries so the publish's lock hold is
        one dict update instead of a Python loop.  Only the compactor
        mutates the segment *set* while ``_compacting`` is held (deletes
        only replace objects), so the prediction can only go stale in
        ways :meth:`Snapshot.adopt_prebuilt_stacked` re-diffs.
        Best-effort: a failure is counted (``record_warm_failure``) and
        the first post-publish query pays the compile."""
        from repro.kernels.stacked_sweep import (StackedLeaves,
                                                 record_warm_failure,
                                                 warm_stacked)
        try:
            plan: CompactionPlan = pin["plan"]
            with self._lock:
                segs = [seg for uid, seg in self._segments.items()
                        if uid not in plan.segment_uids]
            if built is not None:
                segs.append(built)
            prepub = dict(stacked=None, sources=None, locator=None,
                          warmed=0)
            if segs:
                stk = StackedLeaves.from_segments(segs, device=self.device)
                prepub.update(stacked=stk, sources=tuple(segs))
                hook = self._warmup_hook
                if hook is None:
                    # single-host: the shard-local stack IS the serving
                    # program -- warm it
                    prepub["warmed"] = warm_stacked(stk)
                else:
                    # sharded: serving always goes through the hook's
                    # cross-shard concatenation; compiling the never-
                    # dispatched shard-local program would only burn CPU
                    # next to the query path
                    try:
                        hook(stk)
                        prepub["warmed"] += 1
                    except Exception as e:
                        record_warm_failure("pre-publish warm-up hook", e)
            if built is not None:
                # the exchange's round 1 beams each segment tree with its
                # own shape-keyed program; warm it for the new tree too,
                # or the first post-publish exchange compiles on-path
                from repro.core.distributed import warm_round1
                tree = built.tree
                if self.device is not None:
                    tree, _ = built.resident_tree(self.device,
                                                  on_path=False)
                prepub["warmed"] += warm_round1(
                    tree, is_bc=(self.variant == "bc"))
                pid = np.asarray(built.tree.point_ids)
                prepub["locator"] = {
                    int(built.gids[local]): ("seg", built.uid, int(local))
                    for local in pid[pid >= 0]}
            return prepub
        except Exception as e:  # warmup must never break the compaction
            record_warm_failure("pre-publish warm-up", e)
            return None

    def _publish_compaction_locked(self, pin: dict,
                                   built: Segment | None,
                                   prepub: dict | None = None) -> None:
        plan: CompactionPlan = pin["plan"]
        dead_gids = self._pending_tombstones
        if built is not None and dead_gids:
            # deletes that raced the build: mask them in the new segment
            # (vectorized -- this runs under the writer lock)
            dead = np.fromiter(dead_gids, np.int64, len(dead_gids))
            locals_ = np.nonzero(np.isin(built.gids, dead))[0]
            built = built.with_tombstones(locals_)
        for buf in pin["bufs"]:
            self._sealed.remove(buf)
        for uid in plan.segment_uids:
            del self._segments[uid]
        if built is not None:
            self._segments[built.uid] = built
            loc = (prepub.get("locator")
                   if prepub is not None else None)
            if loc is None:
                pid = np.asarray(built.tree.point_ids)
                loc = {int(built.gids[local]): ("seg", built.uid,
                                                int(local))
                       for local in pid[pid >= 0]}
            for gid in dead_gids:  # never resurrect a raced delete
                loc.pop(gid, None)
            self._locator.update(loc)
        self._compacting = False
        self._pending_tombstones = set()
        self._publish(prepub=prepub)
        t1 = time.perf_counter()
        self.compaction_log.append(dict(
            wall_s=t1 - pin["t0"],
            # perf_counter interval endpoints: lets a multi-shard driver
            # measure how much compaction work overlapped across shards
            t0_s=pin["t0"],
            t1_s=t1,
            rows=int(len(pin["gids"])),
            reason=plan.reason,
            epoch=self._epoch,
            warmed=(0 if prepub is None else int(prepub["warmed"])),
        ))

    # ------------------------------------------------------------------
    def _alloc_uid(self) -> int:
        with self._lock:
            uid = self._next_uid
            self._next_uid += 1
            return uid

    def _make_snapshot(self) -> Snapshot:
        views = [DeltaView(*self._delta.frozen_view())]
        views += [DeltaView(*b.frozen_view()) for b in self._sealed]
        return Snapshot(
            epoch=self._epoch,
            last_delete_epoch=self._last_delete_epoch,
            segments=tuple(self._segments.values()),
            deltas=tuple(views),
            live_count=self._live_count,
            max_norm=self._max_norm,
            variant=self.variant,
            n0=self.n0,
            d=self.d,
            device=self.device,
        )

    def _publish(self, prepub: dict | None = None) -> None:
        """Atomic snapshot swap (caller holds the lock).  The new
        snapshot adopts the previous one's stacked-leaf cache when the
        segment set allows it (delta-only publishes reuse it as-is,
        tombstone publishes swap just the changed ids planes -- the
        stack's derived probe operands, e.g. the lane-padded points
        plane, ride along because geometry is shared), so the
        segment-parallel sweep pays its stacking + padding cost once per
        compaction, not once per publish.  A compaction publish passes
        the compactor's pre-built *and pre-warmed* stack (``prepub``):
        adopting it means the first query on the new epoch hits a
        program that was compiled off the query path."""
        with spans.span("p2h.publish"):
            self._epoch += 1
            prev = self._snapshot
            snap = self._make_snapshot()
            if self.device is not None:
                for seg in snap.segments:
                    seg.place(self.device)
            snap.adopt_stacked_from(prev)
            if prepub is not None and prepub.get("stacked") is not None:
                snap.adopt_prebuilt_stacked(prepub["stacked"],
                                            prepub["sources"])
            self._snapshot = snap
        spans.count("publishes")

    # ------------------------------------------------------------------
    # persistence (through repro.checkpoint)
    # ------------------------------------------------------------------
    def save(self, directory: str) -> int:
        """Persist segments + delta atomically; returns the step saved.

        Joins any in-flight background compaction *under the writer
        lock* (a pin between a bare wait and the state walk would move
        delta rows into a sealed buffer the walk doesn't see), and folds
        any failure-leftover sealed buffers into a segment first -- the
        serialized state is always exactly segments + one active delta.
        """
        from repro.checkpoint import CheckpointManager

        with self._lock:
            while self._compacting:
                self._cond.wait(timeout=1.0)
            self._raise_compact_errors_locked()
            if self._sealed:  # leftovers of a failed background run
                self._compact_locked(self._plan_locked())
            state, meta = self._state_pytree_locked()
            if self._wal is not None:
                # the WAL frontier this checkpoint covers: everything at
                # seq <= wal_seq is folded into the serialized state, so
                # restore replays strictly past it and the covered prefix
                # can be truncated away
                meta["wal_offset"] = self._wal.tail_offset()
                meta["wal_seq"] = self._wal.last_seq
            step = self._epoch
            mgr = CheckpointManager(directory, keep=2)
            mgr.save(step, state, blocking=True, extra_meta=meta)
            if self._wal is not None:
                self._wal.truncate_prefix(meta["wal_offset"])
                # the frontier this checkpoint covers, for the sharded
                # front-end's top-level manifest
                self.last_saved_wal = (meta["wal_offset"],
                                       meta["wal_seq"])
        return step

    def _state_pytree_locked(self):
        assert not self._compacting and not self._sealed
        seg_arrays, seg_meta = [], []
        for seg in self._segments.values():
            arrays = {
                f.name: np.asarray(getattr(seg.tree, f.name))
                for f in dataclasses.fields(seg.tree)
                if not f.metadata.get("static", False)
            }
            arrays["gids"] = np.asarray(seg.gids)
            arrays["row_of_local"] = np.asarray(seg.row_of_local)
            seg_arrays.append(arrays)
            seg_meta.append(dict(
                uid=seg.uid, live=seg.live, dead=seg.dead,
                tree_static={
                    f.name: getattr(seg.tree, f.name)
                    for f in dataclasses.fields(seg.tree)
                    if f.metadata.get("static", False)
                },
            ))
        state = {
            "segments": seg_arrays,
            "delta": {"points": self._delta.points, "gids": self._delta.gids},
        }
        meta = {
            "format": _STATE_FORMAT,
            "version": _STATE_VERSION,
            "dim": self.dim,
            "n0": self.n0,
            "variant": self.variant,
            "seed": self.seed,
            "epoch": self._epoch,
            "last_delete_epoch": self._last_delete_epoch,
            "next_gid": self._next_gid,
            "next_uid": self._next_uid,
            "live_count": self._live_count,
            "max_norm": self._max_norm,
            "delta_length": self._delta.length,
            "policy": dataclasses.asdict(self.policy),
            "segments": seg_meta,
        }
        return state, meta

    @classmethod
    def load(cls, directory: str, *, step: int | None = None,
             background: bool = False, wal=None) -> "MutableP2HIndex":
        """Recover a mutable index saved by :meth:`save`.

        ``wal`` (optional :class:`repro.stream.wal.ShardWal`): replay the
        log tail past the checkpoint's recorded ``(wal_offset, wal_seq)``
        frontier, then attach the log for subsequent writes -- recovery
        to the last acknowledged write instead of the last checkpoint."""
        from repro.checkpoint import CheckpointManager
        from repro.core.balltree import FlatTree

        mgr = CheckpointManager(directory)
        if step is None:
            step = mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint under {directory}")
        leaves, manifest = mgr.restore_leaves(step)
        meta = manifest["extra"]
        if meta.get("format") != _STATE_FORMAT:
            raise ValueError(f"{directory}: not a {_STATE_FORMAT} checkpoint")
        if meta.get("version", 0) > _STATE_VERSION:
            raise ValueError(f"{directory}: state version "
                             f"{meta['version']} is newer than this reader")

        # rebuild the skeleton save() flattened, then unflatten into it
        import jax

        array_fields = sorted(
            [f.name for f in dataclasses.fields(FlatTree)
             if not f.metadata.get("static", False)] + ["gids",
                                                        "row_of_local"])
        skeleton = {
            "segments": [{k: 0 for k in array_fields}
                         for _ in meta["segments"]],
            "delta": {"points": 0, "gids": 0},
        }
        treedef = jax.tree_util.tree_structure(skeleton)
        state = jax.tree_util.tree_unflatten(treedef, leaves)

        policy = CompactionPolicy(**meta["policy"])
        self = cls(meta["dim"], n0=meta["n0"], variant=meta["variant"],
                   policy=policy, seed=meta["seed"], background=background)
        with self._lock:
            for arrays, smeta in zip(state["segments"], meta["segments"]):
                gids = np.asarray(arrays.pop("gids"), np.int32)
                row_of_local = np.asarray(arrays.pop("row_of_local"),
                                          np.int32)
                tree = FlatTree(**arrays, **smeta["tree_static"])
                seg = Segment(uid=smeta["uid"], tree=tree, gids=gids,
                              row_of_local=row_of_local,
                              live=smeta["live"], dead=smeta["dead"])
                self._segments[seg.uid] = seg
                pid = np.asarray(tree.point_ids)
                for local in pid[pid >= 0]:
                    self._locator[int(gids[local])] = (
                        "seg", seg.uid, int(local))
            self._delta.points[:] = state["delta"]["points"]
            self._delta.gids[:] = np.asarray(state["delta"]["gids"],
                                             np.int32)
            self._delta.length = meta["delta_length"]
            for row in range(self._delta.length):
                gid = int(self._delta.gids[row])
                if gid >= 0:
                    self._locator[gid] = ("delta", id(self._delta), row)
            self._next_gid = meta["next_gid"]
            self._next_uid = max(meta["next_uid"], self._next_uid)
            self._epoch = meta["epoch"]
            self._last_delete_epoch = meta["last_delete_epoch"]
            self._live_count = meta["live_count"]
            self._max_norm = meta["max_norm"]
            self._snapshot = self._make_snapshot()
        if wal is not None:
            self.wal_replay(wal, from_offset=meta.get("wal_offset", 0),
                            min_seq=meta.get("wal_seq", 0))
            self.attach_wal(wal)
        return self
