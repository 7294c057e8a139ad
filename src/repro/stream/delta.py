"""Fixed-capacity delta buffer: the LSM "memtable" of the mutable index.

Freshly-inserted points land here before any tree exists over them.  The
buffer is a pair of preallocated host arrays -- ``points (C, d)`` (with
the appended 1-coordinate) and ``gids (C,)`` (global ids, -1 for
empty/deleted rows) -- written append-only: row ``i`` is assigned once,
at insert time, and never moves.  That append-only discipline is what
makes snapshot pinning cheap (see ``repro.stream.snapshot``): a snapshot
captures ``(points, gids.copy(), length)`` and later inserts only touch
rows ``>= length``, so the pinned view stays consistent without copying
the point block.

Queries over the delta are an exact brute-force scan: one ``(B, C)``
matmul with dead rows masked to +inf.  The scan is jitted on the static
capacity ``C``, so it compiles exactly once per (C, d, B, k) regardless
of fill level -- the serving engine's fixed-shape batching discipline
extended to the write path.

The scan reads a device copy of the block that the buffer's
:class:`DeltaMirror` keeps, one per device, for the buffer's lifetime.
The same append-only discipline makes it exact: a copy valid up to row
``n`` serves every view with ``length <= n`` (the view's gids mask the
rows past its length), and a longer view only needs rows ``[n, length)``
sent, which no write can change any more.  Writes never touch the
mirror; a scan brings it up to date.
"""
from __future__ import annotations

import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bounds import EXACT
from repro.runtime import spans

__all__ = ["DeltaBuffer", "DeltaMirror", "DeltaView", "delta_topk"]


@functools.partial(jax.jit, static_argnames=("k",))
def _delta_topk(points, gids, queries, k: int):
    d = jnp.abs(jnp.dot(queries, points.T, precision=EXACT))  # (B, C)
    d = jnp.where(gids[None, :] >= 0, d, jnp.inf)
    if k > d.shape[1]:  # capacity smaller than k: pad with invalid slots
        pad = k - d.shape[1]
        d = jnp.pad(d, ((0, 0), (0, pad)), constant_values=jnp.inf)
        gids = jnp.pad(gids, (0, pad), constant_values=-1)
    neg, arg = jax.lax.top_k(-d, k)
    bd = -neg
    bi = jnp.where(jnp.isfinite(bd), jnp.take(gids, arg), -1)
    return bd, bi


def delta_topk(view: "DeltaView", queries, k: int, *, device=None):
    """Exact top-k over one delta view's rows; (dists (B,k), gids (B,k)).
    Span ``p2h.delta.upload`` bounds the host call that brings the
    view's device arrays up to date (:meth:`DeltaView.on_device`; on a
    TPU it returns before a copy lands, and the device waits for it
    later), also when it sends nothing; counter ``delta_upload_bytes``
    the bytes it sends, and ``exchange_upload_bytes`` the same when
    ``device`` places the view (a placed shard).  ``device`` places the
    arrays (and the scan) on that device; None keeps JAX's default
    placement."""
    with spans.span("p2h.delta.upload"):
        points_dev, gids_dev, sent = view.on_device(device)
    if sent:
        spans.count("delta_upload_bytes", sent)
        if device is not None:
            spans.count("exchange_upload_bytes", sent)
    return _delta_topk(points_dev, gids_dev, jnp.asarray(queries), k)


@jax.jit
def _write_rows(block, rows, start):
    """``block`` with ``rows`` written from row ``start`` on: a new
    array, so a pinned snapshot or a launch in flight that still reads
    the old one keeps it."""
    return jax.lax.dynamic_update_slice(block, rows, (start, 0))


def sync_chunks(capacity: int) -> tuple:
    """Row counts a sync may send short of the whole block: powers of 4
    from 16, each under ``capacity``.  A fixed set per (C, d), whatever
    the fill, so every update shape is compiled when a device first gets
    the block; a gap past the largest sends the whole block."""
    out, rows = [], 16
    while rows < capacity:
        out.append(rows)
        rows *= 4
    return tuple(out)


def _warm_chunks(block, device) -> None:
    """Run the row update once for every chunk of ``block``'s shape on
    ``device``, so the compiles happen at a device's first sync of a
    buffer and no later sync compiles."""
    C, d = block.shape
    for rows in sync_chunks(C):
        _write_rows(block, jnp.zeros((rows, d), block.dtype, device=device),
                    np.int32(0))


class DeltaMirror:
    """Device copies of one buffer's ``(C, d)`` points block, one per
    device, each with the number of leading rows it holds as the host
    does.  The buffer's writers never take its lock: a scan brings a
    copy up to a view's length (:meth:`rows_upto`)."""

    def __init__(self, points: np.ndarray):
        self._points = points
        self._lock = threading.Lock()
        self._copies: dict = {}  # device -> (block, rows synced)

    def synced(self, device=None) -> int:
        """Rows the copy on ``device`` holds (0 = no copy yet)."""
        with self._lock:
            copy = self._copies.get(device)
        return 0 if copy is None else copy[1]

    def rows_upto(self, length: int, device=None):
        """``(block, bytes sent)``: a device block whose rows ``[0,
        length)`` equal the host's.  Sends nothing when the copy already
        holds them (counter ``delta_mirror_hits``), else host rows from
        the synced count on, in one chunk of :func:`sync_chunks`
        clamped to end inside the block, or the whole block on a
        device's first sync or past the largest chunk; counter
        ``delta_mirror_rows`` the rows newly held.  Rows a chunk carries
        past ``length`` do not count as held: a writer may still be
        filling them."""
        with self._lock:
            block, synced = self._copies.get(device, (None, 0))
            if block is not None and synced >= length:
                spans.count("delta_mirror_hits")
                return block, 0
            cap = self._points.shape[0]
            rows = next((r for r in sync_chunks(cap)
                         if r >= length - synced), None)
            if block is None or rows is None:
                first = block is None
                block = jax.device_put(self._points, device)
                sent = self._points.nbytes
                if first:
                    _warm_chunks(block, device)
            else:
                start = min(synced, cap - rows)
                part = jax.device_put(self._points[start:start + rows],
                                      device)
                block = _write_rows(block, part, np.int32(start))
                sent = part.nbytes
            if length > synced:
                spans.count("delta_mirror_rows", length - synced)
            self._copies[device] = (block, length)
            return block, sent


@dataclasses.dataclass(frozen=True)
class DeltaView:
    """Frozen view of one delta buffer (active or sealed-for-compaction).

    ``points`` is the buffer's shared append-only block -- rows past
    ``length`` were unassigned at freeze time and their ``gids`` entries
    are -1 in the frozen copy, so later appends are invisible here.
    ``mirror`` is the buffer's device copy of ``points``; the view's
    gids plane is placed once per device and kept with the view.
    """

    points: np.ndarray  # (C, d) shared
    gids: np.ndarray  # (C,) frozen copy, -1 = empty/deleted
    length: int
    mirror: DeltaMirror = dataclasses.field(compare=False, repr=False)
    _gids_dev: dict = dataclasses.field(default_factory=dict, init=False,
                                        compare=False, repr=False)

    @property
    def live(self) -> int:
        return int((self.gids >= 0).sum())

    def on_device(self, device=None):
        """``(points, gids, bytes sent)`` on ``device`` (None = JAX's
        default placement): the mirror brought up to ``length`` and the
        gids plane, placed on this view's first scan there."""
        points_dev, sent = self.mirror.rows_upto(self.length, device)
        gids_dev = self._gids_dev.get(device)
        if gids_dev is None:
            gids_dev = jax.device_put(self.gids, device)
            self._gids_dev[device] = gids_dev
            sent += self.gids.nbytes
        return points_dev, gids_dev, sent


class DeltaBuffer:
    """Append-only write buffer with in-place tombstoning.

    Not thread-safe by itself; :class:`~repro.stream.mutable.MutableP2HIndex`
    serializes all writers behind one lock.
    """

    def __init__(self, capacity: int, d: int):
        assert capacity >= 1
        self.capacity = int(capacity)
        self.d = int(d)
        self.points = np.zeros((self.capacity, self.d), np.float32)
        self.gids = np.full((self.capacity,), -1, np.int32)
        self.length = 0  # rows assigned (live + tombstoned)
        self.mirror = DeltaMirror(self.points)  # device copies of points

    # ------------------------------------------------------------------
    @property
    def full(self) -> bool:
        return self.length >= self.capacity

    @property
    def live(self) -> int:
        return int((self.gids[: self.length] >= 0).sum())

    def append(self, point: np.ndarray, gid: int) -> int:
        """Assign the next row; returns the row index.  Caller checks
        ``full`` first (a full delta must be sealed by compaction)."""
        assert not self.full, "delta buffer full: compact before appending"
        row = self.length
        self.points[row] = point
        self.gids[row] = gid
        self.length += 1
        return row

    def extend(self, points: np.ndarray, gids: np.ndarray) -> np.ndarray:
        """Assign the next ``len(points)`` rows (they must fit); returns
        their row indices."""
        m = len(points)
        assert self.length + m <= self.capacity, "delta buffer overflow"
        rows = np.arange(self.length, self.length + m)
        self.points[rows] = points
        self.gids[rows] = gids
        self.length += m
        return rows

    def tombstone(self, row: int) -> None:
        self.gids[row] = -1

    # ------------------------------------------------------------------
    def live_rows(self):
        """(points, gids) of the live rows -- compaction input."""
        mask = self.gids[: self.length] >= 0
        return self.points[: self.length][mask], self.gids[: self.length][mask]

    def frozen_view(self) -> DeltaView:
        """Immutable view of the buffer for a snapshot.

        ``points`` and the device ``mirror`` are shared (append-only rows
        beyond ``length`` don't affect the view); ``gids`` is copied so
        later tombstones don't leak into a pinned snapshot.
        """
        return DeltaView(self.points, self.gids.copy(), self.length,
                         self.mirror)
