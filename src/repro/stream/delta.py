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
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bounds import EXACT
from repro.runtime import spans

__all__ = ["DeltaBuffer", "delta_topk"]


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


def delta_topk(points: np.ndarray, gids: np.ndarray, queries, k: int, *,
               device=None):
    """Exact top-k over the delta rows; (dists (B,k), gids (B,k)).
    The host block is copied to the device whole, on every call: span
    ``p2h.delta.upload`` bounds the host call that starts the copy (on a
    TPU it returns before the transfer lands, and the device waits for
    it later), counter ``delta_upload_bytes`` its bytes.  ``device``
    places the block (and the scan) on that device; None keeps JAX's
    default placement."""
    with spans.span("p2h.delta.upload"):
        if device is None:
            points_dev, gids_dev = jnp.asarray(points), jnp.asarray(gids)
        else:
            points_dev, gids_dev = jax.device_put((points, gids), device)
    spans.count("delta_upload_bytes", points.nbytes + gids.nbytes)
    return _delta_topk(points_dev, gids_dev, jnp.asarray(queries), k)


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

    def frozen_view(self):
        """Immutable (points, gids, length) triple for a snapshot.

        ``points`` is shared (append-only rows beyond ``length`` don't
        affect the view); ``gids`` is copied so later tombstones don't
        leak into a pinned snapshot.
        """
        return self.points, self.gids.copy(), self.length
