"""The system under test as a single-device mutable index: a
``MutableP2HIndex`` served by a ``P2HEngine``, built and driven through
the program's public API only (``from_data`` / ``insert_batch`` /
``delete``, ``submit`` / ``flush`` / ``result``).

Configuration keys read: ``segment_rows``, ``delta_capacity``,
``tombstone_frac``, ``max_segments``, ``n0``, ``variant``,
``slot_size``.  Layout: bulk-load ``segment_rows`` points (one sealed
segment), ``insert_batch`` the rest (the delta seals whenever it fills),
then delete the generator's set-up deletes one gid per call.
"""
from __future__ import annotations

import time
import traceback

import numpy as np

import harness


class System:
    def __init__(self, config: dict, data, seed: int):
        harness.use_program()
        from repro.serve import P2HEngine
        from repro.serve.resilience import QueryRejected
        from repro.stream import CompactionPolicy, MutableP2HIndex

        self._rejected = QueryRejected
        rows = config["segment_rows"]
        policy = CompactionPolicy(delta_capacity=config["delta_capacity"],
                                  tombstone_frac=config["tombstone_frac"],
                                  max_segments=config["max_segments"])
        tree_seed = int(seed) % (2 ** 31 - 1)
        t0 = time.perf_counter()
        self.index = MutableP2HIndex.from_data(
            data.points[:rows], n0=config["n0"], variant=config["variant"],
            policy=policy, seed=tree_seed)
        t1 = time.perf_counter()
        gids = self.index.insert_batch(data.points[rows:])
        if not np.array_equal(gids, np.arange(rows, len(data.points))):
            raise RuntimeError("bulk insert did not number points by row")
        t2 = time.perf_counter()
        for g in data.dead:
            if not self.index.delete(int(g)):
                raise RuntimeError(f"set-up delete of gid {g} failed")
        #: host seconds of the index build's three steps
        self.build_s = {"from_data": t1 - t0, "insert_batch": t2 - t1,
                        "deletes": time.perf_counter() - t2}
        self.engine = P2HEngine(self.index, slot_size=config["slot_size"],
                                seed=tree_seed)
        self.reset_stats()

    def serve(self, queries: np.ndarray, k: int, submit: dict | None = None):
        """Answers ``[(dists (k,), gids (k,)) | None, ...]``; ``None`` is a
        query that was rejected, shed or raised.  ``submit``: keyword
        arguments of ``P2HEngine.submit`` the mix asks for."""
        tickets = []
        for q in queries:
            try:
                tickets.append(self.engine.submit(q, k, **(submit or {})))
            except self._rejected:
                tickets.append(None)
        try:
            self.engine.flush()
        except Exception:  # a failed batch fails its queries, not the run
            traceback.print_exc()
            return [None] * len(tickets)
        out = []
        for t in tickets:
            if t is None:
                out.append(None)
                continue
            shed = self.engine.result_meta(t).get("shed", False)
            d, i = self.engine.result(t)
            out.append(None if shed else (np.asarray(d), np.asarray(i)))
        return out

    def delete(self, gid: int) -> bool:
        return bool(self.index.delete(int(gid)))

    def insert_batch(self, points: np.ndarray) -> np.ndarray:
        return np.asarray(self.index.insert_batch(points), np.int64)

    def layout(self) -> dict:
        """Segments, live delta rows, live points, the segments' built
        leaf tiles (the tree's pad leaves and the stacked launch's
        common-grid pad left out) and the compactions since
        :meth:`reset_stats`."""
        snap = self.index.snapshot()
        tiles = sum(int(np.asarray(s.tree.node_leaf).max()) + 1
                    for s in snap.segments)
        return {"segments": len(snap.segments),
                "delta_live_rows": int(snap.delta_live),
                "live_points": int(snap.live_count),
                "tiles": tiles,
                "compactions": (len(self.index.compaction_log)
                                - self._compactions0)}

    def stats(self) -> dict:
        return self.engine.stats()

    def reset_stats(self) -> None:
        """Zero the engine's counters and the compaction count (the run
        calls it as the window opens)."""
        self.engine.reset_stats()
        self._compactions0 = len(self.index.compaction_log)

    def close(self) -> None:
        self.index.close()


def build(config: dict, data, seed: int, devices):
    del devices  # one device: JAX's default
    return System(config, data, seed)
