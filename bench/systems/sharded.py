"""The system under test as a placed sharded mutable index: a
``ShardedMutableP2HIndex`` with shard ``s`` resident on device ``s`` of
the devices the run is given, served by a ``P2HEngine`` through the
two-round exchange and the stacked mesh launch.  Built and driven
through the program's public API only (``from_data(devices=...)`` /
``insert_batch`` / ``delete``, ``submit`` / ``flush`` / ``result``).

Configuration keys read: ``shards``, ``segment_rows``,
``delta_capacity``, ``tombstone_frac``, ``max_segments``, ``n0``,
``variant``, ``slot_size``.  Layout: bulk-load ``shards x segment_rows``
points (the hash router gives each shard ``segment_rows`` of them, one
sealed segment each), ``insert_batch`` the rest (each shard's delta
seals whenever it fills), then delete the generator's set-up deletes one
gid per call.  On closing it prints each device's peak memory.
"""
from __future__ import annotations

import json
import time

import numpy as np

import harness

#: the single-device system, whose serving and stats this one shares
_single = harness.load_named("systems", "mutable")


class System(_single.System):
    def __init__(self, config: dict, data, seed: int, devices):
        harness.use_program()
        from repro.serve import P2HEngine
        from repro.serve.resilience import QueryRejected
        from repro.stream import CompactionPolicy
        from repro.stream.sharded import ShardedMutableP2HIndex

        self._rejected = QueryRejected
        shards = config["shards"]
        self.devices = list(devices[:shards])
        rows = shards * config["segment_rows"]
        policy = CompactionPolicy(delta_capacity=config["delta_capacity"],
                                  tombstone_frac=config["tombstone_frac"],
                                  max_segments=config["max_segments"])
        tree_seed = int(seed) % (2 ** 31 - 1)
        t0 = time.perf_counter()
        self.index = ShardedMutableP2HIndex.from_data(
            data.points[:rows], shards, n0=config["n0"],
            variant=config["variant"], policy=policy, seed=tree_seed,
            devices=self.devices)
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

    def layout(self) -> dict:
        """The single-device layout's totals over every shard, and per
        shard its segments and built leaf tiles."""
        out = super().layout()
        out["per_shard"] = [
            {"segments": len(snap.segments),
             "tiles": sum(int(np.asarray(s.tree.node_leaf).max()) + 1
                          for s in snap.segments)}
            for snap in self.index.snapshot().shards]
        return out

    def close(self) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices]
        print(f"peak_bytes_per_device {json.dumps(peaks)}", flush=True)
        super().close()


def build(config: dict, data, seed: int, devices):
    return System(config, data, seed, devices)
