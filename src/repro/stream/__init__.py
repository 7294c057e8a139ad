"""Mutable LSM-style P2HNNS index: streaming inserts/deletes over the
Ball/BC-Tree with background compaction and atomic snapshot publishing.

The frozen ``P2HIndex`` serves a dataset built once; real traffic churns
while queries are in flight.  This package opens that read-write
workload class by exploiting the paper's central property -- Ball-Tree
construction is roughly linear and 1-3 orders of magnitude cheaper than
the hashing baselines' indexing -- which makes *rebuild* a viable update
primitive:

``DeltaBuffer`` / ``DeltaView`` (delta.py)
    The memtable.  Inserts append to a fixed-capacity host buffer,
    queried by an exact brute-force scan jitted on the static capacity;
    a ``DeltaView`` is its frozen form in a snapshot, and the scan reads
    a device copy the buffer keeps, sent only the rows appended since.

``Segment`` / ``Snapshot`` (snapshot.py)
    Sealed ``FlatTree`` segments with global-id tables; deletes mask a
    point's ``point_ids`` row to -1 (the backends' existing pad
    convention, so every bound stays valid).  A ``Snapshot`` is an
    epoch-numbered immutable view published atomically; queries fan out
    across delta + segments with any backend (dfs / sweep / beam /
    pallas), threading a running lambda cap and merging with the sharded
    exchange's ``merge_topk``.

``CompactionPolicy`` (compaction.py)
    When to fold the delta / tombstone-heavy segments into fresh trees
    (size, tombstone-ratio, and fan-out thresholds).

``MutableP2HIndex`` (mutable.py)
    The front-end: ``insert`` / ``delete`` / ``query`` / ``snapshot``,
    inline or background compaction, and ``save``/``load`` through
    ``repro.checkpoint`` so a serving process recovers without a write
    log.

``ShardedMutableP2HIndex`` (sharded.py)
    The scale-out front-end: every shard is a full mutable LSM index
    (own delta, segments, compaction policy, background compactor),
    inserts are routed by a pluggable hash-of-gid router, deletes
    forward to the owning shard, and queries pin a ``ShardedSnapshot``
    (per-shard snapshot vector + epoch vector) served through the
    two-round lambda exchange
    (``repro.core.distributed.two_round_exchange``).

``ShardWal`` / ``WalConfig`` (wal.py)
    Per-shard write-ahead log with group-commit fsync: an acknowledged
    write (``on_ack`` fires post-fsync) survives SIGKILL; recovery =
    newest checkpoint + idempotent tail replay.

``VersionedRouter`` (resharding.py)
    Versioned gid->shard map behind ``split_shard``/``merge_shards``:
    journaled batch migration under traffic, double-read during the
    transition so answers stay bit-exact vs the unsplit oracle.

Serving integration: ``P2HEngine(mutable_index)`` pins one snapshot per
micro-batch and epoch-tags its lambda cache -- warm caps recorded before
a delete are invalidated instead of silently unsound (a delete can grow
the true k-th distance above a cached cap).  Over a sharded mutable
index the cache stores epoch *vectors*, so one shard's delete only
invalidates caps stale in that component.
"""
from repro.stream.compaction import CompactionPlan, CompactionPolicy
from repro.stream.delta import DeltaBuffer
from repro.stream.mutable import MutableP2HIndex
from repro.stream.resharding import VersionedRouter
from repro.stream.sharded import HashRouter, ShardedMutableP2HIndex
from repro.stream.snapshot import DeltaView, Segment, ShardedSnapshot, Snapshot
from repro.stream.wal import ShardWal, WalConfig

__all__ = ["MutableP2HIndex", "ShardedMutableP2HIndex", "HashRouter",
           "Snapshot", "ShardedSnapshot", "Segment", "DeltaView",
           "DeltaBuffer", "CompactionPolicy", "CompactionPlan",
           "ShardWal", "WalConfig", "VersionedRouter"]
