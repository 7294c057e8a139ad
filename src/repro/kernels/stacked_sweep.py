"""Segment-parallel P2H sweep: N stacked leaf tile-sets, one launch.

The mutable/sharded serving path (``repro.stream``) re-serializes the
paper's pruning on the host: ``Snapshot.query`` walks a shard's segments
one by one, and round 2 of ``two_round_exchange`` walks shards one by
one, each threading the running lambda cap sequentially.  This module is
the device-side form of that sweep: the leaf arrays of ``N`` immutable
segments are stacked into one padded ``(N, L, n0, d)`` tile grid (a
:class:`StackedLeaves`, cached per snapshot because segments are sealed)
and swept by **one** Pallas program with grid ``(N, query-blocks,
tiles)`` -- or by its vmapped pure-jnp twin off-TPU -- under a single
*entry* cap per query instead of the sequentially-threaded one.

The one-launch form originally traded cap tightness for launch shape:
within a segment the running top-k still tightens tile by tile, but
segment ``i`` no longer sees segments ``< i``'s merged k-th, so the
per-tile threshold was looser and fewer *live* tiles were skipped than
on the sequential path.  The **two-pass** program closes that gap on
device -- the same move metric trees make by spending a cheap bounding
pass before the expensive scan: pass A ("probe") sweeps only the top
``probe_tiles`` preference-ordered tiles of every segment under the
entry cap, a device-side :func:`repro.core.search.merge_topk_planes`
reduces the per-segment probe k-ths to one tightened per-query cap
``lambda_probe = min(entry cap, merged probe k-th)``, and pass B sweeps
the remaining tiles of all segments under ``lambda_probe``, seeded with
pass A's per-segment top-k state so probed tiles are never rescanned.
The cross-segment finish (global merge + optional per-shard k-th
reductions) runs in the same jitted program, so one serving round is
one device program end to end -- no host-side per-segment merge.  Pad
tiles -- ragged segments are padded to a common quantized tile count,
empty / all-tombstone tiles are masked via the backends' ``point_ids ==
-1`` convention -- are force-skipped through a ``+inf`` node bound and
show up in the per-segment skip counters, so the counters account for
every tile the launch covers.

Exactness argument is unchanged from ``repro.core.search``: the entry
cap is a valid upper bound on the global k-th distance (the delta scan's
k-th, an engine cache cap, or the exchange's lambda0); the probe pass's
merged k-th is the distance of k real scanned points, hence also a valid
upper bound (round 1 of the two-round exchange makes the identical
argument); and per-segment pruning against ``min(cap, running k-th)``
only ever discards candidates that cannot enter that segment's -- hence
the merged -- top-k.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import logging
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as _P

from repro.core import bounds
from repro.parallel.sharding import mesh_signature
from repro.runtime import spans

__all__ = ["StackedLeaves", "stacked_sweep", "stacked_sweep_search",
           "stacked_sweep_query", "prepare_stacked_operands",
           "concat_cached", "PlacedStacks", "tile_density",
           "resolve_probe_tiles",
           "resolve_probe_dtype", "resolve_stacked_backend",
           "quantization_slack", "probe_bytes_per_tile",
           "warm_stacked", "stacked_compile_stats",
           "reset_stacked_compile_stats", "record_warm_failure",
           "STACKED_FANOUT_DEFAULT", "STACKED_DENSITY_DEFAULT",
           "STACKED_PROBE_TILES_DEFAULT",
           "STACKED_PROBE_TILES_ROUND2_DEFAULT", "PROBE_DTYPES"]

logger = logging.getLogger(__name__)

_LANE = 128
_NEG_FILL = jnp.inf

#: default segment fan-out at/above which exact sweeps auto-promote to the
#: stacked launch (``Snapshot.query`` / round 2 of the two-round exchange);
#: ``DispatchPolicy.stacked_min_fanout`` is the serving-layer knob.
STACKED_FANOUT_DEFAULT = 4

#: minimum live-tile fraction of the common grid for auto-promotion:
#: heavily ragged stacks (one big segment + many tiny ones) spend most of
#: the launch on pad tiles, which the branch-free jnp path can only mask,
#: not elide -- below this density the sequential walk stays cheaper
#: off-TPU.  ``DispatchPolicy.stacked_min_density`` is the serving knob.
STACKED_DENSITY_DEFAULT = 0.5

#: default probe-pass width of the two-pass sweep: pass A sweeps this
#: many preference-ordered tiles per (segment, query block) under the
#: entry cap, their merged k-th tightens the cap every remaining tile is
#: pruned against.  Small on purpose -- the probe's tiles would be
#: scanned anyway (pass B is seeded with pass A's state, nothing is
#: rescanned), so the only overhead is the second launch + the device
#: merge, while the payoff is the cross-segment lambda the one-launch
#: form gave up.  ``DispatchPolicy.probe_tiles`` is the serving-layer
#: knob, refit against the registered bench configs (bench_serve /
#: bench_stream_sharded report the crossover).
STACKED_PROBE_TILES_DEFAULT = 4

#: probe-pass width for round 2 of the two-round exchange
#: (``probe_route="round2"``): 0, i.e. single pass.  Round 2 already
#: enters with ``lambda0`` -- round 1's merged k-th over every shard --
#: which is exactly the cross-segment tightening the probe pass exists
#: to recreate, so the probe's extra launch buys nothing there (the
#: registered sharded config measures 0 probe-induced live skips and a
#: 0.94x p50 *regression*).  The snapshot route keeps
#: :data:`STACKED_PROBE_TILES_DEFAULT`: its entry cap is only the delta
#: scan's k-th (or nothing), so the probe still earns its launch.
STACKED_PROBE_TILES_ROUND2_DEFAULT = 0

#: probe-pass precisions the two-pass program accepts.  ``"f32"`` is the
#: historical all-f32 launch; ``"bf16"``/``"int8"`` score the *probe*
#: tiles from a lane-packed low-precision plane and widen the resulting
#: ``lambda_probe`` by a conservative per-tile quantization-slack term
#: (:func:`quantization_slack`), while the main pass rescans survivors
#: in f32 -- final answers are bit-exact vs the all-f32 launch because
#: quantization only moves *thresholds* (kept conservative), never the
#: verified distances the answer is built from.
PROBE_DTYPES = ("f32", "bf16", "int8")

#: unit roundoff of a bf16 significand (8 bits incl. the implicit one).
#: The bf16 probe's per-candidate error is bounded by
#: ``||q|| * ||x|| * u * (2 + O(u))`` (point + query each rounded once,
#: f32 accumulation); the slack uses ``4u`` -- a ~2x safety margin that
#: still costs < 2% of the bound's magnitude.
_BF16_EPS = 2.0 ** -8

#: multiplicative safety margin on the int8 slack term (covers the f32
#: dequantization arithmetic on top of the exact int32 accumulation).
_INT8_SAFETY = 1.05


def _segment_live_tiles(seg) -> int:
    """Tiles of ``seg`` holding >= 1 live point, judged on the *current*
    ids plane (memoized per segment object -- segments are immutable;
    tombstone rewrites produce a new object with a new plane)."""
    n = getattr(seg, "_live_tiles", None)
    if n is None:
        t = seg.tree
        pid = np.asarray(t.point_ids).reshape(t.num_leaves, t.n0)
        n = int((pid >= 0).any(axis=1).sum())
        try:
            object.__setattr__(seg, "_live_tiles", n)
        except AttributeError:
            pass  # slotted stand-ins: recompute per call
    return n


def tile_density(segments) -> float:
    """Raggedness/liveness signal: **live**-tile fraction of the
    rectangular grid ``segments`` stack into, judged on the *unquantized*
    max tile count (1.0 = perfectly even, fully live segments; the
    additional ``_TILE_QUANTUM`` rounding waste is bounded per segment
    and shrinks with grid size, so it is not held against the decision).

    Live tiles are counted from the segments' *current* ids planes, not
    their build-time geometry: tombstone republishes keep the stacked
    grid's geometry but dead tiles are force-skipped exactly like pad
    tiles, so a stack whose rows have been deleted out from under it is
    as ragged as one that was built ragged -- the dispatch signal must
    see that (stale-geometry density was the bug this fixes).

    The denominator uses each tree's *built* leaf count
    (:func:`repro.core.balltree.built_leaves`), not ``num_leaves``:
    ``pad_tree_leaves`` quantization pads are compile-shape waste of the
    same species as the tile-quantum rounding, already excused above --
    counting them would demote well-packed stacks below the floor just
    because their trees were rounded up for program-cache reuse."""
    from repro.core.balltree import built_leaves
    counts = [built_leaves(s.tree) for s in segments]
    if not counts:
        return 1.0
    live = sum(_segment_live_tiles(s) for s in segments)
    return live / (len(counts) * max(counts))


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


#: base tile-count quantum: the common grid's tile count is the max
#: segment's, rounded up to a multiple of :func:`_tile_quantum`.  Coarse
#: enough that snapshots which only differ by a few leaves share jit
#: traces (and cross-shard stacks usually concatenate without
#: re-padding), fine enough that pad tiles -- which the branch-free jnp
#: path cannot elide, only mask -- stay a small fraction of the launch.
_TILE_QUANTUM = 8


def _tile_quantum(max_leaves: int) -> int:
    """Size-scaled tile quantum: bigger grids take coarser rounding so
    successive compactions keep landing on the same padded tile count
    (the pad waste stays a bounded *fraction*, while the set of distinct
    jit shapes a churning index visits stays small)."""
    if max_leaves <= 128:
        return _TILE_QUANTUM
    if max_leaves <= 512:
        return 2 * _TILE_QUANTUM
    return 4 * _TILE_QUANTUM


def _bucket_segments(n: int) -> int:
    """Quantized segment count the launch is padded to: exact for small
    stacks (where a pad row is a large relative cost on the branch-free
    jnp path and compaction tends to *change* the count anyway), coarser
    as the stack grows, so republishes after compaction / shard churn
    land on an already-compiled grid signature instead of retracing.
    The ladder starts quantizing at 5 (not 9): a churning sharded index
    crosses 5..8 one compaction at a time, and ceil-to-2 there turns
    every *other* crossing into an already-compiled signature -- halving
    the background compile windows whose CPU contention is what the
    query tail actually sees once warmup keeps compiles off-path."""
    if n <= 4:
        return n
    if n <= 16:
        return _ceil_to(n, 2)
    if n <= 32:
        return _ceil_to(n, 4)
    if n <= 64:
        return _ceil_to(n, 8)
    return _ceil_to(n, 16)


#: the array fields of :class:`StackedLeaves`
_PLANES = ("pts", "ids", "rx", "xc", "xs", "leaf_centers", "leaf_radii",
           "leaf_cnorm", "valid", "n_leaves")


def _device_of(a):
    """The one device a committed array lives on; None for host arrays
    and arrays JAX may still move (uncommitted)."""
    if not isinstance(a, jax.Array) or not a.committed:
        return None
    devs = a.devices()
    return next(iter(devs)) if len(devs) == 1 else None


#: ``StackedLeaves._derived`` keys that depend only on tile *geometry*
#: (safe to share through ids-plane-only rewrites); everything else is
#: dropped by :meth:`StackedLeaves.with_updated_ids`.
_GEOMETRY_DERIVED = frozenset({"pts_lane"})


@dataclasses.dataclass(frozen=True)
class StackedLeaves:
    """Leaf tile arrays of N sealed segments, padded to one common grid.

    Built once per compaction (segments are immutable between rebuilds)
    and kept device-resident; tombstone-only republishes swap just the
    ``ids``/``valid`` planes (:meth:`with_updated_ids`) because deletes
    never touch tile geometry.  ``ids`` stores **global** ids directly
    (-1 = pad or tombstone), so kernel output needs no per-segment
    local-id translation.  The tile count ``L`` is the max segment's,
    quantized to ``_TILE_QUANTUM`` (jit-trace sharing / cross-shard
    concat alignment vs pad-tile waste -- see the constant's note).
    """

    pts: jnp.ndarray  # (N, L, n0, d) f32 -- unpadded columns (the
    #   kernel path lane-pads per call, exactly like ops.prepare_operands;
    #   the jnp path multiplies at true d -- lane zeros are free on the
    #   MXU but quadruple CPU matmul work)
    ids: jnp.ndarray  # (N, L, n0) i32 -- global ids, -1 = pad/tombstone
    rx: jnp.ndarray  # (N, L, n0) f32
    xc: jnp.ndarray  # (N, L, n0) f32
    xs: jnp.ndarray  # (N, L, n0) f32
    leaf_centers: jnp.ndarray  # (N, L, d) f32 -- unpadded d (phase-1 matmul)
    leaf_radii: jnp.ndarray  # (N, L) f32
    leaf_cnorm: jnp.ndarray  # (N, L, 1) f32
    valid: jnp.ndarray  # (N, L) bool -- tile holds >= 1 live point
    n_leaves: jnp.ndarray  # (N,) i32 -- real (unpadded) tile counts
    uids: tuple  # segment uids, in stack order (cache identity)
    n0: int
    d: int
    #: query-independent probe/sweep operands derived from the geometry
    #: (today: the lane-padded points plane the kernel path consumes),
    #: memoized per stack.  Tombstone republishes share it through
    #: :meth:`with_updated_ids` (``dataclasses.replace`` keeps the same
    #: dict -- geometry is unchanged, only ids planes move), so the pad
    #: copy is paid once per compaction, not once per query; the
    #: per-query probe/main visit orders are sliced from one shared
    #: preference argsort computed inside the launch.  Excluded from
    #: identity: a cache, not part of the stack's value.
    _derived: dict = dataclasses.field(default_factory=dict,
                                       compare=False, repr=False)

    @property
    def num_segments(self) -> int:
        return self.pts.shape[0]

    @property
    def num_tiles(self) -> int:
        return self.pts.shape[1]

    def padded_pts(self) -> jnp.ndarray:
        """The points plane zero-padded to a lane multiple (the Pallas
        kernel's tiling requirement), cached in :attr:`_derived` --
        inner products are unchanged, and the jnp reference path keeps
        :attr:`pts` at true ``d`` (lane zeros are free on the MXU but
        quadruple CPU matmul work)."""
        dp = _ceil_to(self.d, _LANE)
        if dp == self.pts.shape[-1]:
            return self.pts
        hit = self._derived.get("pts_lane")
        if hit is None:
            hit = jnp.pad(
                self.pts,
                ((0, 0), (0, 0), (0, 0), (0, dp - self.pts.shape[-1])))
            self._derived["pts_lane"] = hit
        return hit

    def quantized_pts(self, dtype: str, lane_pad: bool = True):
        """The probe pass's lane-packed low-precision points plane,
        built once per geometry and cached in :attr:`_derived` under a
        ``geom:``-prefixed key -- like :meth:`padded_pts`, tombstone
        republishes share it through :meth:`with_updated_ids` (deletes
        never touch tile geometry), so quantization is paid once per
        compaction, not per query.

        Returns ``(qpts, scale)``: ``qpts`` is ``(N, L, n0, dp)`` in
        ``bfloat16`` or ``int8``; ``scale`` is the int8 mode's per-tile
        dequantization factor ``(N, L, 1)`` f32 (``None`` for bf16).
        int8 scales are ``max |x| / 127`` over the tile with zero-scale
        tiles (all-pad grid rows: ``pts == 0``) forced to 1.0 -- the
        quantized values there are exact zeros either way, and a 0/0 at
        build time (or a 1/0 at dequantization) would leak NaN/inf into
        tile scores that only *pruning* keeps out of the answer."""
        assert dtype in ("bf16", "int8"), dtype
        key = f"geom:quant:{dtype}:{'lane' if lane_pad else 'raw'}"
        hit = self._derived.get(key)
        if hit is None:
            base = self.padded_pts() if lane_pad else self.pts
            if dtype == "bf16":
                hit = (base.astype(jnp.bfloat16), None)
            else:
                # max |x| over the tile's true columns (lane pads are
                # zero, so using `base` would give the same scale)
                maxabs = jnp.max(jnp.abs(self.pts), axis=(2, 3))  # (N, L)
                scale = jnp.where(maxabs > 0.0, maxabs / 127.0, 1.0)
                q = jnp.clip(jnp.round(base / scale[:, :, None, None]),
                             -127.0, 127.0).astype(jnp.int8)
                hit = (q, scale[:, :, None])
            self._derived[key] = hit
        return hit

    # ------------------------------------------------------------------
    @property
    def nbytes(self) -> int:
        """Bytes of the stack's own planes (derived copies left out)."""
        return sum(int(getattr(self, f).nbytes) for f in _PLANES)

    @classmethod
    def from_segments(cls, segments, *, device=None,
                      tiles: int = 0) -> "StackedLeaves":
        """Stack ``segments`` (objects with ``.uid``, ``.tree`` --
        a :class:`repro.core.balltree.FlatTree` -- and ``.gids``, the
        local-id -> global-id table) into one padded tile grid, placed
        on ``device`` (None: JAX's default placement).  ``tiles`` pads
        the tile axis up to at least that count (stacks that must share
        one grid, as the per-device blocks of a placed sharded index)."""
        segments = tuple(segments)
        assert segments, "cannot stack zero segments"
        t0 = segments[0].tree
        n0, d = t0.n0, t0.d
        max_leaves = max(t.tree.num_leaves for t in segments)
        L = max(_ceil_to(max_leaves, _tile_quantum(max_leaves)), int(tiles))
        N = len(segments)
        pts = np.zeros((N, L, n0, d), np.float32)
        ids = np.full((N, L, n0), -1, np.int32)
        rx = np.full((N, L, n0), -1.0, np.float32)
        xc = np.zeros((N, L, n0), np.float32)
        xs = np.zeros((N, L, n0), np.float32)
        centers = np.zeros((N, L, d), np.float32)
        radii = np.zeros((N, L), np.float32)
        cnorm = np.zeros((N, L, 1), np.float32)
        n_leaves = np.zeros((N,), np.int32)
        for s, seg in enumerate(segments):
            t = seg.tree
            Ls = t.num_leaves
            assert t.n0 == n0 and t.d == d, "segments disagree on tiling"
            pts[s, :Ls] = np.asarray(t.points).reshape(Ls, n0, d)
            ids[s, :Ls] = _global_ids(t, seg.gids)
            rx[s, :Ls] = np.asarray(t.rx).reshape(Ls, n0)
            xc[s, :Ls] = np.asarray(t.xcos).reshape(Ls, n0)
            xs[s, :Ls] = np.asarray(t.xsin).reshape(Ls, n0)
            centers[s, :Ls] = np.asarray(t.leaf_centers)
            radii[s, :Ls] = np.asarray(t.leaf_radii)
            cnorm[s, :Ls, 0] = np.asarray(t.leaf_cnorm)
            n_leaves[s] = Ls
        valid = (ids >= 0).any(axis=2)
        planes = dict(pts=pts, ids=ids, rx=rx, xc=xc, xs=xs,
                      leaf_centers=centers, leaf_radii=radii,
                      leaf_cnorm=cnorm, valid=valid, n_leaves=n_leaves)
        planes = (jax.tree.map(jnp.asarray, planes) if device is None
                  else jax.device_put(planes, device))
        return cls(**planes, uids=tuple(seg.uid for seg in segments),
                   n0=n0, d=d)

    def with_updated_ids(self, changed: dict) -> "StackedLeaves":
        """New stack with the ids/valid planes of ``changed`` segments
        (``{stack index: segment}``) rewritten -- the tombstone-only
        republish path: geometry arrays are shared, not copied, and so
        are the geometry-keyed ``_derived`` entries (ids-derived ones
        are dropped: the planes just moved).  Pure host numpy on
        purpose: the ids plane is tiny, and jnp scatter ops here would
        jit-compile per stack shape -- a ~200 ms spike the first
        post-delete query on every fresh shape would eat."""
        ids = np.array(self.ids)  # host copy, (S, T, n0) i32 -- small
        uids = list(self.uids)
        for s, seg in changed.items():
            plane = np.full((self.num_tiles, self.n0), -1, np.int32)
            plane[:seg.tree.num_leaves] = _global_ids(seg.tree, seg.gids)
            ids[s] = plane
            uids[s] = seg.uid
        keep = {key: v for key, v in self._derived.items()
                if key in _GEOMETRY_DERIVED or key.startswith("geom:")}
        live = (ids, (ids >= 0).any(axis=2))
        dev = _device_of(self.ids)
        ids_dev, valid_dev = (jax.tree.map(jnp.asarray, live) if dev is None
                              else jax.device_put(live, dev))
        return dataclasses.replace(self, ids=ids_dev, valid=valid_dev,
                                   uids=tuple(uids), _derived=keep)

    @staticmethod
    def concat(stacks) -> "StackedLeaves":
        """Concatenate stacks along the segment axis (the cross-shard
        one-launch round 2), re-padding smaller tile grids to the max.
        Power-of-two tile counts make the pad a no-op most of the time."""
        stacks = list(stacks)
        assert stacks
        if len(stacks) == 1:
            return stacks[0]
        n0, d = stacks[0].n0, stacks[0].d
        assert all(s.n0 == n0 and s.d == d for s in stacks), \
            "stacks disagree on tiling"
        L = max(s.num_tiles for s in stacks)
        # stacks placed on different devices meet on the first one's
        dev = _device_of(stacks[0].ids)

        def padL(a, fill):
            if dev is not None and _device_of(a) != dev:
                a = jax.device_put(a, dev)
            pad = L - a.shape[1]
            if pad == 0:
                return a
            w = [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
            return jnp.pad(a, w, constant_values=fill)

        return StackedLeaves(
            pts=jnp.concatenate([padL(s.pts, 0.0) for s in stacks]),
            ids=jnp.concatenate([padL(s.ids, -1) for s in stacks]),
            rx=jnp.concatenate([padL(s.rx, -1.0) for s in stacks]),
            xc=jnp.concatenate([padL(s.xc, 0.0) for s in stacks]),
            xs=jnp.concatenate([padL(s.xs, 0.0) for s in stacks]),
            leaf_centers=jnp.concatenate(
                [padL(s.leaf_centers, 0.0) for s in stacks]),
            leaf_radii=jnp.concatenate(
                [padL(s.leaf_radii, 0.0) for s in stacks]),
            leaf_cnorm=jnp.concatenate(
                [padL(s.leaf_cnorm, 0.0) for s in stacks]),
            valid=jnp.concatenate([padL(s.valid, False) for s in stacks]),
            n_leaves=jnp.concatenate(
                [s.n_leaves if dev is None else jax.device_put(s.n_leaves, dev)
                 for s in stacks]),
            uids=tuple(u for s in stacks for u in s.uids),
            n0=n0, d=d)


#: identity-keyed LRU over cross-shard concatenations: repeat queries
#: against the same epoch-vector pin present the same per-shard stack
#: objects, so the combined grid is reused instead of re-copied per
#: query.  Entries hold the source stacks by **weakref** with an
#: eviction callback: the moment any source stack leaves the live
#: snapshot set (compaction republish retires it), its entry -- and the
#: combined grid's device arrays, which on a serving mesh are placed
#: per-device -- is dropped instead of pinning dead segment geometry
#: until 8 newer compositions push it out.  The dead weakrefs also make
#: the id()-tuple keys unambiguous: a recycled id can only collide after
#: the old referent died, and its death already removed the entry.
#: Mutations take the lock (an RLock: the GC may run an eviction
#: callback *inside* a cache operation on the same thread): concurrent
#: serving threads (and background compactors republishing underneath
#: them) hit this on every stacked round 2.
_CONCAT_CACHE: "collections.OrderedDict[tuple, tuple]" = (
    collections.OrderedDict())
_CONCAT_CACHE_SIZE = 8
_CONCAT_LOCK = threading.RLock()


def concat_cached(stacks) -> StackedLeaves:
    """:meth:`StackedLeaves.concat` behind a small identity-keyed LRU
    (the per-query entry point of the exchange's stacked round 2).
    Entries self-evict when a source stack is garbage-collected."""
    stacks = tuple(stacks)
    if len(stacks) == 1:
        # concat would return the source itself; caching that would hold
        # a strong ref to it under its own weakref key -- a self-pin
        return stacks[0]
    key = tuple(id(s) for s in stacks)
    with _CONCAT_LOCK:
        hit = _CONCAT_CACHE.pop(key, None)
        if hit is not None:
            live = tuple(r() for r in hit[0])
            if all(a is b for a, b in zip(live, stacks)):
                _CONCAT_CACHE[key] = hit  # re-insert: most recently used
                return hit[1]
    combined = StackedLeaves.concat(stacks)  # build outside the lock

    def _evict(_ref, _key=key):
        with _CONCAT_LOCK:
            _CONCAT_CACHE.pop(_key, None)

    refs = tuple(weakref.ref(s, _evict) for s in stacks)
    with _CONCAT_LOCK:
        _CONCAT_CACHE[key] = (refs, combined)
        while len(_CONCAT_CACHE) > _CONCAT_CACHE_SIZE:
            _CONCAT_CACHE.popitem(last=False)
    return combined


def _global_ids(tree, gids) -> np.ndarray:
    """(L, n0) global-id tiles: ``point_ids`` translated through the
    segment's gid table (-1 pad/tombstone rows stay -1)."""
    pid = np.asarray(tree.point_ids).reshape(tree.num_leaves, tree.n0)
    gids = np.asarray(gids, np.int32)
    safe = np.clip(pid, 0, max(0, len(gids) - 1))
    return np.where(pid >= 0,
                    gids[safe] if len(gids) else -1,
                    -1).astype(np.int32)


def quantization_slack(probe_dtype: str, *, d: int, leaf_cnorm,
                       leaf_radii, tile_scale=None):
    """Per-tile slack coefficients ``(sa, sb)`` (each ``(N, L, 1)`` f32)
    such that for every point ``x`` of tile ``t`` and query ``q``::

        |score_quant(q, x) - |<q, x>||  <=  ||q|| * sa[t] + sq * sb[t]

    where ``sq`` is the query's int8 quantization scale (0 for bf16).
    Adding this to the quantized probe scores keeps every widened value
    >= the true distance, so the probe's merged k-th stays a valid upper
    bound on the global k-th -- the same conservative-slack argument the
    lambda cache makes for f32 noise, with the error sourced from
    quantization instead.

    Derivation sketch (``||x|| <= ||c_t|| + r_t`` for leaf-ball tiles):

    * bf16: point and query each round once (unit roundoff ``u=2^-8``),
      accumulation is f32, so the error is ``<= ||q||*||x||*u*(2+O(u))``;
      ``sa = (||c_t|| + r_t) * 4u`` keeps a 2x margin, ``sb = 0``.
    * int8: per-component dequantization error is ``s/2``; with
      ``s_t`` the tile scale and ``sq`` the query scale the dot error is
      ``<= (sqrt(d)/2) * (s_t*||q|| + sq*||x||) + (d/4)*sq*s_t`` (int32
      accumulation is exact), so ``sa = safety*(sqrt(d)/2)*s_t`` and
      ``sb = safety*((sqrt(d)/2)*(||c_t||+r_t) + (d/4)*s_t)``.

    ``d`` must be the **true** point dimensionality -- lane-pad columns
    are exact zeros on both sides and contribute no error."""
    cr = (jnp.asarray(leaf_cnorm)[..., 0]
          + jnp.asarray(leaf_radii))[..., None]  # (N, L, 1)
    if probe_dtype == "bf16":
        sa = cr * (4.0 * _BF16_EPS)
        return sa, jnp.zeros_like(sa)
    assert probe_dtype == "int8", probe_dtype
    s_t = jnp.asarray(tile_scale)  # (N, L, 1)
    half_rd = 0.5 * float(np.sqrt(d))
    sa = _INT8_SAFETY * half_rd * s_t
    sb = _INT8_SAFETY * (half_rd * cr + 0.25 * float(d) * s_t)
    return sa, sb


def probe_bytes_per_tile(probe_dtype: str, n0: int, d: int) -> int:
    """Bytes the probe pass streams per (n0, d) tile of points: the
    roofline the quantized probe attacks.  Low-precision modes add the
    per-tile scalar operands they read (int8: dequant scale + both slack
    coefficients; bf16: the slack coefficient)."""
    if probe_dtype == "f32":
        return n0 * d * 4
    if probe_dtype == "bf16":
        return n0 * d * 2 + 4
    assert probe_dtype == "int8", probe_dtype
    return n0 * d + 12


# ======================================================================
# phase 1: stacked bounds + per-(segment, query-block) visit order
# ======================================================================


def prepare_stacked_operands(stk: StackedLeaves, queries, *, frac=1.0,
                             bq=8, lambda_cap=None, lane_pad=False):
    """Stacked twin of :func:`repro.kernels.ops.prepare_operands`.

    One einsum gives ``<q, leaf.c>`` for every (segment, leaf); invalid
    (pad / all-tombstone) tiles get a ``+inf`` node bound -- always
    skipped, always counted -- and sort to the end of each visit list.
    ``lane_pad`` zero-pads point/query columns to a lane multiple (the
    Pallas kernel's tiling requirement; inner products are unchanged) --
    the jnp reference path keeps the true ``d``.
    """
    N, L = stk.num_segments, stk.num_tiles
    d = stk.d
    dp = _ceil_to(d, _LANE) if lane_pad else d
    B0 = queries.shape[0]
    Bp = _ceil_to(B0, bq)
    q = jnp.asarray(queries, jnp.float32)
    if Bp != B0:  # replicate the last query (rows discarded on return)
        q = jnp.concatenate(
            [q, jnp.broadcast_to(q[-1:], (Bp - B0, d))], axis=0)
    qn = jnp.sqrt(jnp.sum(q * q, axis=1, keepdims=True))  # (Bp, 1)
    cap = (jnp.full((Bp, 1), jnp.inf, jnp.float32) if lambda_cap is None
           else jnp.pad(jnp.asarray(lambda_cap, jnp.float32).reshape(B0, 1),
                        ((0, Bp - B0), (0, 0)), constant_values=jnp.inf))

    ipc = jnp.einsum("bd,nld->nbl", q, stk.leaf_centers,
                     precision=bounds.EXACT)  # (N, Bp, L)
    lb = bounds.node_ball_bound(ipc, qn[None, :, :],
                                stk.leaf_radii[:, None, :])
    lb = jnp.where(stk.valid[:, None, :], lb, jnp.inf)
    pref = jnp.min(jnp.abs(ipc).reshape(N, Bp // bq, bq, L), axis=2)
    pref = jnp.where(stk.valid[:, None, :], pref, jnp.inf)
    visit = jnp.argsort(pref, axis=2).astype(jnp.int32)  # (N, nqb, L)
    n_visit = max(1, min(L, int(round(frac * L))))
    visit = visit[:, :, :n_visit]

    # the stack may hand us an already-lane-padded points plane (the
    # per-stack ``padded_pts`` cache) -- pad only what still needs it
    pts = (stk.pts if stk.pts.shape[-1] == dp else
           jnp.pad(stk.pts,
                   ((0, 0), (0, 0), (0, 0), (0, dp - stk.pts.shape[-1]))))
    ops = dict(
        pts_tiles=pts,
        ids_tiles=stk.ids,
        rx_tiles=stk.rx,
        xc_tiles=stk.xc,
        xs_tiles=stk.xs,
        leaf_cnorm=stk.leaf_cnorm,
        queries=q if dp == d else jnp.pad(q, ((0, 0), (0, dp - d))),
        qnorm=qn,
        cap=cap,
        leaf_ip=ipc,
        leaf_lb=lb,
        visit=visit,
    )
    return ops, B0


# ======================================================================
# the stacked Pallas kernel
# ======================================================================

#: words of the 1 MiB scalar memory the flattened visit table may take in
#: one launch.  A larger batch is swept by several launches over whole
#: query blocks: a block's sweep never spans two launches, so the split
#: changes no result.  Half the memory, leaving room for the kernel's own
#: scalars.
_SMEM_VISIT_WORDS = 128 * 1024

#: rows of the per-step scalar plane after the ``2 * bq`` bound rows: the
#: tile's ``||c||``, int8 dequant scale and the two quantization-slack
#: coefficients, padded to a sublane multiple.
_TILE_SCALAR_ROWS = 8

#: per-point rows of a tile, packed as int32: rx, xcos and xsin bit-cast
#: from f32, then the ids.  Integer on the way in because small ids
#: bit-cast to f32 are subnormals, which a TPU flushes to zero.
_POINT_ROWS = 4


def _first_index(mask, iota, fill):
    """Per-row lane index of the first True of ``mask`` (``fill`` if none):
    ``argmin``/``argmax`` as a masked min, which Mosaic lowers."""
    return jnp.min(jnp.where(mask, iota, fill), axis=1, keepdims=True)


def _insert_trips(cand, kth, k):
    """Steps of a tile's top-k insertion loop that can change the running
    top-k: ``min(k, most candidates of one row strictly below its running
    k-th)``.  Each insertion takes a candidate below the row's k-th as it
    stood before the loop (the k-th only falls), and a row's first step
    that inserts nothing leaves it unchanged for every later step, so the
    steps past this count are no-ops in every row."""
    below = jnp.sum(jnp.where(cand < kth, 1, 0), axis=1, keepdims=True)
    return jnp.minimum(jnp.max(below), k)


def stacked_sweep_kernel(
    # scalar prefetch
    visit_ref,  # SMEM (N * nqb * n_visit,) i32 -- flattened visit order
    # inputs (blocked; leading grid dims squeezed)
    q_ref,      # (bq, dp) -- query block (f32; bf16/int8 when the probe
    #              pass scores quantized tiles -- probe_dtype static)
    qn_ref,     # (bq, 1)  f32 -- ||q||
    sq_ref,     # (bq, 1)  f32 -- per-query int8 quantization scale
    #              (dequant + slack operand; zeros for f32/bf16)
    cap_ref,    # (bq, 1)  f32 -- the single entry cap (delta k-th /
    #                             cache cap / exchange lambda0)
    gs_ref,     # (bq, k)  f32 -- global top-k *value* seed (+inf cold)
    sd_ref,     # (bq, k)  f32 -- this segment's seed top-k (+inf cold)
    si_ref,     # (bq, k)  i32
    step_ref,   # (R, 128) f32 -- per-step scalars of 128 visit steps:
    #              rows [0, bq) <q, leaf.c>, [bq, 2bq) node ball bound
    #              (+inf = pad tile), then ||c||, int8 tile scale,
    #              slack_a, slack_b of the visited tile
    pts_ref,    # (n0, dp) -- the tile's points (f32, or the lane-packed
    #              bf16/int8 plane on the quantized probe)
    rows_ref,   # (4, n0)  i32 -- rx, xcos, xsin (f32 bits), ids
    # outputs
    out_d_ref,  # (bq, k)  f32 -- this segment's top-k (unsorted)
    out_i_ref,  # (bq, k)  i32
    out_s_ref,  # (1, 128) i32 -- lane 0 skipped tiles, lane 1 scanned
    #              tiles, lanes 2+ insertion steps run
    # scratch
    topd,       # VMEM (bq, k) f32 -- running per-segment top-k
    topi,       # VMEM (bq, k) i32
    glob,       # VMEM (nqb, bq, k) f32 -- per-block *global* top-k
    #             values, threaded across the (sequential) segment axis
    counts,     # SMEM (3,) i32 -- the three counts of out_s_ref
    *,
    k: int,
    bq: int,
    use_ball: bool,
    use_cone: bool,
    probe_dtype: str = "f32",
):
    """One grid step = one leaf tile of one segment for one query block.

    The running top-k scratch re-initializes at each segment's first tile
    from the *seed* planes -- +inf/-1 on a cold start, pass A's
    per-segment state on the two-pass main sweep (so probed tiles are
    never rescanned).

    The launch also carries an **in-launch global top-k**: per query
    block, the ``glob`` scratch accumulates the k smallest verified
    distances over every segment processed so far (folded in at each
    segment's last tile; the TPU grid is sequential, so segment ``s``
    sees segments ``< s``'s merged state -- the device-side form of the
    sequential path's cap threading).  The per-tile threshold is
    ``min(entry cap, global running k-th, segment running k-th)``, and
    pass B additionally seeds ``glob`` with pass A's merged probe planes
    -- caps at least as tight as the host-threaded walk's, one launch.

    Every operand block is Mosaic-legal: its last two dims are whole
    array dims or (8, 128) multiples.  Per-step scalars therefore arrive
    as one lane of a visit-ordered ``(R, 128)`` block (re-fetched once
    per 128 steps) instead of ``(bq, 1)`` column blocks, and the tile's
    per-point rows as one ``(4, n0)`` block.
    """
    del visit_ref  # consumed by the index maps
    s = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    n_tiles = pl.num_programs(2)

    @pl.when((s == 0) & (j == 0))
    def _init_global():  # once per query block: seed the global state
        glob[pl.ds(i, 1)] = gs_ref[...][None]

    @pl.when(j == 0)
    def _init():  # fresh segment (or query block): resume from the seed
        topd[...] = sd_ref[...]
        topi[...] = si_ref[...]
        counts[0] = 0
        counts[1] = 0
        counts[2] = 0

    blk = step_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    col = jnp.max(jnp.where(lane == j % _LANE, blk, -jnp.inf), axis=1,
                  keepdims=True)  # (R, 1): this step's scalars
    ip = col[:bq]                 # (bq, 1)
    lb = col[bq:2 * bq]
    cn, ts, sa, sb = (col[2 * bq + r:2 * bq + r + 1] for r in range(4))

    kth = jnp.max(topd[...], axis=1, keepdims=True)  # (bq, 1)
    gmax = jnp.max(glob[pl.ds(i, 1)][0], axis=1, keepdims=True)  # (bq, 1)
    lam = jnp.minimum(jnp.minimum(kth, gmax), cap_ref[...])  # (bq, 1)
    active = lb < lam  # Theorem 2 prune (pad tiles: lb=+inf)
    any_active = jnp.max(jnp.where(active, 1.0, 0.0)) > 0.0

    @pl.when(jnp.logical_not(any_active))
    def _count_skip():
        counts[0] = counts[0] + 1

    @pl.when(any_active)
    def _scan_tile():
        rows = rows_ref[...]
        rx, xc, xs = (jax.lax.bitcast_convert_type(rows[r:r + 1],
                                                   jnp.float32)
                      for r in range(3))  # (1, n0) each
        ids = rows[3:4]
        keep = (ids >= 0) & active  # (bq, n0)
        qn = qn_ref[...]
        if use_ball:  # Corollary 1 (rx sorted descending within the tile)
            pb = jnp.maximum(jnp.abs(ip) - qn * rx, 0.0)
            keep &= pb < lam
        if use_cone:  # Theorem 3
            qcos = ip / jnp.maximum(cn, 1e-12)
            qsin = jnp.sqrt(jnp.maximum(qn * qn - qcos * qcos, 0.0))
            cb = bounds._cone_cases(qcos, qsin, xc, xs)
            keep &= cb < lam
        # scoring matmul on the MXU: (bq, dp) x (dp, n0).  Quantized
        # probe modes dequantize + widen here, *inside* the pl.when
        # gate, so pad / all-tombstone tiles (lb = +inf -> never active)
        # are force-skipped before any dequantization arithmetic runs --
        # a degenerate scale can never leak NaN/inf into live scores.
        raw = jax.lax.dot_general(
            q_ref[...], pts_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=bounds.EXACT if probe_dtype == "f32" else None,
            preferred_element_type=(jnp.int32 if probe_dtype == "int8"
                                    else jnp.float32))
        if probe_dtype == "f32":
            cand = jnp.where(keep, jnp.abs(raw), _NEG_FILL)  # (bq, n0)
        else:
            if probe_dtype == "int8":  # exact int32 accumulation, then
                #    dequantize by (query scale * tile scale)
                raw = raw.astype(jnp.float32) * (sq_ref[...] * ts)
            # widen by the conservative quantization slack: every
            # candidate value stays >= its true distance, so the merged
            # probe k-th stays a valid global cap (quantization_slack)
            err = qn * sa + sq_ref[...] * sb  # (bq, 1)
            cand = jnp.where(keep, jnp.abs(raw) + err, _NEG_FILL)

        # the insertion loop runs only the steps that can change a row's
        # top-k (_insert_trips): after pass A's seed most tiles hold no
        # candidate below the running k-th, and the loop is skipped
        n_iter = _insert_trips(cand, kth, k)
        counts[1] = counts[1] + 1
        counts[2] = counts[2] + n_iter

        @pl.when(n_iter > 0)
        def _insert():
            iota_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
            iota_n = jax.lax.broadcasted_iota(jnp.int32, cand.shape, 1)

            def insert(_, carry):
                td, ti, cd = carry
                m = jnp.min(cd, axis=1, keepdims=True)
                am = _first_index(cd == m, iota_n, cd.shape[1])
                wv = jnp.max(td, axis=1, keepdims=True)
                wa = _first_index(td == wv, iota_k, k)
                better = m < wv
                oh_w = (iota_k == wa) & better
                oh_c = iota_n == am
                win_id = jnp.max(jnp.where(oh_c, ids, -1), axis=1,
                                 keepdims=True)
                td = jnp.where(oh_w, m, td)
                ti = jnp.where(oh_w, win_id, ti)
                cd = jnp.where(oh_c & better, _NEG_FILL, cd)
                return td, ti, cd

            td, ti, _ = jax.lax.fori_loop(
                0, n_iter, insert, (topd[...], topi[...], cand))
            topd[...] = td
            topi[...] = ti

    @pl.when(j == n_tiles - 1)
    def _write_out():
        out_d_ref[...] = topd[...]
        out_i_ref[...] = topi[...]
        lane_s = jax.lax.broadcasted_iota(jnp.int32, out_s_ref.shape, 1)
        out_s_ref[...] = jnp.where(
            lane_s == 0, counts[0],
            jnp.where(lane_s == 1, counts[1], counts[2]))
        # fold this segment's top-k values into the per-block global
        # running state (k-smallest of the 2k values; same insertion
        # pattern as the tile scan, values only -- ids stay per-segment)
        iota_k = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)

        def fold(_, carry):
            g, cd = carry
            m = jnp.min(cd, axis=1, keepdims=True)
            am = _first_index(cd == m, iota_k, k)
            wv = jnp.max(g, axis=1, keepdims=True)
            wa = _first_index(g == wv, iota_k, k)
            better = m < wv
            g = jnp.where((iota_k == wa) & better, m, g)
            cd = jnp.where((iota_k == am) & better, _NEG_FILL, cd)
            return g, cd

        g, _ = jax.lax.fori_loop(
            0, k, fold, (glob[pl.ds(i, 1)][0], topd[...]))
        glob[pl.ds(i, 1)] = g[None]


def resolve_stacked_backend(use_kernel: bool | None,
                            interpret: bool | None):
    """The stacked launch's backend-dispatch rule, shared by
    :func:`stacked_sweep` and the jit front-end: the Mosaic kernel on
    TPU; on GPU the vmapped jnp twin jitted by XLA:GPU (the GPU lowering
    -- ``pltpu`` grid specs have no Triton lowering, so an explicit
    ``use_kernel=True`` falls back to the interpreter, a parity tool);
    the interpret-mode twin on CPU.  ``repro.launch.platform`` is the
    process-level platform selector this rule reads through
    ``jax.default_backend()``."""
    backend = jax.default_backend()
    if use_kernel is None:
        use_kernel = backend == "tpu"
    if interpret is None:
        interpret = backend != "tpu"
    if use_kernel and backend == "gpu":
        interpret = True  # TPU-shaped Pallas grid: no Triton lowering
    return bool(use_kernel), bool(interpret)


def _step_scalars(leaf_ip, leaf_lb, leaf_cnorm, tile_scale, slack_a,
                  slack_b, visit, bq):
    """The kernel's per-step scalars in visit order, lane-packed:
    ``(N, nqb, ceil(n_visit / 128), R, 128)`` f32 with ``R = 2 * bq +
    8``.  Step ``j`` of block ``i`` in segment ``s`` is lane ``j % 128`` of
    block ``[s, i, j // 128]``: rows ``[0, bq)`` hold ``<q, leaf.c>``,
    ``[bq, 2 bq)`` the node ball bound, then the tile's ``||c||``, int8
    scale and slack coefficients (rows left over are zero)."""
    N, B, L = leaf_ip.shape
    _, nqb, nv = visit.shape

    def per_query(a):  # (N, B, L) -> (N, nqb, bq, nv)
        return jnp.take_along_axis(a.reshape(N, nqb, bq, L),
                                   visit[:, :, None, :], axis=3)

    seg = jnp.arange(N)[:, None, None]

    def per_tile(a):  # (N, L, 1) -> (N, nqb, 1, nv)
        return a[..., 0][seg, visit][:, :, None, :]

    planes = jnp.concatenate(
        [per_query(leaf_ip), per_query(leaf_lb), per_tile(leaf_cnorm),
         per_tile(tile_scale), per_tile(slack_a), per_tile(slack_b),
         jnp.zeros((N, nqb, _TILE_SCALAR_ROWS - 4, nv), jnp.float32)],
        axis=2)
    nvp = _ceil_to(nv, _LANE)
    planes = jnp.pad(planes, ((0, 0), (0, 0), (0, 0), (0, nvp - nv)))
    R = planes.shape[2]
    return planes.reshape(N, nqb, R, nvp // _LANE, _LANE).transpose(
        0, 1, 3, 2, 4)


def _launch_blocks(nqb: int, per_block: int) -> int:
    """Query blocks per launch: as many as keep the flattened visit table
    within :data:`_SMEM_VISIT_WORDS`, balanced over the launches."""
    if per_block > _SMEM_VISIT_WORDS:
        raise ValueError(
            f"one query block's visit table ({per_block} words) exceeds "
            f"the {_SMEM_VISIT_WORDS}-word scalar-memory budget")
    g = max(1, min(nqb, _SMEM_VISIT_WORDS // per_block))
    n_launch = -(-nqb // g)
    return -(-nqb // n_launch)


def stacked_sweep(
    pts_tiles,   # (N, L, n0, dp) -- f32, or bf16/int8 quantized probe
    ids_tiles,   # (N, L, n0) i32
    rx_tiles,    # (N, L, n0) f32
    xc_tiles,    # (N, L, n0) f32
    xs_tiles,    # (N, L, n0) f32
    leaf_cnorm,  # (N, L, 1) f32
    queries,     # (B, dp), B % bq == 0 -- dtype matches pts_tiles
    qnorm,       # (B, 1) f32
    cap,         # (B, 1) f32 -- the single entry cap
    leaf_ip,     # (N, B, L) f32
    leaf_lb,     # (N, B, L) f32 (+inf = pad tile)
    visit,       # (N, B // bq, n_visit) i32
    *,
    k: int,
    bq: int = 8,
    use_ball: bool = True,
    use_cone: bool = True,
    interpret: bool | None = None,
    seed_d=None,  # (N, B, k) f32 -- pass A's per-segment state (None=cold)
    seed_i=None,  # (N, B, k) i32
    global_seed=None,  # (B, k) f32 -- in-launch global top-k value seed
    probe_dtype: str = "f32",
    sq=None,          # (B, 1) f32 -- per-query int8 scale (zeros f32/bf16)
    tile_scale=None,  # (N, L, 1) f32 -- per-tile int8 dequant scale
    slack_a=None,     # (N, L, 1) f32 -- quantization slack (* ||q||)
    slack_b=None,     # (N, L, 1) f32 -- quantization slack (* sq)
):
    """pallas_call wrapper: grid ``(N segments, query blocks, tiles)``.

    Returns unsorted ``(dists (N, B, k), ids (N, B, k),
    skips (N, B//bq, 1), steps (N, B//bq, 2))``; ``skips`` counts
    block-granular tile skips per segment, **including** the
    force-skipped pad tiles of ragged / empty / all-tombstone segments
    (they are part of the launch); ``steps`` counts the steps that
    scanned a tile and the top-k insertion steps those scans ran (at
    most ``k`` each).
    ``seed_d``/``seed_i`` seed each segment's running top-k (the probe
    handoff of the two-pass sweep); ``global_seed`` seeds the in-launch
    global top-k values every segment's threshold folds in (pass B gets
    pass A's merged planes); ``None`` starts cold.

    ``probe_dtype != "f32"`` runs the **quantized probe** form:
    ``pts_tiles``/``queries`` carry the low-precision planes, tile
    scores are dequantized and widened by the conservative
    :func:`quantization_slack` term in-kernel, and the returned ``dists``
    are *widened upper bounds* (valid pruning state, not exact answers
    -- the caller's f32 main pass rescans).

    The operands are re-laid out for Mosaic here (per-step scalars in
    visit order, per-point rows packed per tile) and a batch whose visit
    table outgrows scalar memory runs as several launches.
    """
    _, interpret = resolve_stacked_backend(True, interpret)
    B, dp = queries.shape
    N, L, n0, _ = pts_tiles.shape
    _, nqb, nv = visit.shape
    assert B == nqb * bq, (B, nqb, bq)
    assert visit.shape[0] == N, (visit.shape, N)
    if seed_d is None:
        seed_d = jnp.full((N, B, k), _NEG_FILL, jnp.float32)
        seed_i = jnp.full((N, B, k), -1, jnp.int32)
    if global_seed is None:
        global_seed = jnp.full((B, k), _NEG_FILL, jnp.float32)
    if sq is None:
        sq = jnp.zeros((B, 1), jnp.float32)
    if tile_scale is None:
        tile_scale = jnp.ones((N, L, 1), jnp.float32)
    if slack_a is None:
        slack_a = jnp.zeros((N, L, 1), jnp.float32)
    if slack_b is None:
        slack_b = jnp.zeros((N, L, 1), jnp.float32)

    step = _step_scalars(leaf_ip, leaf_lb, leaf_cnorm, tile_scale, slack_a,
                         slack_b, visit, bq)
    R = step.shape[3]
    rows = jnp.stack(
        [jax.lax.bitcast_convert_type(a, jnp.int32)
         for a in (rx_tiles, xc_tiles, xs_tiles)] + [ids_tiles],
        axis=2)  # (N, L, 4, n0) i32
    kernel = functools.partial(
        stacked_sweep_kernel, k=k, bq=bq, use_ball=use_ball,
        use_cone=use_cone, probe_dtype=probe_dtype)

    def launch(c0, g):  # query blocks [c0, c0 + g)
        b0, b1 = c0 * bq, (c0 + g) * bq

        def qmap(s, i, j, v):      # query-block operands
            return (i, 0)

        def omap(s, i, j, v):      # per-(segment, query-block) planes
            return (s, i, 0)

        def smap(s, i, j, v):      # 128 visit steps per block
            return (s, i, j // _LANE, 0, 0)

        def tmap(s, i, j, v):      # the j-th preferred tile
            return (s, v[(s * g + i) * nv + j], 0, 0)

        out_d, out_i, out_s = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(N, g, nv),
                in_specs=[
                    pl.BlockSpec((bq, dp), qmap),            # queries
                    pl.BlockSpec((bq, 1), qmap),             # qnorm
                    pl.BlockSpec((bq, 1), qmap),             # sq
                    pl.BlockSpec((bq, 1), qmap),             # cap
                    pl.BlockSpec((bq, k), qmap),             # global seed
                    pl.BlockSpec((None, bq, k), omap),       # seed dists
                    pl.BlockSpec((None, bq, k), omap),       # seed ids
                    pl.BlockSpec((None, None, None, R, _LANE), smap),
                    pl.BlockSpec((None, None, n0, dp), tmap),  # points
                    pl.BlockSpec((None, None, _POINT_ROWS, n0), tmap),
                ],
                out_specs=[
                    pl.BlockSpec((None, bq, k), omap),
                    pl.BlockSpec((None, bq, k), omap),
                    pl.BlockSpec((None, None, 1, _LANE),
                                 lambda s, i, j, v: (s, i, 0, 0)),
                ],
                scratch_shapes=[
                    pltpu.VMEM((bq, k), jnp.float32),
                    pltpu.VMEM((bq, k), jnp.int32),
                    pltpu.VMEM((g, bq, k), jnp.float32),  # global top-k
                    pltpu.SMEM((3,), jnp.int32),
                ],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((N, g * bq, k), jnp.float32),
                jax.ShapeDtypeStruct((N, g * bq, k), jnp.int32),
                jax.ShapeDtypeStruct((N, g, 1, _LANE), jnp.int32),
            ],
            interpret=interpret,
        )(visit[:, c0:c0 + g].reshape(-1), queries[b0:b1], qnorm[b0:b1],
          sq[b0:b1], cap[b0:b1], global_seed[b0:b1], seed_d[:, b0:b1],
          seed_i[:, b0:b1], step[:, c0:c0 + g], pts_tiles, rows)
        return out_d, out_i, out_s[:, :, :, 0], out_s[:, :, 0, 1:3]

    g = _launch_blocks(nqb, N * nv)
    outs = [launch(c0, min(g, nqb - c0)) for c0 in range(0, nqb, g)]
    return tuple(jnp.concatenate(parts, axis=1) for parts in zip(*outs))


# ======================================================================
# jit'd front-end (kernel on TPU, vmapped jnp reference elsewhere)
# ======================================================================


def _quant_probe_operands(probe_dtype, ops, qpts, qscale, radii, cnorm,
                          d):
    """The probe pass's quantized operand overrides: the low-precision
    points/queries planes plus the dequant + slack scalars
    (:func:`quantization_slack`).  Returns ``(qops, quant_kw)`` --
    ``run(**dict(qops, ...), **quant_kw)`` is the quantized pass A."""
    if probe_dtype == "bf16":
        qq = ops["queries"].astype(jnp.bfloat16)
        sqv = jnp.zeros_like(ops["qnorm"])
        ts = None
    else:  # int8: per-query scale, zero-guarded like the tile scales
        qf = ops["queries"]
        mq = jnp.max(jnp.abs(qf), axis=1, keepdims=True)
        sqv = jnp.where(mq > 0.0, mq / 127.0, 1.0)
        qq = jnp.clip(jnp.round(qf / sqv), -127.0, 127.0).astype(jnp.int8)
        ts = qscale
    sa, sb = quantization_slack(probe_dtype, d=d, leaf_cnorm=cnorm,
                                leaf_radii=radii, tile_scale=qscale)
    qops = dict(ops, pts_tiles=qpts, queries=qq)
    return qops, dict(probe_dtype=probe_dtype, sq=sqv, tile_scale=ts,
                      slack_a=sa, slack_b=sb)


def _sweep_runner(use_kernel, interpret, **kw):
    """The per-pass sweep both programs run: ``run(**operands)`` returns
    :func:`stacked_sweep`'s ``(dists, ids, skips, steps)``; the jnp twin
    counts no steps (zeros)."""
    from repro.kernels import ref

    if use_kernel:
        return functools.partial(stacked_sweep, interpret=interpret, **kw)

    def run(**ops):
        d, i, skips = ref.stacked_sweep_ref(**ops, **kw)
        return d, i, skips, jnp.zeros(skips.shape[:2] + (2,), jnp.int32)

    return run


def _widened_probe_cap(cap, pd, k):
    """``lambda_probe`` of the quantized probe: the merged widened k-th,
    nudged *strictly* above itself.  The quantized pass's candidates are
    widened bounds, not exact distances, so they cannot seed the f32
    main pass -- it rescans the full visit list cold, and a candidate
    whose true distance exactly equals the cap must survive the strict
    ``<`` prunes (the f32 two-pass form tolerates equality because the
    probed candidates ride its seeds; here the margin restores that).
    Entry-cap ties need no margin: the caller that supplies a cap also
    feeds its supporting candidates through the final merge."""
    kth = pd[:, k - 1:k]
    return jnp.minimum(cap, kth * (1.0 + 2.0 ** -16) + 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("n0", "d", "k", "frac", "bq", "use_ball", "use_cone",
                     "use_kernel", "interpret", "probe_tiles",
                     "probe_dtype", "num_shards", "has_extra",
                     "sort_planes"),
)
def _run_stacked(arrays, queries, lambda_cap, extra_d, extra_i, seg_shard,
                 n_true, *, n0, d, k, frac, bq, use_ball, use_cone,
                 use_kernel, interpret, probe_tiles, probe_dtype,
                 num_shards, has_extra, sort_planes):
    """One device program end to end: probe pass + main pass + in-launch
    global merge.

    Pass A sweeps the first ``probe_tiles`` preference-ordered tiles of
    every segment (under the entry cap + the in-launch global top-k the
    launch threads across its sequential segment axis); the per-segment
    probe planes are reduced on device by
    :func:`repro.core.search.merge_topk_planes` into one merged value
    set -- valid pruning state because every entry is the distance of a
    real scanned point, so its k-th upper-bounds the global k-th (the
    round-1 argument of the two-round exchange).  Pass B sweeps the
    *remaining* tiles with that merged state as its global-top-k seed
    (``lambda_probe`` = the seed's k-th, tightening further as segments
    fold in) and pass A's per-segment top-k as its scratch seed, so
    probed tiles are never rescanned and the union of both passes covers
    each visit list exactly once.  The cross-source finish --
    :func:`repro.core.search.merge_topk_planes` over the ``(N, B, k)``
    planes plus any ``extra`` candidate list (the delta scan's top-k) --
    and the per-shard k-th reductions run inside the same jitted
    program: callers get the final global top-k with no host merge.

    Everything that churns under a mutable index is **dynamic**, so the
    trace is shared across republishes: the segment axis is padded to a
    :func:`_bucket_segments` bucket (dead pad rows: ``valid=False``,
    ``n_leaves=0`` -> +inf node bounds, force-skipped), ``n_true`` (a
    traced scalar) masks those rows out of the counters, and shard
    membership arrives as the ``seg_shard`` vector (segment -> shard
    index, -1 = pad) against a *static* shard count -- a shard-local
    compaction changes values, not the trace.
    """
    from repro.core import search

    arrays = dict(arrays)
    qpts = arrays.pop("qpts", None)
    qscale = arrays.pop("qscale", None)
    stk = StackedLeaves(**arrays, uids=(), n0=n0, d=d)
    ops, B0 = prepare_stacked_operands(
        stk, queries, frac=frac, bq=bq, lambda_cap=lambda_cap,
        lane_pad=use_kernel)
    run = _sweep_runner(use_kernel, interpret, k=k, bq=bq,
                        use_ball=use_ball, use_cone=use_cone)
    visit = ops["visit"]
    N, nqb, n_visit = visit.shape
    true_row = jnp.arange(N) < n_true  # bucket-pad rows: swept (force-
    #   skipped via +inf bounds) but never *counted* -- the counters must
    #   match what an unpadded launch would report
    p = max(0, min(probe_tiles, n_visit))
    if has_extra:
        Bp = ops["cap"].shape[0]
        extra_d = jnp.pad(jnp.asarray(extra_d, jnp.float32),
                          ((0, Bp - B0), (0, 0)),
                          constant_values=jnp.inf)
        extra_i = jnp.pad(jnp.asarray(extra_i, jnp.int32),
                          ((0, Bp - B0), (0, 0)), constant_values=-1)
        # the extra candidates (the delta scan's merged top-k: real,
        # deduplicated points disjoint from every segment) seed the
        # in-launch global top-k, so per-segment thresholds track the
        # *union* k-th over delta + completed segments -- exactly the
        # sequential walk's merged running cap, not just min-of-parts
        gseed = (extra_d if extra_d.shape[1] == k
                 else -jax.lax.top_k(-extra_d, k)[0])
    else:
        extra_d = extra_i = gseed = None
    if probe_dtype != "f32" and p > 0:
        # quantized pass A: score the probe tiles from the low-precision
        # plane, every candidate *widened* by the per-tile slack before
        # top-k insertion (see quantization_slack) -- the merged k-th is
        # then >= the k-th true distance over the scanned set, i.e.
        # still a valid global cap.  Widened values are bounds, not
        # distances, so they cannot seed pass B: the f32 main pass
        # rescans the FULL visit list cold-seeded, which also keeps the
        # pass-B skip counters covering the whole visit list exactly
        # once (the counter invariant the f32 two-pass gets from its
        # disjoint-passes union).
        qops, quant_kw = _quant_probe_operands(
            probe_dtype, ops, qpts, qscale, arrays["leaf_radii"],
            arrays["leaf_cnorm"], d)
        da, ia, skips_a, steps_a = run(
            **dict(qops, visit=visit[:, :, :p]), global_seed=gseed,
            **quant_kw)
        pd, _ = search.merge_topk_planes(da, ia, k)
        cap_b = _widened_probe_cap(ops["cap"], pd, k)
        bd, bi, skips, steps = run(**dict(ops, cap=cap_b),
                                   global_seed=gseed)
        steps = steps + steps_a
        probe_skips = jnp.sum(
            jnp.where(true_row[:, None, None], skips_a, 0))
    elif 0 < p < n_visit:
        # pass A: probe the top-p preference tiles of every segment
        da, ia, skips_a, steps_a = run(**dict(ops, visit=visit[:, :, :p]),
                                       global_seed=gseed)
        pd, _ = search.merge_topk_planes(da, ia, k)
        cap_b = jnp.minimum(ops["cap"], pd[:, k - 1:k])  # lambda_probe
        # pass B: remaining tiles under lambda_probe, per-segment
        # scratch seeded by pass A.  The global top-k re-threads from
        # the extra seed only (NOT the merged probe planes: each
        # segment's pass A values are already inside its seeded scratch,
        # and the value-only global fold has no id dedup, so seeding
        # them would double-count probe candidates and break the cap's
        # validity) -- lambda_probe carries the cross-segment probe
        # bound instead, and the global state tightens past it as
        # completed segments fold in.
        bd, bi, skips_b, steps_b = run(**dict(ops, visit=visit[:, :, p:],
                                              cap=cap_b),
                                       seed_d=da, seed_i=ia,
                                       global_seed=gseed)
        skips = skips_a + skips_b
        steps = steps_a + steps_b
        probe_skips = jnp.sum(
            jnp.where(true_row[:, None, None], skips_a, 0))
    else:  # p == 0 (single pass) or p == n_visit (probe IS the sweep)
        bd, bi, skips, steps = run(**ops, global_seed=gseed)
        probe_skips = (jnp.sum(jnp.where(true_row[:, None, None],
                                         skips, 0))
                       if p else jnp.int32(0))
    return _finish_stacked(bd, bi, skips, steps, probe_skips, extra_d,
                           extra_i, seg_shard, true_row, stk.n_leaves, k=k,
                           B0=B0, num_shards=num_shards,
                           sort_planes=sort_planes, nqb=nqb,
                           n_visit=n_visit)


def _finish_stacked(bd, bi, skips, steps, probe_skips, extra_d, extra_i,
                    seg_shard, true_row, n_leaves, *, k, B0, num_shards,
                    sort_planes, nqb, n_visit):
    """Cross-source finish shared by the single-launch
    (:func:`_run_stacked`) and mesh (:func:`_run_stacked_mesh`)
    programs, on full bucket-padded planes: the in-launch global merge
    of the per-segment planes (+ the caller's extra candidates, e.g. the
    delta scan) into one (B, k) answer, the per-shard k-th reductions,
    the optional plane sort, and the counter conventions.  The launch's
    last output is ``(3,)`` i32: the probe pass's skipped tiles, then
    both passes' scanned steps and top-k insertion steps over the true
    segments (:func:`stacked_sweep`'s ``steps``).  ``true_row`` (Np,)
    marks the rows that are real segments (the rest are bucket pad)."""
    from repro.core import search

    n_true = jnp.sum(true_row.astype(jnp.int32))
    fd, fi = search.merge_topk_planes(bd, bi, k, extra_d=extra_d,
                                      extra_i=extra_i)
    fd, fi = fd[:B0], fi[:B0]
    shard_kth = None
    if num_shards:
        rows = []
        for s in range(num_shards):  # static shard count; membership is
            # the dynamic seg_shard vector, so a shard-local compaction
            # (or bucket re-pad) changes values, never the trace
            m = (seg_shard == s)[:, None, None]
            skd, _ = search.merge_topk_planes(
                jnp.where(m, bd, jnp.inf),
                jnp.where(m, bi, -1), k)
            rows.append(skd[:B0, k - 1])
        shard_kth = jnp.stack(rows)  # (S, B)
    if sort_planes:  # the planes API sorts; the fused query path's
        #              merge consumes them unsorted -- skip the work
        order = jnp.argsort(bd, axis=2)  # per-segment top-k is unsorted
        bd = jnp.take_along_axis(bd, order, axis=2)[:, :B0]
        bi = jnp.take_along_axis(bi, order, axis=2)[:, :B0]
    else:
        bd, bi = bd[:, :B0], bi[:, :B0]
    # counters follow repro.core.search conventions where derivable;
    # tile visits/skips are block-granular (the pl.when elision unit) and
    # include the force-skipped pad tiles of the common grid.  The two
    # passes cover each (segment, block) visit list exactly once, so the
    # totals are pass-count independent.
    seg_skips = jnp.sum(skips, axis=(1, 2)).astype(jnp.int32)  # (N,)
    total_skip = jnp.sum(jnp.where(true_row, seg_skips, 0))
    counters = (jnp.zeros((8,), jnp.int32)
                .at[3].set(jnp.int32(B0)
                           * jnp.sum(n_leaves).astype(jnp.int32))
                .at[2].set(n_true.astype(jnp.int32)
                           * jnp.int32(nqb * n_visit) - total_skip)
                .at[7].set(total_skip))
    steps = jnp.sum(jnp.where(true_row[:, None, None], steps, 0),
                    axis=(0, 1)).astype(jnp.int32)
    launch_counts = jnp.concatenate(
        [jnp.reshape(probe_skips, (1,)).astype(jnp.int32), steps])
    return bd, bi, fd, fi, counters, seg_skips, shard_kth, launch_counts


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "mesh_axis", "n0", "d", "k", "frac", "bq",
                     "use_ball", "use_cone", "use_kernel", "interpret",
                     "probe_tiles", "probe_dtype", "num_shards",
                     "has_extra", "sort_planes"),
)
def _run_stacked_mesh(arrays, queries, lambda_cap, extra_d, extra_i,
                      seg_shard, true_row, *, mesh, mesh_axis, n0, d, k,
                      frac, bq, use_ball, use_cone, use_kernel, interpret,
                      probe_tiles, probe_dtype, num_shards, has_extra,
                      sort_planes):
    """The stacked program mapped onto a device mesh: the (bucket- and
    device-count-padded) segment axis of ``arrays`` is sharded across
    ``mesh_axis`` via ``shard_map``, every device sweeps its own
    contiguous block of segments over the full (replicated) query block,
    and the cross-device reductions the single-launch program did with a
    sequential in-launch fold become collectives:

      * the two-pass probe handoff gathers every device's pass-A planes
        (``all_gather``, tiled -- contiguous blocks restore stack order)
        and merges them replicated, so ``lambda_probe`` carries every
        *device's* probe bound, not just the local one;
      * the per-segment result planes are gathered the same way, and the
        shared :func:`_finish_stacked` (global merge, per-shard k-ths,
        counters) runs replicated on the full planes.

    Within a device the local segment scan still threads its running
    global top-k sequentially (that is the pruning the single launch
    gets from its sequential grid); across devices the tightening
    travels through the probe merge instead.  Exactness is unchanged --
    thresholds only *prune*, and every threshold is still a valid upper
    bound on the global k-th -- only tile-skip diagnostics may differ
    from the single-device launch.  Single-pass dispatches (``p == 0``,
    e.g. the exchange's round 2 under ``lambda0``) skip the probe
    collective entirely: one gather at the end is the whole exchange.
    ``true_row`` (Np,) marks the real segments: a placed index's
    per-device blocks each end in their own pad rows.
    """
    from repro.core import search

    B0 = queries.shape[0]
    Bp = _ceil_to(B0, bq)
    nqb = Bp // bq
    L = arrays["pts"].shape[1]
    n_visit = max(1, min(L, int(round(frac * L))))
    p = max(0, min(probe_tiles, n_visit))
    cap0 = (jnp.full((B0,), jnp.inf, jnp.float32) if lambda_cap is None
            else jnp.asarray(lambda_cap, jnp.float32).reshape(-1))
    if has_extra:
        extra_d = jnp.pad(jnp.asarray(extra_d, jnp.float32),
                          ((0, Bp - B0), (0, 0)),
                          constant_values=jnp.inf)
        extra_i = jnp.pad(jnp.asarray(extra_i, jnp.int32),
                          ((0, Bp - B0), (0, 0)), constant_values=-1)
        gseed = (extra_d if extra_d.shape[1] == k
                 else -jax.lax.top_k(-extra_d, k)[0])
    else:
        extra_d = extra_i = None
        gseed = jnp.full((Bp, k), _NEG_FILL, jnp.float32)

    def local(arrs, q, cap, gs):
        arrs = dict(arrs)
        qpts_l = arrs.pop("qpts", None)
        qscale_l = arrs.pop("qscale", None)
        stk_l = StackedLeaves(**arrs, uids=(), n0=n0, d=d)
        ops, _ = prepare_stacked_operands(
            stk_l, q, frac=frac, bq=bq, lambda_cap=cap,
            lane_pad=use_kernel)
        run = _sweep_runner(use_kernel, interpret, k=k, bq=bq,
                            use_ball=use_ball, use_cone=use_cone)
        visit = ops["visit"]
        gather = functools.partial(jax.lax.all_gather,
                                   axis_name=mesh_axis, axis=0,
                                   tiled=True)
        if probe_dtype != "f32" and p > 0:
            # quantized probe as a collective: every device's *widened*
            # pass-A planes meet in the gather-merge, so lambda_probe
            # stays a valid global cap for the same reason as the
            # single-launch form; pass B rescans the full local visit
            # list in f32, cold-seeded (widened values never seed).
            qops, quant_kw = _quant_probe_operands(
                probe_dtype, ops, qpts_l, qscale_l, arrs["leaf_radii"],
                arrs["leaf_cnorm"], d)
            da, ia, sk_a, st_a = run(**dict(qops, visit=visit[:, :, :p]),
                                     global_seed=gs, **quant_kw)
            pd, _ = search.merge_topk_planes(gather(da), gather(ia), k)
            cap_b = _widened_probe_cap(ops["cap"], pd, k)
            bd_l, bi_l, sk_l, st_l = run(**dict(ops, cap=cap_b),
                                         global_seed=gs)
            st_l = st_l + st_a
            psk_l = sk_a
        elif 0 < p < n_visit:
            da, ia, sk_a, st_a = run(**dict(ops, visit=visit[:, :, :p]),
                                     global_seed=gs)
            # the lambda exchange as a collective: every device's probe
            # planes meet here; the merged k-th is the same valid bound
            # the single launch threads sequentially
            pd, _ = search.merge_topk_planes(gather(da), gather(ia), k)
            cap_b = jnp.minimum(ops["cap"], pd[:, k - 1:k])
            bd_l, bi_l, sk_b, st_b = run(
                **dict(ops, visit=visit[:, :, p:], cap=cap_b),
                seed_d=da, seed_i=ia, global_seed=gs)
            sk_l = sk_a + sk_b
            st_l = st_a + st_b
            psk_l = sk_a
        else:  # p == 0 (single pass) or p == n_visit (probe IS the sweep)
            bd_l, bi_l, sk_l, st_l = run(**ops, global_seed=gs)
            psk_l = sk_l if p else jnp.zeros_like(sk_l)
        return (gather(bd_l), gather(bi_l), gather(sk_l), gather(st_l),
                gather(psk_l))

    in_spec = jax.tree.map(lambda _: _P(mesh_axis), arrays)
    # replication checking off: the gathered outputs are replicated by
    # construction, which the static checker cannot prove
    bd, bi, skips, steps, probe_sk = jax.shard_map(
        local, mesh=mesh,
        in_specs=(in_spec, _P(), _P(), _P()),
        out_specs=(_P(), _P(), _P(), _P(), _P()), check_vma=False,
    )(arrays, queries, cap0, gseed)
    probe_skips = (jnp.sum(jnp.where(true_row[:, None, None],
                                     probe_sk, 0))
                   if p else jnp.int32(0))
    return _finish_stacked(bd, bi, skips, steps, probe_skips, extra_d,
                           extra_i, seg_shard, true_row, arrays["n_leaves"],
                           k=k, B0=B0, num_shards=num_shards,
                           sort_planes=sort_planes, nqb=nqb,
                           n_visit=n_visit)


def _n_visit(stk: StackedLeaves, frac: float) -> int:
    """The visit-list length ``prepare_stacked_operands`` will produce."""
    L = stk.num_tiles
    return max(1, min(L, int(round(frac * L))))


def resolve_probe_tiles(probe_tiles, n_visit: int,
                        route: str = "snapshot") -> int:
    """Clamp the probe knob to ``[0, n_visit]``.  ``None`` resolves to
    the *route's* default -- ``STACKED_PROBE_TILES_DEFAULT`` on the
    snapshot route, ``STACKED_PROBE_TILES_ROUND2_DEFAULT`` (0: the
    probe's cross-segment tightening is redundant under the exchange's
    ``lambda0``) on round 2 of the two-round exchange."""
    if probe_tiles is None:
        probe_tiles = (STACKED_PROBE_TILES_ROUND2_DEFAULT
                       if route == "round2"
                       else STACKED_PROBE_TILES_DEFAULT)
    return max(0, min(int(probe_tiles), n_visit))


def resolve_probe_dtype(probe_dtype, probe_tiles_resolved: int) -> str:
    """Normalize the probe-precision knob at the launch boundary:
    ``None`` -> ``"f32"`` (the historical all-f32 launch, and the
    library default for forced routes), ``"auto"`` -> ``"bf16"`` (the
    quantized default wherever a probe pass actually runs), and *any*
    dtype degrades to ``"f32"`` when the resolved probe width is 0 -- a
    single-pass launch has no probe to quantize, and folding that into
    the resolution keeps spurious bf16/int8 trace variants of the same
    all-f32 program out of the compile registry (e.g. the exchange's
    round-2 route, whose probe default is 0)."""
    if probe_dtype is None:
        probe_dtype = "f32"
    elif probe_dtype == "auto":
        probe_dtype = "bf16"
    if probe_dtype not in PROBE_DTYPES:
        raise ValueError(
            f"probe_dtype {probe_dtype!r} not in {PROBE_DTYPES}")
    return "f32" if probe_tiles_resolved == 0 else probe_dtype


#: the fill of each launch plane's pad rows and pad tiles: dead tiles
#: (ids -1, ``valid`` False, rx -1) the sweep force-skips; int8 scales
#: pad with 1.0, the zero-guard of :meth:`StackedLeaves.quantized_pts`
_FILL = dict(pts=0.0, ids=-1, rx=-1.0, xc=0.0, xs=0.0, leaf_centers=0.0,
             leaf_radii=0.0, leaf_cnorm=0.0, valid=False, n_leaves=0,
             qpts=0, qscale=1.0)


def _pad_plane(name: str, a, rows: int, tiles: int):
    """Plane ``name`` padded to ``rows`` segments and, where it has a
    tile axis (every plane but ``n_leaves``), to ``tiles`` tiles."""
    w = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
    if a.ndim > 1:
        w[1] = (0, tiles - a.shape[1])
    if all(p == (0, 0) for p in w):
        return a
    return jnp.pad(a, w, constant_values=_FILL[name])


def _bucketed_arrays(stk: StackedLeaves, *, use_kernel: bool,
                     multiple: int = 1, probe_dtype: str = "f32",
                     rows: int | None = None, tiles: int | None = None):
    """The launch's arrays dict with the segment axis padded to the
    :func:`_bucket_segments` bucket (or to ``rows``) and the tile axis
    to ``tiles`` (default: the stack's own).  Pad rows and tiles are
    dead (``valid=False``, ``n_leaves=0``, ids -1) so the sweep
    force-skips them; the padded geometry planes are memoized in
    ``_derived`` under ``geom:``-prefixed keys (shared through tombstone
    republishes -- geometry never moves), the ids-derived pads under
    plain keys (rebuilt when the planes do move).  Pads are made where
    the stack lives.  ``multiple`` further rounds the bucket up (the
    mesh path needs the segment axis divisible by the device count; pad
    rows are free dead weight, and the memo keys carry the padded shape
    so variants coexist).  ``probe_dtype`` != "f32" adds the quantized
    probe plane (``qpts``) and the int8 per-tile scales (``qscale``).
    Returns ``(arrays, padded segment count)``."""
    N = stk.num_segments
    Np = _bucket_segments(N) if rows is None else int(rows)
    if multiple > 1:
        Np = _ceil_to(Np, multiple)
    L = stk.num_tiles if tiles is None else int(tiles)
    tag = "lane" if use_kernel else "raw"
    pts = stk.padded_pts() if use_kernel else stk.pts
    quant = {}
    if probe_dtype != "f32":
        qpts, qscale = stk.quantized_pts(probe_dtype, lane_pad=use_kernel)
        quant = dict(qpts=qpts)
        if qscale is not None:
            quant["qscale"] = qscale
    geom = dict(pts=pts, rx=stk.rx, xc=stk.xc, xs=stk.xs,
                leaf_centers=stk.leaf_centers, leaf_radii=stk.leaf_radii,
                leaf_cnorm=stk.leaf_cnorm)
    live = dict(ids=stk.ids, valid=stk.valid, n_leaves=stk.n_leaves)
    if Np == N and L == stk.num_tiles:
        return {**geom, **live, **quant}, Np

    def padded(key, planes):
        hit = stk._derived.get(key)
        if hit is None:
            hit = {f: _pad_plane(f, a, Np, L) for f, a in planes.items()}
            stk._derived[key] = hit
        return hit

    shape = f"{Np}:{L}"
    if quant:
        quant = padded(f"geom:quant:bucket:{shape}:{probe_dtype}:{tag}",
                       quant)
    return {**padded(f"geom:bucket:{shape}:{tag}", geom),
            **padded(f"bucket:{shape}:ids", live), **quant}, Np


@dataclasses.dataclass(frozen=True)
class PlacedStacks:
    """The stacks of a placed sharded index: ``stacks[j]`` lives on
    device ``j`` of ``mesh`` along ``axis`` (None: that shard has no
    segment).  The mesh launch takes each device's own stack as its
    block of the segment axis, padded where it lives to one common
    segment count and tile count, so no plane moves between devices.
    Segment order is shard by shard; ``num_segments`` counts the real
    ones."""

    stacks: tuple
    mesh: object
    axis: str = "shard"

    @property
    def present(self) -> list:
        return [s for s in self.stacks if s is not None]

    @property
    def n0(self) -> int:
        return self.present[0].n0

    @property
    def d(self) -> int:
        return self.present[0].d

    @property
    def num_segments(self) -> int:
        return sum(s.num_segments for s in self.present)

    @property
    def num_tiles(self) -> int:
        return max(s.num_tiles for s in self.present)

    @property
    def block_rows(self) -> int:
        """Segment rows of every device's block."""
        return _bucket_segments(max(s.num_segments for s in self.present))

    def arrays(self, *, use_kernel: bool, probe_dtype: str = "f32"):
        """``(arrays, Np, true_row, seg_shard, rows)``: the launch's
        planes as mesh arrays sharded along :attr:`axis`, assembled from
        the per-device blocks; the padded row count; the (Np,) masks of
        real rows and of each row's shard (-1 = pad); and the real rows'
        positions, in stack order."""
        devices = list(np.asarray(self.mesh.devices).flat)
        assert len(devices) == len(self.stacks), (devices, self.stacks)
        nl, L = self.block_rows, self.num_tiles
        blocks = []
        for dev, stk in zip(devices, self.stacks):
            if stk is None:
                blocks.append(self._dead_block(dev, nl, L, use_kernel,
                                               probe_dtype))
            else:
                blocks.append(_bucketed_arrays(
                    stk, use_kernel=use_kernel, probe_dtype=probe_dtype,
                    rows=nl, tiles=L)[0])
        arrays = {}
        for f in blocks[0]:
            local = [b[f] for b in blocks]
            sharding = NamedSharding(
                self.mesh, _P(self.axis, *(None,) * (local[0].ndim - 1)))
            arrays[f] = jax.make_array_from_single_device_arrays(
                (len(local) * nl,) + local[0].shape[1:], sharding, local)
        counts = [0 if s is None else s.num_segments for s in self.stacks]
        true_row = np.zeros((len(counts) * nl,), bool)
        seg_shard = np.full((len(counts) * nl,), -1, np.int32)
        for j, n in enumerate(counts):
            true_row[j * nl:j * nl + n] = True
            seg_shard[j * nl:j * nl + n] = j
        return (arrays, len(counts) * nl, true_row, seg_shard,
                np.nonzero(true_row)[0])

    def _dead_block(self, dev, nl, L, use_kernel, probe_dtype):
        """An all-pad block made on ``dev`` (a shard with no segment)."""
        n0, d = self.n0, self.d
        dp = _ceil_to(d, _LANE) if use_kernel else d
        shapes = dict(pts=((nl, L, n0, dp), jnp.float32),
                      ids=((nl, L, n0), jnp.int32),
                      rx=((nl, L, n0), jnp.float32),
                      xc=((nl, L, n0), jnp.float32),
                      xs=((nl, L, n0), jnp.float32),
                      leaf_centers=((nl, L, d), jnp.float32),
                      leaf_radii=((nl, L), jnp.float32),
                      leaf_cnorm=((nl, L, 1), jnp.float32),
                      valid=((nl, L), jnp.bool_),
                      n_leaves=((nl,), jnp.int32))
        if probe_dtype != "f32":
            shapes["qpts"] = ((nl, L, n0, dp),
                              jnp.bfloat16 if probe_dtype == "bf16"
                              else jnp.int8)
            if probe_dtype == "int8":
                shapes["qscale"] = ((nl, L, 1), jnp.float32)
        return {f: jnp.full(shape, _FILL[f], dtype, device=dev)
                for f, (shape, dtype) in shapes.items()}


def _live_tiles(stk) -> np.ndarray:
    """(N,) tiles holding a live point, per real segment (memoized per
    stack: ids-derived, so dropped by ids-plane rewrites)."""
    if isinstance(stk, PlacedStacks):
        return np.concatenate([_live_tiles(s) for s in stk.present])
    live = stk._derived.get("live_tiles")
    if live is None:
        live = np.asarray(stk.valid).sum(axis=1).astype(np.int64)
        stk._derived["live_tiles"] = live
    return live


#: arrays-dict fields whose pad/placement rides tombstone republishes
#: (pure tile geometry; ``geom:``-keyed in ``_derived``) vs the ids
#: planes that are rebuilt when deletes move them (plain keys).
_GEOM_FIELDS = ("pts", "rx", "xc", "xs", "leaf_centers", "leaf_radii",
                "leaf_cnorm")
_IDS_FIELDS = ("ids", "valid", "n_leaves")


def _placed_arrays(stk: StackedLeaves, arrays: dict, Np: int, mesh,
                   axis: str, use_kernel: bool,
                   probe_dtype: str = "f32") -> dict:
    """``arrays`` with every plane committed to ``mesh`` sharded along
    ``axis`` on the leading segment dimension (contiguous blocks of
    ``Np // mesh.shape[axis]`` segments per device, in stack order).

    Memoized in ``stk._derived`` keyed by the mesh's topology signature:
    the one-time host->device scatter is paid on the *first* launch
    against a given stack (or, on the serving path, by the compactor's
    pre-publish :func:`warm_stacked` replay -- off the query path), and
    every subsequent query's ``shard_map`` finds its operands already
    resident on their owning devices.  Geometry entries survive
    tombstone republishes (``geom:`` prefix); ids-plane entries are
    rebuilt when deletes move the planes."""
    sig = mesh_signature(mesh)
    tag = "lane" if use_kernel else "raw"

    def put(a):
        return jax.device_put(a, NamedSharding(
            mesh, _P(axis, *(None,) * (a.ndim - 1))))

    gkey = f"geom:mesh:{sig}:{axis}:{Np}:{tag}"
    geom = stk._derived.get(gkey)
    if geom is None:
        geom = {f: put(arrays[f]) for f in _GEOM_FIELDS}
        stk._derived[gkey] = geom
    lkey = f"mesh:{sig}:{axis}:{Np}:ids"
    live = stk._derived.get(lkey)
    if live is None:
        live = {f: put(arrays[f]) for f in _IDS_FIELDS}
        stk._derived[lkey] = live
    quant = {}
    if probe_dtype != "f32":
        # the quantized probe plane is pure geometry: placement memo
        # rides tombstone republishes like the f32 planes above
        qkey = f"geom:quant:mesh:{sig}:{axis}:{Np}:{probe_dtype}:{tag}"
        quant = stk._derived.get(qkey)
        if quant is None:
            quant = {f: put(arrays[f]) for f in ("qpts", "qscale")
                     if f in arrays}
            stk._derived[qkey] = quant
    return {**geom, **live, **quant}


# ----------------------------------------------------------------------
# compile-signature registry: every `_call_run_stacked` dispatch is
# classified as a hit (an already-seen jit signature: shapes + statics)
# or a miss (a fresh trace/compile).  The benches surface the totals and
# the CI ratio fence leans on them; `warm_stacked` replays the recent
# *templates* (signatures minus the stack's grid dims) against a
# soon-to-be-published stack so the first query on a new epoch finds its
# program compiled.
# ----------------------------------------------------------------------
_COMPILE_LOCK = threading.Lock()
_COMPILE_SIGS: "dict[tuple, int]" = {}
_COMPILE_STATS = {"misses": 0, "hits": 0,
                  "warm_compiles": 0, "warm_hits": 0, "warm_failures": 0}
_RECENT_TEMPLATES: "collections.OrderedDict[tuple, bool]" = \
    collections.OrderedDict()
_RECENT_TEMPLATES_SIZE = 16
# last few query-path misses (full signatures) -- the thing you grep
# when the timed-window miss counter is nonzero and you need to know
# *which* shape slipped past the warmup
_RECENT_MISSES: "collections.deque[tuple]" = collections.deque(maxlen=8)
_RECENT_WARM_ERRORS: "collections.deque[str]" = collections.deque(maxlen=4)


def record_warm_failure(where: str, exc: BaseException) -> None:
    """Count and log one warm-up that raised.  A warm-up never breaks a
    publish, but its failure means the first query on the new state
    compiles on path -- or, on a chip, that the program does not build
    at all -- so every such failure shows in ``warm_failures`` of
    :func:`stacked_compile_stats` and of the engine's ``stats()``."""
    with _COMPILE_LOCK:
        _COMPILE_STATS["warm_failures"] += 1
        _RECENT_WARM_ERRORS.append(f"{where}: {exc!r}")
    logger.warning("warm-up failed in %s: %r", where, exc, exc_info=exc)


def _record_sig(sig: tuple, template: tuple, warm: bool) -> bool:
    """Count one dispatch against the signature registry; remember the
    template (LRU) unless this is itself a warmup call."""
    with _COMPILE_LOCK:
        known = sig in _COMPILE_SIGS
        _COMPILE_SIGS[sig] = _COMPILE_SIGS.get(sig, 0) + 1
        if warm:
            _COMPILE_STATS["warm_hits" if known else "warm_compiles"] += 1
        else:
            _COMPILE_STATS["hits" if known else "misses"] += 1
            if not known:
                _RECENT_MISSES.append(sig)
                spans.count("stacked_compile_misses")
            _RECENT_TEMPLATES.pop(template, None)
            _RECENT_TEMPLATES[template] = True
            while len(_RECENT_TEMPLATES) > _RECENT_TEMPLATES_SIZE:
                _RECENT_TEMPLATES.popitem(last=False)
        return known


def stacked_compile_stats() -> dict:
    """Registry counters: ``misses``/``hits`` (serving dispatches that
    did / did not need a fresh trace), ``warm_compiles``/``warm_hits``
    (same, for :func:`warm_stacked` replays), ``warm_failures`` and
    ``recent_warm_errors`` (:func:`record_warm_failure`), plus the
    bench-facing
    aliases ``compile_count`` (all fresh traces, warm included -- warm
    ones are *off* the query path, which is the point) and ``cache_hit``
    (serving hits)."""
    with _COMPILE_LOCK:
        st = dict(_COMPILE_STATS)
        st["signatures"] = len(_COMPILE_SIGS)
        st["recent_misses"] = list(_RECENT_MISSES)
        st["recent_warm_errors"] = list(_RECENT_WARM_ERRORS)
    st["compile_count"] = st["misses"] + st["warm_compiles"]
    st["cache_hit"] = st["hits"]
    return st


def reset_stacked_compile_stats(full: bool = False) -> None:
    """Zero the counters; ``full=True`` also forgets the seen signatures
    and recent templates (a from-cold registry, for tests)."""
    with _COMPILE_LOCK:
        for key in _COMPILE_STATS:
            _COMPILE_STATS[key] = 0
        _RECENT_MISSES.clear()
        _RECENT_WARM_ERRORS.clear()
        if full:
            _COMPILE_SIGS.clear()
            _RECENT_TEMPLATES.clear()


def _mesh_axis_size(mesh, mesh_axis: str) -> int:
    """Devices along ``mesh_axis`` (0 when the axis is absent)."""
    if mesh is None:
        return 0
    return int(dict(mesh.shape).get(mesh_axis, 0))


def _call_run_stacked(stk: StackedLeaves, queries, k, *, frac, bq,
                      use_ball, use_cone, lambda_cap, probe_tiles,
                      probe_route="snapshot", probe_dtype=None,
                      extra_d=None, extra_i=None,
                      shard_bounds=None, use_kernel=None, interpret=None,
                      sort_planes=True, mesh=None, mesh_axis="shard",
                      _warm=False):
    use_kernel, interpret = resolve_stacked_backend(use_kernel, interpret)
    placed = isinstance(stk, PlacedStacks)
    if placed:
        mesh, mesh_axis = stk.mesh, stk.axis
    D = _mesh_axis_size(mesh, mesh_axis)
    if D <= 1 and not placed:
        mesh = None  # a 1-device (or axis-less) mesh IS the single
        #              launch -- run the plain program, share its traces
        D = 0
    p = resolve_probe_tiles(probe_tiles, _n_visit(stk, frac),
                            route=probe_route)
    pdt = resolve_probe_dtype(probe_dtype, p)
    N = stk.num_segments
    rows = None
    if placed:
        # every block already sits on its device: only the per-device
        # pads are made, where the block lives
        arrays, Np, true_row, seg_shard, rows = stk.arrays(
            use_kernel=bool(use_kernel), probe_dtype=pdt)
        num_shards = len(stk.stacks)
    else:
        arrays, Np = _bucketed_arrays(
            stk, use_kernel=bool(use_kernel),
            multiple=(D if mesh is not None else 1), probe_dtype=pdt)
        if mesh is not None:
            arrays = _placed_arrays(stk, arrays, Np, mesh, mesh_axis,
                                    bool(use_kernel), probe_dtype=pdt)
        bounds = (tuple(int(x) for x in shard_bounds) if shard_bounds
                  else ())
        num_shards = len(bounds)
        seg_shard = np.full((Np,), -1, np.int32)
        if bounds:
            assert sum(bounds) == N, (bounds, N)
            seg_shard[:N] = np.repeat(
                np.arange(num_shards, dtype=np.int32), bounds)
        true_row = np.arange(Np) < N
    has_extra = extra_d is not None
    q2 = jnp.atleast_2d(jnp.asarray(queries, jnp.float32))
    B = int(q2.shape[0])
    extra_k = int(extra_d.shape[1]) if has_extra else 0
    has_cap = lambda_cap is not None
    # the template omits the stack's grid dims (what warm_stacked fills
    # in from the stack it warms) and keeps the *requested* probe knob
    # (re-resolved per stack); the signature mirrors the jit cache key:
    # statics + every dynamic shape + the device-topology signature
    # (cross-mesh fence: a program compiled against one topology must
    # never be accounted -- or warmed -- against another).  The template
    # carries the Mesh object itself (hashable), so a warm replay always
    # targets exactly the topology the template was recorded against.
    template = (B, k, float(frac), int(bq), bool(use_ball),
                bool(use_cone), bool(use_kernel), bool(interpret),
                None if probe_tiles is None else int(probe_tiles),
                probe_route, probe_dtype, num_shards, has_extra, extra_k,
                has_cap, bool(sort_planes), mesh, mesh_axis)
    sig = (Np, stk.num_tiles, stk.n0, stk.d, B, k, float(frac), int(bq),
           bool(use_ball), bool(use_cone), bool(use_kernel),
           bool(interpret), p, pdt, num_shards, has_extra, extra_k,
           has_cap, bool(sort_planes), mesh_signature(mesh), mesh_axis)
    _record_sig(sig, template, _warm)
    runner = (_run_stacked if mesh is None
              else functools.partial(_run_stacked_mesh, mesh=mesh,
                                     mesh_axis=mesh_axis))
    out = runner(arrays, q2, lambda_cap,
                 extra_d if has_extra else None,
                 extra_i if has_extra else None,
                 jnp.asarray(seg_shard),
                 np.int32(N) if mesh is None else jnp.asarray(true_row),
                 n0=stk.n0, d=stk.d, k=k, frac=frac, bq=bq,
                 use_ball=use_ball, use_cone=use_cone,
                 use_kernel=bool(use_kernel),
                 interpret=bool(interpret), probe_tiles=p,
                 probe_dtype=pdt, num_shards=num_shards,
                 has_extra=has_extra, sort_planes=sort_planes)
    if Np != N:  # per-segment outputs slice back to the true rows
        bd, bi, fd, fi, counters, seg_skips, shard_kth, launch_counts = out
        take = slice(0, N) if rows is None else rows
        out = (bd[take], bi[take], fd, fi, counters, seg_skips[take],
               shard_kth, launch_counts)
    return out, p, pdt


def warm_stacked(stk: StackedLeaves, templates=None) -> int:
    """Pre-compile the stacked programs a soon-to-be-published stack will
    be queried through: replay ``templates`` (default: the registry's
    recently-seen ones) against ``stk`` with throwaway operands, so the
    jit cache is hot before the first real query lands.  Dummy caps are
    ``+inf`` arrays and dummy extras empty (+inf/-1) lists -- same
    shapes/tree-structure as serving, so the same trace; shard layout is
    fabricated (membership is dynamic, only the shard *count* shapes the
    program).  A template records the Mesh it served on (or ``None``),
    so each replay compiles against exactly the topology that recorded
    it -- a template from one mesh can never warm (or mis-place) a
    program on another.  Returns the number of templates replayed."""
    if templates is None:
        with _COMPILE_LOCK:
            templates = list(_RECENT_TEMPLATES)
    n = 0
    for t in templates:
        (B, k, frac, bq, use_ball, use_cone, use_kernel, interpret,
         probe_tiles, probe_route, probe_dtype, num_shards, has_extra,
         extra_k, has_cap, sort_planes, mesh, mesh_axis) = t
        q = np.ones((B, stk.d), np.float32)
        cap = np.full((B,), np.inf, np.float32) if has_cap else None
        ed = (np.full((B, extra_k), np.inf, np.float32)
              if has_extra else None)
        ei = np.full((B, extra_k), -1, np.int32) if has_extra else None
        sb = (([stk.num_segments] + [0] * (num_shards - 1))
              if num_shards else None)
        try:
            _call_run_stacked(
                stk, q, k, frac=frac, bq=bq, use_ball=use_ball,
                use_cone=use_cone, lambda_cap=cap,
                probe_tiles=probe_tiles, probe_route=probe_route,
                probe_dtype=probe_dtype,
                extra_d=ed, extra_i=ei, shard_bounds=sb,
                use_kernel=use_kernel, interpret=interpret,
                sort_planes=sort_planes, mesh=mesh, mesh_axis=mesh_axis,
                _warm=True)
            n += 1
        except Exception as e:  # warmup must never break a publish
            record_warm_failure("warm_stacked", e)
    return n


def stacked_sweep_search(stk: StackedLeaves, queries, k: int = 1, *,
                         frac: float = 1.0, bq: int = 8,
                         use_ball: bool = True, use_cone: bool = True,
                         lambda_cap=None, probe_tiles: int = 0,
                         probe_dtype: str | None = None,
                         use_kernel: bool | None = None,
                         interpret: bool | None = None,
                         mesh=None, mesh_axis: str = "shard"):
    """Sweep all of ``stk``'s segments in one launch; per-segment planes.

    Returns ``(dists (N, B, k) ascending, global ids (N, B, k),
    counters (8,), per-segment skip counts (N,))``.  ``probe_tiles > 0``
    runs the two-pass form (probe-tightened cap, see
    :func:`_run_stacked`); the default 0 is the single-pass sweep under
    the entry cap alone.  ``use_kernel=None`` resolves to the Pallas
    kernel on TPU and the vmapped jnp reference elsewhere (interpret
    mode is a parity tool, not a serving backend) -- the same rule
    ``DispatchPolicy.prefer_pallas`` applies to the sequential backends.
    The serving entry point (in-launch global merge, no host merge) is
    :func:`stacked_sweep_query`.
    """
    out, _, _ = _call_run_stacked(stk, queries, k, frac=frac, bq=bq,
                                  use_ball=use_ball, use_cone=use_cone,
                                  lambda_cap=lambda_cap,
                                  probe_tiles=probe_tiles,
                                  probe_dtype=probe_dtype,
                                  use_kernel=use_kernel,
                                  interpret=interpret,
                                  mesh=mesh, mesh_axis=mesh_axis)
    bd, bi, _, _, counters, seg_skips, _, _ = out
    return bd, bi, counters, seg_skips


def stacked_sweep_query(stk: StackedLeaves, queries, k: int = 1, *,
                        frac: float = 1.0, bq: int = 8,
                        use_ball: bool = True, use_cone: bool = True,
                        lambda_cap=None, probe_tiles: int | None = None,
                        probe_route: str = "snapshot",
                        probe_dtype: str | None = None,
                        extra_d=None, extra_i=None, shard_bounds=None,
                        use_kernel: bool | None = None,
                        interpret: bool | None = None,
                        mesh=None, mesh_axis: str = "shard"):
    """Serving entry point: probe + main + merge in ONE device program.

    Returns ``(dists (B, k), global ids (B, k), counters (8,), info)``
    -- the *merged* global top-k over every segment plus the optional
    ``extra_d``/``extra_i`` ``(B, M)`` candidate list (the delta scan's
    top-k), with no host-side per-segment merge.  ``extra`` must hold
    real, de-duplicated candidates *disjoint from every segment* (the
    delta/segment split guarantees this): they also seed the in-launch
    global top-k, so duplicates would break the threshold's validity.
    ``probe_tiles=None`` resolves to ``probe_route``'s default
    (:func:`resolve_probe_tiles`); 0 degenerates to the single-pass
    sweep, >= the visit-list length makes the probe pass the full
    sweep.  ``shard_bounds`` (optional, segments per shard in
    stack order) additionally reduces per-shard merged k-ths on device
    (``info["shard_kth"]``, the exchange's lambda-cache diagnostic).

    ``info`` carries ``seg_skips`` (N,), ``forced_skips`` (N,) --
    the pad/dead tiles each segment's visit list force-skips, so
    ``seg_skips - forced_skips`` is the *live*-tile skip count --
    ``shard_kth`` ((S, B) or None) and ``probe`` (resolved tile count /
    scanned / skipped: the probe-pass overhead surfaced in
    ``BENCH_serve.json``), plus ``mesh_devices`` -- the device count the
    launch actually spanned (1 = the single-device program; see
    :func:`_run_stacked_mesh` for the ``mesh=`` form).
    """
    with spans.span("p2h.stacked.launch"):  # host side: returns at dispatch
        out, p, pdt = _call_run_stacked(
            stk, queries, k, frac=frac, bq=bq, use_ball=use_ball,
            use_cone=use_cone, lambda_cap=lambda_cap,
            probe_tiles=probe_tiles, probe_route=probe_route,
            probe_dtype=probe_dtype, extra_d=extra_d, extra_i=extra_i,
            shard_bounds=shard_bounds, use_kernel=use_kernel,
            interpret=interpret, sort_planes=False, mesh=mesh,
            mesh_axis=mesh_axis)
    _, _, fd, fi, counters, seg_skips, shard_kth, launch_counts = out
    # the device time as the host sees it: the launch's own outputs, so
    # the first blocking read below (launch_counts) waits for nothing more
    with spans.span("p2h.device_wait"):
        jax.block_until_ready((fd, fi, counters, launch_counts))
    probe_skips, scan_steps, insert_steps = (
        int(c) for c in np.asarray(launch_counts))
    if scan_steps:  # the jnp twin counts no steps
        spans.count("stacked_scan_steps", scan_steps)
        spans.count("stacked_insert_steps", insert_steps)
    B = int(np.atleast_2d(np.asarray(queries)).shape[0])
    nqb = -(-B // bq)
    n_visit = _n_visit(stk, frac)
    live = _live_tiles(stk)
    forced = nqb * np.maximum(0, n_visit - live)  # invalid tiles visited
    probe_scanned = int(stk.num_segments * nqb * p) - probe_skips
    info = {
        "seg_skips": seg_skips,
        "forced_skips": forced,
        "shard_kth": shard_kth,
        "probe": {"tiles": p, "scanned": probe_scanned,
                  "skipped": probe_skips, "dtype": pdt},
        "mesh_devices": (len(stk.stacks) if isinstance(stk, PlacedStacks)
                         else max(1, _mesh_axis_size(mesh, mesh_axis))),
    }
    return fd, fi, counters, info
