"""P2HEngine: micro-batched, auto-dispatched, lambda-warm P2HNNS serving.

Composes the three serve-layer pieces over a built :class:`P2HIndex`
(optionally with a :class:`ShardedP2HIndex`), a mutable
:class:`repro.stream.MutableP2HIndex`, or a sharded mutable
:class:`repro.stream.ShardedMutableP2HIndex` -- in the mutable cases
every micro-batch pins one epoch-numbered snapshot (an epoch *vector*
pin across shards for the sharded index, served through the two-round
lambda exchange) and the lambda cache is epoch-tagged per shard (see
``lambda_cache``):

  * :class:`~repro.serve.batcher.MicroBatcher` -- fixed-shape slot batches
    (jitted backends never retrace);
  * :class:`~repro.serve.dispatch.DispatchPolicy` -- per-batch backend
    choice by occupancy / k / recall target;
  * :class:`~repro.serve.lambda_cache.LambdaCache` -- warm-start
    ``lambda_cap`` from previously-served neighbor queries (exactness
    argument in that module's docstring).

The engine is the host-side control loop; every device-side program it
calls is an existing jitted backend (``dfs_search``, ``sweep_search``,
``sweep_search_pallas``, ``_sharded_query``).
"""
from __future__ import annotations

import collections
from typing import Any

import numpy as np

from repro.core import search
from repro.core.balltree import normalize_query
from repro.runtime import spans
from repro.serve.batcher import MicroBatcher
from repro.serve.dispatch import DispatchPolicy, Route
from repro.serve.lambda_cache import LambdaCache
from repro.serve.resilience import (RESILIENCE_COUNTERS, Deadline,
                                    QueryRejected, ResilienceConfig,
                                    ShardSupervisor)

__all__ = ["P2HEngine"]

#: result metadata for a batch served with nothing missing
_META_COMPLETE = {"complete": True, "degraded": False, "shed": False,
                  "missing_shards": ()}


class P2HEngine:
    """Serving front-end for P2HNNS query traffic.

    Two APIs:

      * streaming -- ``submit()`` requests, ``flush()``, ``result(ticket)``;
      * drop-in   -- ``query(queries, k)`` (same contract as
        ``P2HIndex.query``; also reachable as
        ``index.query(..., engine=engine)``).

    ``use_cache=False`` disables the lambda warm start (cold dispatch);
    with it enabled, answers are still bit-identical to cold (the cache
    only ever supplies *valid* caps, see ``lambda_cache``).

    ``resilience`` (a :class:`repro.serve.resilience.ResilienceConfig`)
    arms the read-path resilience layer: per-request deadlines
    (``deadline_s=`` on submit/query) propagate into per-shard budgets,
    shard timeouts/errors degrade to exact-over-live-shards partial
    results (``result_meta`` / ``return_meta=True`` expose
    ``missing_shards`` and ``complete``), per-shard circuit breakers
    fast-fail wedged shards, and ``max_pending`` sheds at admission
    with :class:`~repro.serve.resilience.QueryRejected`.  Left at None
    (the default) the engine runs the historical fail-fast path
    bit-for-bit.
    """

    def __init__(self, index, *, sharded=None, slot_size: int = 8,
                 policy: DispatchPolicy | None = None, use_cache: bool = True,
                 cache_bits: int = 14, seed: int = 0,
                 resilience: ResilienceConfig | None = None):
        import dataclasses

        import jax

        from repro.stream.mutable import MutableP2HIndex
        from repro.stream.sharded import ShardedMutableP2HIndex

        if isinstance(index, (MutableP2HIndex, ShardedMutableP2HIndex)):
            # update-aware serving: every micro-batch pins one snapshot
            # (an epoch *vector* pin for the sharded mutable index),
            # lambda-cache entries are epoch-tagged (see lambda_cache)
            assert sharded is None, "mutable + sharded not supported yet"
            self.mutable = index
            self._sharded_mutable = isinstance(index, ShardedMutableP2HIndex)
            self.index = None
            d = index.d
            # monotone over inserts; refreshed from the pinned snapshot
            # each batch so caps always use a current R >= max ||x||
            self.max_norm = float(index.max_norm)
        else:
            self.mutable = None
            self._sharded_mutable = False
            self.index = index
            tree = index.tree
            d = tree.d
            # R >= max ||x||: every point lies in the root ball
            self.max_norm = float(
                np.linalg.norm(np.asarray(tree.centers[0]))
                + float(tree.radii[0]))
        self.sharded = sharded
        self.policy = policy or DispatchPolicy()
        if self.policy.prefer_pallas is None:
            self.policy = dataclasses.replace(
                self.policy,
                prefer_pallas=jax.default_backend() == "tpu")
        self.resilience = resilience
        self._supervisor = (ShardSupervisor(resilience)
                            if resilience is not None else None)
        self.batcher = MicroBatcher(
            d, slot_size,
            max_pending=resilience.max_pending if resilience else None)
        self.cache = (LambdaCache(d, self.max_norm, n_bits=cache_bits,
                                  seed=seed) if use_cache else None)
        self._results: dict[int, tuple] = {}
        self._meta: dict[int, dict] = {}
        self._shed = {"queue_full": 0, "deadline": 0, "expired_batches": 0}
        self._route_counts: dict[str, int] = {}
        self._counters: dict[str, np.ndarray] = {}
        # p2h.search durations of this engine's batches, the last
        # spans.RING of them: latency_p50_ms / latency_p99_ms
        self._search_s = collections.deque(maxlen=spans.RING)
        self._batch_seq = 0  # p2h.batch's ``batch`` attr
        self._batches = 0
        self._queries_served = 0
        # placement generation tracking (sharded mutable): every batch
        # pins the router version its snapshot was routed under, so a
        # live split/merge is observable as a version transition here --
        # cap *soundness* across the transition is the lambda cache's
        # epoch-vector length check, not this counter
        self._router_version = None
        self._router_transitions = 0
        # largest multi-device mesh any served snapshot carried (1 =
        # every batch ran single-program); observability only -- the
        # mesh itself travels snapshot -> exchange -> stacked launch
        self._mesh_devices = 1

    # ------------------------------------------------------------------
    # streaming API
    # ------------------------------------------------------------------
    def submit(self, query, k: int = 1, *, recall_target: float = 1.0,
               normalize: bool = True,
               deadline_s: float | None = None) -> int:
        """Enqueue one hyperplane query; returns a ticket for result().

        ``deadline_s`` gives the request a latency budget from now:
        exhausted-at-submit requests (and, with
        ``resilience.max_pending`` set, submits into a full queue) are
        rejected with :class:`~repro.serve.resilience.QueryRejected`
        instead of queueing -- the rejection is counted in
        ``stats()["resilience"]``."""
        q = np.asarray(query, np.float32).reshape(1, -1)
        if normalize:
            q = normalize_query(q)
        deadline = (Deadline.after(deadline_s)
                    if deadline_s is not None else None)
        try:
            return self.batcher.submit(q[0], k, recall_target,
                                       deadline=deadline)
        except QueryRejected as e:
            self._shed[e.reason] = self._shed.get(e.reason, 0) + 1
            raise

    def flush(self) -> int:
        """Serve every pending request; returns the number of batches."""
        n = 0
        for mb in self.batcher.drain():
            self._execute(mb)
            n += 1
        return n

    def result(self, ticket: int):
        """(dists (k,), ids (k,)) for a served ticket (pops it, along
        with its metadata -- read :meth:`result_meta` first)."""
        self._meta.pop(ticket, None)
        return self._results.pop(ticket)

    def result_meta(self, ticket: int) -> dict:
        """Degradation metadata for a served-but-not-yet-popped ticket:
        ``complete`` (False iff a missing shard could hold a closer
        point), ``missing_shards``, ``degraded``, ``shed``."""
        return self._meta.get(ticket, _META_COMPLETE)

    # ------------------------------------------------------------------
    # drop-in API
    # ------------------------------------------------------------------
    def query(self, queries, k: int = 1, *, recall_target: float = 1.0,
              method: str | None = None, normalize: bool = True,
              return_stats: bool = False, deadline_s: float | None = None,
              return_meta: bool = False):
        """Batch query with the same contract as ``P2HIndex.query``.

        ``method`` forces a dispatch route (None = auto).
        ``deadline_s`` bounds the whole call's latency budget (shared by
        every row); with the resilience layer armed, shards that cannot
        answer in time degrade the result instead of stalling it --
        ``return_meta=True`` appends the per-batch degradation metadata
        (``complete``/``missing_shards``, see :meth:`result_meta`)."""
        deadline = (Deadline.after(deadline_s)
                    if deadline_s is not None else None)
        if deadline is not None and deadline.expired:
            self._shed["deadline"] += 1
            raise QueryRejected("deadline")
        q = np.atleast_2d(np.asarray(queries))
        if normalize:
            q = normalize_query(q)
        q = q.astype(np.float32)
        # force=True: the drop-in path drains immediately, so its own
        # rows are in-flight work, not backlog the queue bound guards
        tickets = [self.batcher.submit(row, k, recall_target,
                                       deadline=deadline, force=True)
                   for row in q]
        for mb in self.batcher.drain():
            self._execute(mb, method=method)
        metas = [self.result_meta(t) for t in tickets]
        ds, is_ = zip(*(self.result(t) for t in tickets))
        bd, bi = np.stack(ds), np.stack(is_)
        out = (bd, bi)
        if return_stats:
            out += (self.stats(),)
        if return_meta:
            out += (metas,)
        return out

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _execute(self, mb, *, method: str | None = None):
        self._batch_seq += 1
        with spans.span("p2h.batch", batch=self._batch_seq):
            self._run_batch(mb, method)

    def _run_batch(self, mb, method: str | None):
        deadline = mb.deadline
        if (mb.deadlines and all(d is not None and d.expired
                                 for d in mb.deadlines)):
            # every member's budget burned while queued: shed the batch
            # (inf/-1 + shed metadata, never an exception -- the callers
            # already hold tickets) instead of running work nobody can
            # use within its budget
            empty = (np.full((mb.k,), np.inf, np.float32),
                     np.full((mb.k,), -1, np.int32))
            meta = {"complete": False, "degraded": True, "shed": True,
                    "missing_shards": ()}
            for ticket in mb.tickets:
                self._results[ticket] = empty
                self._meta[ticket] = meta
            self._shed["expired_batches"] += 1
            self._batches += 1
            self._queries_served += mb.occupancy
            return
        # resilient exchange iff this batch carries a deadline or the
        # engine was armed -- otherwise the historical path, bit-for-bit
        resilient = (self._sharded_mutable
                     and (self._supervisor is not None
                          or deadline is not None))
        if resilient and self._supervisor is None:
            # deadline on an unarmed engine: default supervision, kept
            # so breaker state and counters persist across batches
            self._supervisor = ShardSupervisor()
        with spans.span("p2h.pin"):
            # pin one consistent view for the whole micro-batch: concurrent
            # inserts/deletes publish new snapshots, this batch never sees
            # them
            snap = (self.mutable.snapshot() if self.mutable is not None
                    else None)
            if snap is not None and self._sharded_mutable:
                rv = getattr(snap, "router_version", 0)
                if self._router_version is not None \
                        and rv != self._router_version:
                    self._router_transitions += 1
                self._router_version = rv
            fanout = (len(snap.segments) + len(snap.deltas)) if snap else 1
            if snap is not None:
                from repro.kernels.stacked_sweep import tile_density

                # snapshot-composition signals for the stacked crossover:
                # live sealed segments (the units one launch can absorb),
                # live delta rows over live points, dead over sealed rows,
                # live-tile fraction of the would-be stacked grid
                stackable = sum(1 for s in snap.segments if s.live)
                delta_frac = snap.delta_live / max(1, snap.live_count)
                tombstone_frac = snap.tombstone_frac
                density = tile_density(snap.segments)
            else:
                stackable, delta_frac, tombstone_frac = 0, 0.0, 0.0
                density = 1.0
            mesh = getattr(snap, "mesh", None)
            mesh_devices = (1 if mesh is None
                            else int(np.asarray(mesh.devices).size))
            if mesh_devices > 1:
                self._mesh_devices = mesh_devices
            route = (Route(method, frac=self.policy.frac_for_recall(
                         mb.recall_target) if method == "beam" else 1.0,
                         reason="forced")
                     if method is not None else
                     self.policy.route(mb.occupancy, mb.k, mb.recall_target,
                                       sharded=self.sharded is not None,
                                       segments=fanout,
                                       stackable=stackable,
                                       delta_frac=delta_frac,
                                       tombstone_frac=tombstone_frac,
                                       tile_density=density,
                                       mesh_devices=mesh_devices))
        # warm start: valid caps only for exact routes (a cap bounds the
        # *exact* k-th distance; applying it to a budgeted beam could prune
        # candidates the direct beam would have returned)
        # ... and never for the resilient exchange: the cache's caps
        # bound the *full*-set k-th, which can undercut the
        # live-shard-restricted k-th a degraded answer must match
        caps = None
        if self.cache is not None and route.method != "beam" \
                and not resilient:
            with spans.span("p2h.cache.lookup"):
                if snap is not None:
                    # inserts may have grown max ||x||; the cap formula
                    # needs the current bound (monotone, so only grows)
                    self.cache.max_norm = max(self.cache.max_norm,
                                              snap.max_norm)
                # look up live slots only: pad rows replicate slot 0, and
                # counting them would inflate hit/miss stats with dead
                # work
                c = np.full((len(mb.queries),), np.inf, np.float32)
                c[:mb.occupancy] = self.cache.lookup(
                    mb.queries[:mb.occupancy], mb.k,
                    min_epoch=snap.last_delete_epoch if snap else 0)
                if np.isfinite(c).any():
                    caps = c
        # the backend call up to its answers on the host
        with spans.span("p2h.search") as search_span:
            shard_kth = None
            # the policy (not the library-level fan-out default) owns
            # the stacked decision on the engine path: pass it down
            # explicitly so snapshot/exchange auto-promotion never
            # overrides a route the crossover knobs resolved to
            # sequential, and route stats stay truthful about which
            # schedule actually ran.  The policy's probe_tiles knob rides
            # along for the two-pass program.
            use_stacked = route.method == "stacked"
            meta = None
            degraded = False
            if snap is not None and self._sharded_mutable:
                # epoch-vector pin: the two-round exchange also reports
                # each shard's local k-th bound for per-shard cache
                # components
                bd, bi, cnt, info = snap.query(
                    mb.queries, mb.k, method=route.method, frac=route.frac,
                    lambda_cap=caps, return_counters=True, return_info=True,
                    stacked=use_stacked, probe_tiles=route.probe_tiles,
                    probe_dtype=route.probe_dtype,
                    deadline=deadline if resilient else None,
                    resilience=self._supervisor if resilient else None)
                shard_kth = info["shard_kth"]  # (S, B)
                degraded = bool(info.get("degraded", False))
                if resilient:
                    meta = {"complete": bool(info.get("complete", True)),
                            "degraded": degraded, "shed": False,
                            "missing_shards": tuple(
                                info.get("missing_shards", ()))}
            elif snap is not None:
                bd, bi, cnt = snap.query(
                    mb.queries, mb.k, method=route.method, frac=route.frac,
                    lambda_cap=caps, return_counters=True,
                    stacked=use_stacked, probe_tiles=route.probe_tiles,
                    probe_dtype=route.probe_dtype)
            else:
                bd, bi, cnt = self._run_backend(route, mb.queries, mb.k,
                                                caps)
            bd, bi = np.asarray(bd), np.asarray(bi)
        self._search_s.append(search_span.duration_s)

        for slot, ticket in enumerate(mb.tickets):
            self._results[ticket] = (bd[slot], bi[slot])
            if meta is not None:
                self._meta[ticket] = meta
        # a degraded batch's per-shard k-ths are restricted-set bounds
        # with +inf rows for the missing shards: skip the cache update
        # entirely rather than reason about partial validity
        if self.cache is not None and not degraded:
            with spans.span("p2h.cache.update"):
                live = slice(0, mb.occupancy)
                if shard_kth is not None:
                    self.cache.update_sharded(
                        mb.queries[live], mb.k, shard_kth.T[live],
                        epoch=snap.epoch,
                        min_epoch=snap.last_delete_epoch)
                else:
                    self.cache.update(
                        mb.queries[live], mb.k, bd[live, mb.k - 1],
                        epoch=snap.epoch if snap else 0,
                        min_epoch=snap.last_delete_epoch if snap else 0)
        # stats
        self._route_counts[route.method] = (
            self._route_counts.get(route.method, 0) + 1)
        c8 = np.asarray(cnt)
        self._counters[route.method] = (
            self._counters.get(route.method, np.zeros(8, np.int64)) + c8)
        self._batches += 1
        self._queries_served += mb.occupancy

    def _run_backend(self, route: Route, q: np.ndarray, k: int, caps):
        tree = self.index.tree
        is_bc = self.index.variant == "bc"
        common = dict(use_ball=is_bc, use_cone=is_bc)
        if route.method == "sharded":
            assert self.sharded is not None, "no sharded index attached"
            bd, bi, st = self.sharded.query(q, k, normalize=False,
                                            lambda_cap=caps)
            return bd, bi, np.array([st[n] for n in
                                     search._COUNTER_NAMES], np.int64)
        if route.method == "dfs":
            return search.dfs_search(tree, q, k, use_collab=is_bc,
                                     lambda_cap=caps, **common)
        if route.method == "stacked":
            # a frozen index is a single tree: the stacked sweep
            # degenerates to the ordinary one (forced-route escape hatch)
            return search.sweep_search(tree, q, k, frac=1.0,
                                       lambda_cap=caps, **common)
        if route.method == "sweep":
            return search.sweep_search(tree, q, k, frac=1.0,
                                       lambda_cap=caps, **common)
        if route.method == "beam":
            return search.sweep_search(tree, q, k, frac=route.frac, **common)
        if route.method == "pallas":
            from repro.kernels import ops

            return ops.sweep_search_pallas(tree, q, k, frac=1.0,
                                           lambda_cap=caps, **common)
        raise ValueError(f"unknown route {route.method!r}")

    # ------------------------------------------------------------------
    def route_counters(self, method: str) -> np.ndarray:
        """Cumulative (8,) search counters for one dispatch route."""
        return np.array(self._counters.get(method, np.zeros(8, np.int64)))

    def total_counters(self) -> np.ndarray:
        """Cumulative (8,) search counters summed over all routes."""
        out = np.zeros(8, np.int64)
        for c in self._counters.values():
            out += c
        return out

    def stats(self) -> dict:
        """This engine's counters since :meth:`reset_stats`;
        ``latency_p50_ms`` / ``latency_p99_ms`` over its last
        ``spans.RING`` batches' ``p2h.search`` spans.  ``spans`` and
        ``span_counters`` are the process-wide recorder
        (:mod:`repro.runtime.spans`), which every engine and index of
        the process records into."""
        lat = sorted(self._search_s)

        def pct(p):
            if not lat:
                return float("nan")
            return lat[min(len(lat) - 1, int(round(p / 100 * (len(lat) - 1))))]

        out: dict[str, Any] = {
            "batches": self._batches,
            "queries": self._queries_served,
            "routes": dict(self._route_counts),
            "latency_p50_ms": pct(50) * 1e3,
            "latency_p99_ms": pct(99) * 1e3,
            "counters": {m: search.SearchStats(c)
                         for m, c in self._counters.items()},
        }
        rec = spans.snapshot()
        out["spans"], out["span_counters"] = rec["spans"], rec["counters"]
        if self.cache is not None:
            out["lambda_cache"] = self.cache.stats()
        if self._router_version is not None:
            out["router_version"] = self._router_version
            out["router_transitions"] = self._router_transitions
        if self._mesh_devices > 1:
            out["mesh_devices"] = self._mesh_devices
        # process-wide: warm-ups that raised (pre-publish, post-publish,
        # round-1 and template replays) -- nonzero means some program was
        # left to compile on the query path, or did not build at all
        from repro.kernels.stacked_sweep import stacked_compile_stats
        out["warm_failures"] = stacked_compile_stats()["warm_failures"]
        admission = getattr(self.mutable, "admission_stats", None)
        if callable(admission):
            # write-admission counters (seals/stalls/pending) from the
            # mutable index: the serving-side view of whether compaction
            # backpressure ever stalled an acknowledged write
            out["admission"] = admission()
        # uniform resilience surface: zero-filled when the layer never
        # armed, so dashboards/benches key the same fields either way
        res: dict[str, Any] = {k: 0 for k in RESILIENCE_COUNTERS}
        if self._supervisor is not None:
            res.update(self._supervisor.stats())
        res["shed_queue_full"] = self._shed["queue_full"]
        res["shed_deadline"] = self._shed["deadline"]
        res["shed_expired_batches"] = self._shed["expired_batches"]
        out["resilience"] = res
        if self._sharded_mutable:
            # router-drift tripwire (PR 7): deletes whose gid no shard
            # owned -- surfaced next to the degradation counters so
            # "observable, not just survivable" covers writes too
            out["misroutes"] = self.mutable.misroutes
        return out

    def reset_stats(self):
        """Zero this engine's counters, and the process-wide span
        recorder with them (every engine's spans)."""
        self._route_counts.clear()
        self._counters.clear()
        self._search_s.clear()
        spans.reset()
        self._batches = 0
        self._queries_served = 0
        self._shed = {"queue_full": 0, "deadline": 0, "expired_batches": 0}
