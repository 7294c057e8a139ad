"""Open loop: independent users send distinct hyperplanes at ``k``, due
on a Poisson schedule fixed before the window.

Mix keys: ``k``, ``rate_per_s``, ``warm_queries``, and optionally
``submit`` (keyword arguments of the engine's ``submit``).  A run of
``seconds`` offers ``rate_per_s * seconds`` queries: the same work for
every seed.  The loop hands the system at most one slot batch per call,
so each query completes with its batch, and every latency runs from
when the query was due.
"""
from __future__ import annotations

import time

import numpy as np

import harness


def shape(mix: dict, seconds: float) -> dict:
    """The generator's sizes for a run of ``seconds``."""
    return {"n_queries": mix["warm_queries"] + window_queries(mix, seconds)}


def window_queries(mix: dict, seconds: float) -> int:
    return int(round(mix["rate_per_s"] * seconds))


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """Arrival offsets of ``n`` queries over ``seconds``: Poisson-like,
    with exponential gaps.  Every seed draws the same set of gaps (the
    ``n`` quantiles of the exponential distribution) in its own order,
    so the offered load and its burstiness are the same for all seeds."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = np.random.default_rng([int(seed) % (1 << 63), 7]).permutation(
        gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())


class Driver:
    def __init__(self, system, mix, data, log, spans, *, slot: int,
                 seed: int):
        self.system, self.mix, self.log, self.spans = system, mix, log, spans
        self.slot, self.seed = slot, seed
        self.k, self.n_warm = mix["k"], mix["warm_queries"]
        self.submit = mix.get("submit")
        self.queries = data.queries

    def _serve(self, qs):
        return self.system.serve(qs, self.k, self.submit)

    def warm(self):
        qs = self.queries[:self.n_warm]
        for b in range(0, len(qs), self.slot):
            with self.spans("bench.warm"):
                ans = self._serve(qs[b:b + self.slot])
            self.log.record_queries(qs[b:b + self.slot], ans, False)

    def window(self, seconds: float) -> dict:
        qs, log, slot = self.queries[self.n_warm:], self.log, self.slot
        t0 = time.perf_counter()
        due = t0 + arrivals(len(qs), seconds, self.seed)
        nxt, pending, backlog_max, flush_s = 0, [], 0, []
        slowest = [0.0, 0.0]  # the slowest flush: wall and CPU seconds
        while nxt < len(qs) or pending:
            now = time.perf_counter()
            while nxt < len(qs) and due[nxt] <= now:
                pending.append(nxt)
                log.lateness_s.append(now - due[nxt])
                nxt += 1
            backlog_max = max(backlog_max, len(pending))
            if not pending:
                with self.spans("bench.wait"):
                    time.sleep(max(0.0, due[nxt] - time.perf_counter()))
                continue
            batch, pending = pending[:slot], pending[slot:]
            t_flush, cpu = time.perf_counter(), time.thread_time()
            with self.spans("bench.flush"):
                ans = self._serve(qs[batch])
            done = time.perf_counter()
            flush_s.append(done - t_flush)
            if flush_s[-1] >= slowest[0]:
                slowest = [flush_s[-1], time.thread_time() - cpu]
            log.record_queries(qs[batch], ans, True)
            log.latency_s.extend(done - due[i] for i in batch)
        return {"window_s": time.perf_counter() - t0,
                "backlog_max": backlog_max, "batches": len(flush_s),
                "flush_ms": harness.summary_ms(flush_s),
                "slowest_flush_wall_cpu_ms": [v * 1e3 for v in slowest]}

    def replay(self, seconds: float, block: int = 256) -> None:
        """Every query of a run of ``seconds``, answered untimed (the
        control)."""
        qs = self.queries[:self.n_warm + window_queries(self.mix, seconds)]
        for b in range(0, len(qs), block):
            self.log.record_queries(qs[b:b + block],
                                    self._serve(qs[b:b + block]),
                                    b >= self.n_warm)
