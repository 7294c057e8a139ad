"""Closed-loop rounds of margin-based active learning.

Each round serves one hyperplane per class at ``k`` and waits for all of
them, deletes every distinct returned id (one call per gid), inserts the
round's new points, and moves every hyperplane by a seeded step of
``step_frac`` of its norm.

Mix keys: ``classes``, ``k``, ``step_frac``, ``inserts_per_round``,
``warm_rounds``, ``rounds_per_s``, and optionally ``submit``.  The window
is a fixed amount of work: ``rounds_per_s * seconds`` rounds, timed end
to end, so a faster or slower program ends the window in the same index
state, and rates are taken over all of that work and all of its time.
"""
from __future__ import annotations

import time

import numpy as np

import harness


def window_rounds(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rounds_per_s"] * seconds)))


def shape(mix: dict, seconds: float) -> dict:
    """The generator's sizes for a run of ``seconds``."""
    r = mix["warm_rounds"] + window_rounds(mix, seconds)
    return {"n_queries": mix["classes"],
            "n_pool": r * mix["inserts_per_round"],
            "n_steps": r * mix["classes"]}


class Driver:
    def __init__(self, system, mix, data, log, spans, *, slot: int,
                 seed: int):
        del slot, seed
        self.system, self.mix, self.log, self.spans = system, mix, log, spans
        self.submit = mix.get("submit")
        self.pool = data.pool
        self.q = data.queries.astype(np.float64)
        self.steps = data.steps.astype(np.float64).reshape(
            -1, mix["classes"], self.q.shape[1])
        self.r = 0
        self.delete_s: list[float] = []  # window delete calls
        self.insert_s: list[float] = []  # window insert_batch calls

    def _round(self, window: bool):
        log, mix, spans = self.log, self.mix, self.spans
        tag = (lambda name: name) if window else (lambda name: "bench.warm")
        qs = self.q.astype(np.float32)
        ts = time.perf_counter()
        with spans(tag("bench.flush")):
            ans = self.system.serve(qs, mix["k"], self.submit)
        lat = time.perf_counter() - ts
        log.record_queries(qs, ans, window)
        if window:
            log.latency_s.extend([lat] * len(qs))
        labelled = np.unique(np.concatenate(
            [a[1] for a in ans if a is not None] or [np.zeros(0)]))
        for g in labelled[labelled >= 0].astype(np.int64).tolist():
            tw = time.perf_counter()
            with spans(tag("bench.delete")):
                ok = self.system.delete(g)
            if window:
                log.write_s.append(time.perf_counter() - tw)
                self.delete_s.append(log.write_s[-1])
            log.record_delete(g, ok, window)
        per = mix["inserts_per_round"]
        new = self.pool[self.r * per:(self.r + 1) * per]
        tw = time.perf_counter()
        with spans(tag("bench.insert")):
            gids = self.system.insert_batch(new)
        if window:
            log.write_s.append(time.perf_counter() - tw)
            self.insert_s.append(log.write_s[-1])
        log.record_insert(new, gids, window)
        log.next_epoch()
        s = self.steps[self.r]
        self.q = self.q + (mix["step_frac"]
                           * np.linalg.norm(self.q, axis=1, keepdims=True)
                           * s / np.linalg.norm(s, axis=1, keepdims=True))
        self.r += 1

    def warm(self):
        for _ in range(self.mix["warm_rounds"]):
            self._round(False)

    def window(self, seconds: float) -> dict:
        rounds = window_rounds(self.mix, seconds)
        t0 = time.perf_counter()
        for _ in range(rounds):
            self._round(True)
        return {"window_s": time.perf_counter() - t0, "rounds": rounds,
                "delete_ms": harness.summary_ms(self.delete_s),
                "insert_ms": harness.summary_ms(self.insert_s)}

    def replay(self, seconds: float) -> None:
        """The warm-up and the window's rounds, untimed (the control)."""
        self.warm()
        self.window(seconds)
