"""Core of the P2H serving benchmark: cells found by name, seeded data,
host spans, the control system and the record of a run.

Everything a cell needs is a file found by a name, so a new cell adds
files and entries and edits none:

* ``BENCHMARK.json`` (repo root) maps a workload to a configuration and
  a traffic mix;
* ``bench/configs/<config>.json`` is the deployment: shapes, layout,
  guarantees, the limits of the correctness check, and the names of the
  modules below that serve it;
* ``bench/configs/<reference>.py`` is its plain reference;
* ``bench/generators/<generator>.py`` makes its data from the seed;
* ``bench/systems/<system>.py`` builds the program under test;
* ``bench/checks/<check>.py`` is the comparison that decides
  ``correct``;
* ``bench/mixes/<traffic>.json`` holds the mix's parameters, among them
  ``loop``, the name of ``bench/loops/<loop>.py``, the driving loop that
  reads them;
* ``bench/metrics/<metric>.py`` is the reader of one metric.

The program under test is imported from ``src/`` only inside a system
module; data, traffic, the reference and the comparison never touch it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


# ----------------------------------------------------------------------
# files found by name
# ----------------------------------------------------------------------
def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a file under ``bench/`` by path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench: dict | None = None):
    """``(benchmark, workload entry, config, mix)`` for a workload name."""
    bench = bench or load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(ROOT / cfg["file"])
    mix = load_json(BENCH / "mixes" / f"{entry['traffic']}.json")
    return bench, entry, config, mix


@functools.lru_cache(maxsize=None)
def load_named(kind: str, name: str):
    """``bench/<kind>/<name>.py``, imported once."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {name!r} ({path})")
    return load_module(path, f"{kind}.{name}")


def load_reference(config: dict):
    return load_named("configs", config["reference"])


def load_check(config: dict):
    return load_named("checks", config["check"])


def load_loop(mix: dict):
    return load_named("loops", mix["loop"])


def build_system(config: dict, data, seed: int, devices):
    """The program under test, from ``systems/<system>.py``."""
    return load_named("systems", config["system"]).build(
        config, data, seed, devices)


# ----------------------------------------------------------------------
# seeded data
# ----------------------------------------------------------------------
@dataclasses.dataclass
class Data:
    """What a generator (``generators/<name>.py`` ``make``) returns."""

    points: np.ndarray   # (n, d) f32 -- gid i is row i
    dead: np.ndarray     # (m,) gids deleted at set-up
    queries: np.ndarray  # (n_queries, d + 1) raw hyperplanes (w, b)
    pool: np.ndarray     # (n_pool, d) points the mix inserts, in order
    steps: np.ndarray    # (n_steps, d + 1) directions the mix moves along


def seed_key(seed: int):
    """A JAX key from any whole number up to 64 bits."""
    import jax

    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_data(config: dict, mix: dict, seed: int, seconds: float) -> Data:
    """The run's data from the configuration's generator, at the sizes
    the mix's loop asks for."""
    shape = load_loop(mix).shape(mix, seconds)
    return load_named("generators", config["generator"]).make(
        config, seed, **shape)


def summary_ms(samples) -> list:
    """``[p50, p95, largest]`` of ``samples`` (seconds), in ms: a
    diagnostic of a window's host times."""
    if not len(samples):
        return []
    return [float(np.percentile(samples, 50)) * 1e3,
            float(np.percentile(samples, 95)) * 1e3,
            float(np.max(samples)) * 1e3]


def use_program() -> None:
    """Make the program under ``src/`` importable (systems only)."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


# ----------------------------------------------------------------------
# host spans: profiler annotations + per-name host time
# ----------------------------------------------------------------------
class Spans:
    """``with spans("bench.flush"):`` marks a span in the profiler's
    trace (``jax.profiler.TraceAnnotation``) and adds its host time to
    ``seconds[name]``."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads (JAX's
    monitoring events) while ``active``."""

    KEYS = ("/jax/core/compile/backend_compile_duration",
            "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, key, _secs, **_kw):
        if self.active and key in self.KEYS:
            self.count += 1


class GcWatch:
    """Times Python's garbage collector while ``active``: the number of
    full (generation 2) collections and the longest pass of any
    generation, so a host stall in the window can be told apart from
    one of the collector's."""

    def __init__(self):
        self.active = False
        self.full = 0
        self.longest_s = 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if not self.active:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._t0)
            self.full += info["generation"] == 2
            self._t0 = None


# ----------------------------------------------------------------------
# the reference in the program's place (the control)
# ----------------------------------------------------------------------
class ControlSystem:
    """The plain reference in the program's place, in the precision
    below the configuration's (``reference.control_topk``): the control
    the correctness check must fail.  Same interface as a system
    module's; gids are rows.  The store is laid out once for every point
    the mix can insert (the pool, in order), so one compiled search
    serves the whole run."""

    def __init__(self, config: dict, data: Data, seed: int, reference):
        del config, seed
        self.ref = reference
        self.store = np.concatenate([data.points, data.pool])
        self.n = len(data.points)
        self.live = np.zeros(len(self.store), bool)
        self.live[:self.n] = True
        self.live[data.dead] = False
        self._dev = None

    def serve(self, queries, k, submit=None):
        import jax.numpy as jnp

        del submit
        if self._dev is None:
            self._dev = jnp.asarray(self.store)
        d, i = self.ref.control_topk(self._dev, self.live,
                                     np.asarray(queries), k)
        return [(d[r], i[r]) for r in range(len(queries))]

    def delete(self, gid):
        ok = 0 <= gid < self.n and bool(self.live[gid])
        if ok:
            self.live[gid] = False
        return ok

    def close(self):
        pass

    def insert_batch(self, points):
        m = len(points)
        if not np.array_equal(self.store[self.n:self.n + m], points):
            self.store[self.n:self.n + m] = points
            self._dev = None
        gids = np.arange(self.n, self.n + m)
        self.live[gids] = True
        self.n += m
        return gids

# ----------------------------------------------------------------------
# the record of a run: what was asked, answered and written, by epoch
# ----------------------------------------------------------------------
class Log:
    """Queries with the epoch (write round) they were served at, their
    answers and latencies, and every point's birth / death epoch, so the
    reference can rebuild the live set each query was served against."""

    def __init__(self, data: Data):
        self.store = [data.points]
        self.n = len(data.points)
        self.gid_row: dict[int, int] = {}   # only for gids != row
        self.birth = [np.zeros(self.n, np.int64)]
        self.death = np.full(self.n, np.iinfo(np.int64).max, np.int64)
        self.death[data.dead] = 0  # deleted at set-up: never live
        self.epoch = 0
        self.q_raw: list[np.ndarray] = []
        self.q_epoch: list[int] = []
        self.answers: list = []
        self.latency_s: list[float] = []    # window queries only
        self.write_s: list[float] = []      # window write calls only
        self.lateness_s: list[float] = []   # open loop: submit - due
        # window counts: queries and write calls, and those that failed
        self.queries = self.failed_queries = 0
        self.writes = self.failed_writes = 0
        self.bad_gids = 0  # inserts acknowledged with unusable gids

    def row_of(self, gid: int) -> int:
        return self.gid_row.get(gid, gid)

    def record_queries(self, qs, answers, window: bool):
        for q, a in zip(qs, answers):
            self.q_raw.append(np.asarray(q, np.float32))
            self.q_epoch.append(self.epoch)
            self.answers.append(a)
            if window:
                self.queries += 1
                self.failed_queries += a is None

    def record_delete(self, gid: int, ok: bool, window: bool):
        if window:
            self.writes += 1
            self.failed_writes += not ok
        row = self.row_of(int(gid))
        if ok and 0 <= row < len(self.death):
            self.death[row] = min(self.death[row], self.epoch + 1)

    def record_insert(self, points, gids, window: bool):
        self.writes += window
        if len(gids) != len(points) or len(set(gids.tolist())) != len(gids):
            self.failed_writes += window
            self.bad_gids += 1
            return
        start = self.n
        for j, g in enumerate(gids.tolist()):
            if g != start + j:
                self.gid_row[g] = start + j
        self.store.append(np.asarray(points, np.float32))
        self.birth.append(np.full(len(points), self.epoch + 1, np.int64))
        self.death = np.concatenate(
            [self.death, np.full(len(points), np.iinfo(np.int64).max)])
        self.n += len(points)

    def next_epoch(self):
        self.epoch += 1

    def arrays(self):
        """``(points, birth, death)`` over every point ever stored."""
        return (np.concatenate(self.store), np.concatenate(self.birth),
                self.death)
