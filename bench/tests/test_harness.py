"""The benchmark's reference, both mixes, the control and the faults,
at a tiny size on the CPU, driven through the engine by the code path a
chip run takes (``run.run_cell``), with the stacked kernel in interpret
mode."""
import copy
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import control
import harness
import run

TINY = {"music100.serve": {"n": 3000, "d": 16, "segment_rows": 700,
                           "delta_capacity": 700},
        "sun397.al": {"n": 6000, "d": 24, "segment_rows": 1400,
                      "delta_capacity": 1400}}
SEED = 2 ** 31 + 12345  # larger than 32 signed bits hold


def tiny_cell(workload):
    bench, entry, config, mix = harness.load_cell(workload)
    config = dict(config, **TINY[workload])
    if mix["loop"] == "open":
        mix = dict(mix, rate_per_s=min(mix["rate_per_s"], 40.0))
    else:  # as at full size, no compaction in the window
        mix = dict(mix, rounds_per_s=4)
    return bench, entry, config, mix


def run_tiny(workload, *, trace=False, tmp_path=None, seconds=1.0):
    bench, entry, config, mix = tiny_cell(workload)
    peak = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    return run.run_cell(bench, entry, config, mix, seed=SEED,
                        seconds=seconds, trace=trace,
                        peak=peak["TPU v5 lite"], devices=jax.devices(),
                        t0=0.0, trace_dir=tmp_path,
                        say=lambda *a: None)


def test_benchmark_files_are_found_by_name():
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        _, entry, config, mix = harness.load_cell(w["name"], bench)
        loop = harness.load_loop(mix)
        assert callable(loop.shape) and callable(loop.Driver)
        assert callable(harness.load_named(
            "generators", config["generator"]).make)
        assert callable(harness.load_named("systems", config["system"]).build)
        assert set(config["limits"]) == set(harness.load_check(config).NAMES)
        assert callable(harness.load_reference(config).exact_topk)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.load_named("metrics", m["name"]).read)
    with pytest.raises(FileNotFoundError):
        harness.load_named("loops", "no_such_loop")


def test_reference_is_exact_top_k_over_the_live_set():
    ref = harness.load_reference({"reference": "p2h_exact"})
    rng = np.random.default_rng(0)
    pts = rng.normal(size=(700, 9)).astype(np.float32)
    q = rng.normal(size=(40, 10)).astype(np.float32)
    birth = rng.integers(0, 3, 700)
    death = birth + rng.integers(1, 4, 700)
    epochs = rng.integers(0, 5, 40)
    d, rows = ref.exact_topk(pts, birth, death, q, epochs, 7, block=16)
    x1 = np.concatenate([pts, np.ones((700, 1))], 1).astype(np.float64)
    for b in range(40):
        q64 = q[b].astype(np.float64)
        qn = q64 / np.linalg.norm(q64[:-1])
        dist = np.abs(x1 @ qn)
        live = np.nonzero((birth <= epochs[b]) & (death > epochs[b]))[0]
        order = live[np.lexsort((live, dist[live]))][:7]
        np.testing.assert_array_equal(rows[b, :len(order)], order)
        np.testing.assert_allclose(d[b, :len(order)], dist[order],
                                   rtol=1e-12)
        assert np.all(rows[b, len(order):] == -1)


@pytest.mark.parametrize("workload", sorted(TINY))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct_through_the_kernel(workload, trace,
                                              interpret_kernel, tmp_path):
    res = run_tiny(workload, trace=trace, tmp_path=tmp_path / "trace")
    assert interpret_kernel, "the stacked kernel was never launched"
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    want = bench["per_layer"] if trace else bench["end_to_end"]
    for m in want:
        if run.applies(m, workload) and m["source"] != "device_trace":
            assert m["name"] in res["metrics"], m["name"]
    if not trace:
        assert res["metrics"]["setup_s"]["value"] > 0
    json.dumps(res)


@pytest.mark.parametrize("workload", sorted(TINY))
def test_control_comes_out_not_correct(workload):
    _, _, config, mix = tiny_cell(workload)
    out = control.control_readings(config, mix, SEED, 1.0)
    assert not out["correct"], out


def _alter_answers(monkeypatch, how):
    from repro.stream import snapshot

    orig = snapshot.Snapshot.query

    def query(self, *a, **kw):
        out = list(orig(self, *a, **kw))
        bd, bi = np.array(out[0]), np.array(out[1])
        if how == "answer":      # one id altered where it is produced
            bi[0, 0] = bi[0, -1]
        else:                    # half of the batch left out
            h = len(bi) // 2
            bd[h:], bi[h:] = bd[0], bi[0]
        out[0], out[1] = bd, bi
        return tuple(out)

    monkeypatch.setattr(snapshot.Snapshot, "query", query)


def _drop_writes(monkeypatch, what):
    from repro.stream import mutable

    if what == "delete":  # acknowledged, not applied
        monkeypatch.setattr(mutable.MutableP2HIndex, "delete",
                            lambda self, gid, commit=True: True)
    else:
        def insert_batch(self, points, gids=None):
            n = len(points)
            out = np.arange(self._next_gid, self._next_gid + n)
            self._next_gid += n
            return out.astype(np.int32)

        monkeypatch.setattr(mutable.MutableP2HIndex, "insert_batch",
                            insert_batch)


FAULTS = [("music100.serve", "answer"), ("music100.serve", "half_batch"),
          ("sun397.al", "answer"), ("sun397.al", "half_batch"),
          ("sun397.al", "delete"), ("sun397.al", "insert")]


@pytest.mark.parametrize("workload,fault", FAULTS)
def test_fault_in_the_timed_path_comes_out_not_correct(workload, fault,
                                                       monkeypatch):
    # the set-up builds the index first; the fault is planted under the
    # window's calls by patching after the system exists
    def faulty(config, data, seed, devices):
        system = harness.build_system(config, data, seed, devices)
        if fault in ("answer", "half_batch"):
            _alter_answers(monkeypatch, fault)
        else:
            _drop_writes(monkeypatch, fault)
        return system

    bench, entry, config, mix = tiny_cell(workload)
    peak = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    res = run.run_cell(bench, entry, config, mix, seed=SEED, seconds=1.0,
                       trace=False, peak=peak["TPU v5 lite"],
                       devices=jax.devices(), t0=0.0,
                       system_factory=faulty, say=lambda *a: None)
    assert not res["correct"], res["checks"]


class _InstantSystem:
    """Answers every hyperplane with no point and acknowledges writes."""

    def __init__(self):
        self.next = 10 ** 6

    def serve(self, queries, k, submit=None):
        return [(np.full(k, np.inf), np.full(k, -1)) for _ in queries]

    def delete(self, gid):
        return True

    def insert_batch(self, points):
        self.next += len(points)
        return np.arange(self.next - len(points), self.next)


@pytest.mark.parametrize("seconds", [0.5, 2.0])
def test_rounds_window_is_a_fixed_amount_of_work(seconds):
    _, _, config, mix = tiny_cell("sun397.al")
    loop = harness.load_loop(mix)
    data = harness.make_data(config, mix, SEED, seconds)
    log = harness.Log(data)
    drv = loop.Driver(_InstantSystem(), mix, data, log, harness.Spans(),
                      slot=8, seed=SEED)
    drv.warm()
    info = drv.window(seconds)
    want = round(mix["rounds_per_s"] * seconds)
    assert info["rounds"] == want and info["window_s"] < seconds
    assert log.queries == want * mix["classes"]
    assert len(data.pool) == (want + mix["warm_rounds"]) \
        * mix["inserts_per_round"]
