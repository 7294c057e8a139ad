"""The trace reduction on a synthetic trace, and the xplane reader on a
trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

import trace_reduce as tr

MS = 1_000_000  # ns


def synthetic():
    dev = [  # (name, start, end), ns
        ("fusion.1", 1 * MS, 2 * MS),
        ("run.7", 2 * MS, 5 * MS),
        ("fusion.2", 4 * MS, 6 * MS),       # overlaps the kernel
        ("run.7", 12 * MS, 14 * MS),
        ("copy.3", 19 * MS, 25 * MS),       # runs past the window
    ]
    text = {"run.7": 'run.7 custom_call_target="tpu_custom_call"'}
    spans = [("bench.window", 0, 20 * MS),
             ("bench.flush", 0, 8 * MS),
             ("bench.wait", 8 * MS, 11 * MS),
             ("bench.flush", 11 * MS, 16 * MS)]
    return {"devices": {"/device:TPU:0": dev}, "text": text,
            "spans": spans}


def test_busy_kernel_and_idle_shares():
    r = tr.reduce(synthetic())
    assert r["window_s"] == pytest.approx(0.020)
    # union: [1,6] + [12,14] + [19,20] = 8 ms
    assert r["busy_s"] == pytest.approx(0.008)
    assert tr.matching_seconds(r, "tpu_custom_call") == pytest.approx(0.005)
    assert tr.matching_seconds(r, "no_such_kernel") == 0.0
    # flush spans: 13 ms, of which busy 5 + 2 = 7 ms
    assert r["idle_share_in"]["bench.flush"] == pytest.approx(6 / 13)
    assert r["idle_share_in"]["bench.wait"] == pytest.approx(1.0)
    ops = dict(r["device_ops"])
    assert ops["run.7"] == pytest.approx(0.005)
    assert ops["copy.3"] == pytest.approx(0.001)  # clipped to the window
    assert [n for n, _ in r["device_ops"]][0] == "run.7"


def test_gaps_attributed_to_host_spans():
    gaps = dict(tr.reduce(synthetic())["idle_gaps"])
    # idle [0,1] and [6,8] in flush #1, [8,11] in wait, [11,12] and
    # [14,16] in flush #2, [16,19] in no span
    assert gaps["bench.flush"] == pytest.approx(0.006)
    assert gaps["bench.wait"] == pytest.approx(0.003)
    assert gaps["(no span)"] == pytest.approx(0.003)
    assert sum(gaps.values()) == pytest.approx(0.020 - 0.008)


def test_devices_are_averaged_and_no_device_reads_none():
    t = synthetic()
    t["devices"]["/device:TPU:1"] = [("fusion.9", 0, 20 * MS)]
    r = tr.reduce(t)
    assert r["busy_s"] == pytest.approx((0.008 + 0.020) / 2)
    assert tr.matching_seconds(r, "tpu_custom_call") == pytest.approx(
        0.005 / 2)
    assert tr.reduce({"devices": {}, "spans": t["spans"]}) is None
    assert tr.reduce({"devices": t["devices"], "spans": []}) is None


def test_reads_host_spans_from_a_recorded_xplane(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.flush"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path is not None
    t = tr.load(path)
    names = [n for n, _, _ in t["spans"]]
    assert names.count("bench.flush") == 2
    assert names.count(tr.WINDOW_SPAN) == 1
    win = next(s for s in t["spans"] if s[0] == tr.WINDOW_SPAN)
    assert all(win[1] <= s <= e <= win[2] for n, s, e in t["spans"])
    # the CPU has no /device:TPU plane: nothing for the device numbers
    assert t["devices"] == {} and tr.reduce(t) is None
