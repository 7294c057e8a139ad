"""The exchange-layer readers on stub contexts: what they read from the
program's spans, counters and trace, and that a program without them
reads nothing and raises nothing."""
import types

import pytest

import exchange
import harness
import kernel_cost

GIST = harness.load_json(harness.BENCH / "configs" / "gist.json")
PEAK = harness.load_json(harness.BENCH / "peaks.json")["devices"][
    "TPU v5 lite"]


def ctx(stats=None, trace=None, layout=None):
    return types.SimpleNamespace(
        config=GIST, peak=PEAK, cost=kernel_cost, stats=stats or {},
        trace=trace, layout=layout or {"tiles": 0, "compactions": 0})


def test_spans_and_counters():
    c = ctx({"batches": 4, "span_counters": {
        "exchange_upload_bytes": 4 * 882_700_000},
        "spans": {"p2h.exchange.round1": {"count": 4, "p50_ms": 61.5},
                  "p2h.exchange.round2": {"count": 4, "p50_ms": 9.25}}})
    assert exchange.span_p50_ms(c, "p2h.exchange.round1") == 61.5
    assert exchange.span_p50_ms(c, "p2h.exchange.round2") == 9.25
    assert exchange.upload_mb_per_batch(c) == pytest.approx(882.7)


def test_a_program_without_the_exchange_reads_nothing():
    c = ctx({"batches": 4, "spans": {}, "span_counters": {}})
    assert exchange.span_p50_ms(c, "p2h.exchange.round1") is None
    assert exchange.upload_mb_per_batch(c) is None
    assert exchange.mesh_roofline_pct(c) is None


@pytest.mark.parametrize("round1_leaves", [0, 3_000_000])
def test_mesh_roofline_charges_each_chip_its_share(round1_leaves):
    """The scanned steps are the kernel's own count: round 1's beam
    leaves, which the engine's ``stacked`` counters also hold, leave the
    share as it is."""
    tiles, batches = 11_600, 10
    steps = batches * tiles  # one query block a batch
    kernel_s = 2 * steps / 4 * kernel_cost.main_tile_bytes(128, 960) \
        / PEAK["hbm_bytes_per_s"]
    trace = {"op_seconds": {"k": kernel_s}, "op_text": {"k": "pallas_call"}}
    c = ctx({"routes": {"stacked": batches},
             "counters": {"stacked": {
                 "leaves_scanned": steps + round1_leaves}},
             "span_counters": {"stacked_scan_steps": steps}},
            trace=trace, layout={"tiles": tiles, "compactions": 0})
    assert exchange.mesh_roofline_pct(c) == pytest.approx(50.0)


def test_mesh_roofline_reads_nothing_without_the_kernel_count():
    trace = {"op_seconds": {"k": 1.0}, "op_text": {"k": "pallas_call"}}
    c = ctx({"routes": {"stacked": 10},
             "counters": {"stacked": {"leaves_scanned": 10}}},
            trace=trace, layout={"tiles": 100, "compactions": 0})
    assert exchange.mesh_roofline_pct(c) is None
