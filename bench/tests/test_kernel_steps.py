"""The reader of the stacked sweep kernel's step counters on a stub
context: nothing without the counters, the share of the loop with them."""
import types

import pytest

import harness
import kernel_steps


def ctx(counters=None):
    stats = {} if counters is None else {"span_counters": counters}
    return types.SimpleNamespace(stats=stats)


@pytest.mark.parametrize("counters", [
    None, {}, {"delta_upload_bytes": 5},
    {"stacked_scan_steps": 0, "stacked_insert_steps": 0},
    {"stacked_scan_steps": 40}])
def test_reads_nothing_without_the_counters(counters):
    for cell in ("music100.serve", "sun397.al"):
        assert kernel_steps.topk_insert_share(ctx(counters), cell) is None


@pytest.mark.parametrize("cell", ["music100.serve", "sun397.al"])
def test_share_of_the_fixed_loop(cell):
    k = harness.load_cell(cell)[3]["k"]
    c = ctx({"stacked_scan_steps": 400, "stacked_insert_steps": 3 * k})
    assert kernel_steps.topk_insert_share(c, cell) == pytest.approx(0.75)
    full = ctx({"stacked_scan_steps": 7, "stacked_insert_steps": 7 * k})
    assert kernel_steps.topk_insert_share(full, cell) == pytest.approx(100)
    name = "topk_insert_share." + cell.split(".")[1]
    assert harness.load_named("metrics", name).read(c) == pytest.approx(0.75)
