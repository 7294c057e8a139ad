"""The block-narrowed reference (``p2h_exact_blocked``) gives
``p2h_exact``'s answers: the same rows, and float64 distances to the last
bits of a matrix product, whether a query's candidates come from the
blocks' lists or, where a block's list may be cut short, from the host's
pass over the whole live set."""
import numpy as np
import pytest

import harness

BASE = harness.load_reference({"reference": "p2h_exact"})
BLOCKED = harness.load_reference({"reference": "p2h_exact_blocked"})


@pytest.mark.parametrize("rows, wide", [(256, 64), (256, 4), (4096, 8192)])
def test_blocked_reference_equals_p2h_exact(monkeypatch, rows, wide):
    monkeypatch.setattr(BLOCKED, "ROWS", rows)
    monkeypatch.setattr(BLOCKED, "WIDE", wide)
    rng = np.random.default_rng(3)
    n, d = 1500, 12
    centers = 4.0 * rng.normal(size=(6, d))
    pts = (centers[rng.integers(0, 6, n)]
           + 0.5 * rng.normal(size=(n, d))).astype(np.float32)
    q = rng.normal(size=(40, d + 1)).astype(np.float32)
    q[:, -1] = -np.sum(q[:, :-1] * pts[rng.integers(0, n, 40)], axis=1)
    birth = rng.integers(0, 3, n)
    death = birth + rng.integers(1, 4, n)
    death[:5] = np.iinfo(np.int64).max
    epochs = rng.integers(0, 5, 40)
    want = BASE.exact_topk(pts, birth, death, q, epochs, 10, block=16)
    got = BLOCKED.exact_topk(pts, birth, death, q, epochs, 10, block=16)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-12, atol=1e-12)


def test_blocked_reference_keeps_p2h_exact_scales_and_control():
    for name in ("normalize", "distances", "max_norm1", "tolerance"):
        assert getattr(BLOCKED, name) is getattr(BASE, name)
    import jax.numpy as jnp

    rng = np.random.default_rng(5)
    pts = rng.normal(size=(300, 7)).astype(np.float32)
    live = rng.random(300) < 0.9
    q = rng.normal(size=(13, 8)).astype(np.float32)
    want = BASE.control_topk(jnp.asarray(pts), live, q, 5)
    got = BLOCKED.control_topk(jnp.asarray(pts), live, q, 5)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-6)
