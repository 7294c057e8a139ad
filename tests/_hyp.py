"""Optional-``hypothesis`` shim for the test suite.

``hypothesis`` is a *declared* test dependency (see requirements-dev.txt /
the ``dev`` extra), but the suite must degrade gracefully when it is not
installed: property-based tests are skipped, everything else runs.  Test
modules import from here instead of importing ``hypothesis`` directly:

    from _hyp import HAVE_HYPOTHESIS, hypothesis, st, hnp

and define ``@hypothesis.given(...)`` tests inside ``if HAVE_HYPOTHESIS:``
blocks (the decorators need the real library at definition time).  Where a
property matters for correctness coverage, a deterministic seeded fallback
test should exist alongside (see tests/test_parity.py).
"""
import pytest

try:
    # all three or nothing: guarded tests use hnp inside their
    # `if HAVE_HYPOTHESIS:` blocks, so a partial install (hypothesis
    # without the numpy extra) must also read as "not available"
    import hypothesis
    import hypothesis.extra.numpy as hnp
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    hypothesis = None
    st = None
    hnp = None
    HAVE_HYPOTHESIS = False

#: module-level guard: ``pytestmark = skip_without_hypothesis`` skips a
#: whole module the way ``pytest.importorskip`` would.
skip_without_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed")


def given_int_seed(*, max_examples: int, hi: int, lo: int = 0,
                   fallback_seeds=(0, 1, 2), examples=()):
    """``@given(st.integers(lo, hi))`` for single-seed property tests.

    With hypothesis installed this is the real property test, and every
    seed in ``examples`` runs on each call besides the drawn ones; without
    it the test degrades to a fixed-seed parametrization so the property
    keeps (reduced) coverage instead of being skipped.
    """

    def deco(fn):
        if HAVE_HYPOTHESIS:
            test = hypothesis.given(st.integers(lo, hi))(fn)
            for seed in examples:
                test = hypothesis.example(seed)(test)
            return hypothesis.settings(max_examples=max_examples,
                                       deadline=None)(test)
        return pytest.mark.parametrize("seed", list(fallback_seeds))(fn)

    return deco
