"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``
from the repository root (``JAX_PLATFORMS=cpu``)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


@pytest.fixture
def interpret_kernel(monkeypatch):
    """Route the stacked sweep through the Pallas kernel in interpret
    mode (off-TPU the program would take its jnp twin); yields the count
    of launch decisions that took it."""
    from repro.kernels import stacked_sweep

    calls = []

    def forced(use_kernel, interpret):
        calls.append(1)
        return True, True

    monkeypatch.setattr(stacked_sweep, "resolve_stacked_backend", forced)
    return calls
