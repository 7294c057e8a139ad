"""Pallas TPU kernels for the paper's compute hot spot: the fused
tile-sweep candidate-verification scan (|QX^T| + bound pruning + running
top-k).  ``ops`` holds the jit'd public wrappers, ``ref`` the pure-jnp
oracles, ``stacked_sweep`` the pl.pallas_call kernel (N stacked leaf
tile-sets swept by one launch under a single entry cap -- the
device-side form of the mutable index's segment fan-out and the
two-round exchange's round 2), and ``p2h_scan`` the one-tree sweep on
that kernel.
"""
from repro.kernels import ops, ref, stacked_sweep  # noqa: F401
from repro.kernels.ops import sweep_search_pallas  # noqa: F401
from repro.kernels.stacked_sweep import (  # noqa: F401
    StackedLeaves, stacked_sweep_query, stacked_sweep_search)

__all__ = ["ops", "ref", "stacked_sweep", "sweep_search_pallas",
           "StackedLeaves", "stacked_sweep_query", "stacked_sweep_search"]
