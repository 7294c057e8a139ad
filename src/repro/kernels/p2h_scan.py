"""Fused P2HNNS sweep kernel (the paper's candidate-verification hot spot).

This is the TPU-native BC-sweep of DESIGN.md section 2 over one flat
BC-Tree.  One grid step = one leaf *tile* visited in (per-query-block)
center-preference order:

  * the tile visit order is a **scalar-prefetch** operand, so the BlockSpec
    ``index_map`` gathers the j-th *preferred* leaf's points/cone tables
    directly from HBM (data-dependent block indexing);
  * a running top-k (distances + ids) lives in VMEM scratch and persists
    across the sequential grid dimension -- its row-max is the paper's
    ``q.lambda`` pruning threshold, tightening as tiles are consumed;
  * a whole tile is skipped with ``pl.when`` when the **node-level ball
    bound** (Theorem 2) of every query in the block is >= lambda;
  * inside a live tile, points are pruned with the **point-level ball
    bound** (Corollary 1) and **point-level cone bound** (Theorem 3) before
    the |<x,q>| verification matmul, then at most ``k`` vectorized insert
    passes update the running top-k: only as many as the most candidates
    of one query below its running k-th.

A frozen tree is a one-segment stack, so the sweep runs the stacked
kernel (:func:`repro.kernels.stacked_sweep.stacked_sweep`) with a
segment axis of 1 and a cold global top-k: its thresholds are then
exactly this sweep's.  The pure-jnp oracle with identical semantics is
:func:`repro.kernels.ref.p2h_sweep_ref`.
"""
from __future__ import annotations

from repro.kernels.stacked_sweep import stacked_sweep

__all__ = ["p2h_sweep"]


def p2h_sweep(
    pts_tiles,   # (L, n0, dp) f32
    ids_tiles,   # (L, n0) i32
    rx_tiles,    # (L, n0) f32
    xc_tiles,    # (L, n0) f32
    xs_tiles,    # (L, n0) f32
    leaf_cnorm,  # (L, 1) f32
    queries,     # (B, dp) f32, B % bq == 0
    qnorm,       # (B, 1) f32
    cap,         # (B, 1) f32
    leaf_ip,     # (B, L) f32 -- <q, leaf.c>
    leaf_lb,     # (B, L) f32 -- node-level ball bound
    visit,       # (B // bq, n_visit) i32
    *,
    k: int,
    bq: int = 8,
    use_ball: bool = True,
    use_cone: bool = True,
    interpret: bool | None = None,
):
    """pallas_call wrapper.

    Returns unsorted ``(dists (B,k), ids (B,k), skips (B//bq, 1))`` where
    ``skips`` is the number of tiles whose DMA'd block was skipped
    *block-granularly* (node-level ball bound >= lambda for every query in
    the block -- the ``pl.when`` elision in the kernel).
    """
    tiles = (pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm)
    d, i, s, _ = stacked_sweep(
        *(a[None] for a in tiles), queries, qnorm, cap, leaf_ip[None],
        leaf_lb[None], visit[None], k=k, bq=bq, use_ball=use_ball,
        use_cone=use_cone, interpret=interpret)
    return d[0], i[0], s[0]
