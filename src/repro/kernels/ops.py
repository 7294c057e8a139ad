"""jit'd wrappers around the Pallas P2H sweep kernel.

``sweep_search_pallas`` is a drop-in alternative backend for
:func:`repro.core.search.sweep_search` (exposed as ``method="pallas"`` on
:class:`repro.core.api.P2HIndex`):

  1. pad ``d`` to a lane multiple (zero columns leave inner products
     unchanged) and the query batch to a block multiple (replicating the
     last query; replicas are dropped on return);
  2. phase 1 (one matmul): ``<q, leaf.c>`` for all leaves -> node-level
     ball bounds and the per-query-block center-preference visit order
     (block preference = min over the block's |<q,c>|, so every query in
     the block agrees the first tiles are promising);
  3. phase 2: the fused Pallas sweep (:mod:`repro.kernels.p2h_scan`).

On CPU (this container) the kernel runs with ``interpret=True``; on TPU it
compiles to Mosaic.  Stats counters follow the convention of
``repro.core.search`` where derivable without re-running the sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bounds
from repro.core.balltree import FlatTree
from repro.kernels import p2h_scan, ref

__all__ = ["sweep_search_pallas", "prepare_operands"]

_LANE = 128


def _pad_cols(a, dp):
    return jnp.pad(a, ((0, 0), (0, dp - a.shape[1])))


def prepare_operands(tree: FlatTree, queries, *, frac=1.0, bq=8, lambda_cap=None):
    """Shared phase-1 prep for the kernel and its reference oracle."""
    L, n0, d = tree.num_leaves, tree.n0, tree.d
    dp = -(-d // _LANE) * _LANE
    B0 = queries.shape[0]
    Bp = -(-B0 // bq) * bq
    q = jnp.asarray(queries, jnp.float32)
    if Bp != B0:  # replicate the last query (results discarded on return)
        q = jnp.concatenate([q, jnp.broadcast_to(q[-1:], (Bp - B0, d))], axis=0)
    qn = jnp.sqrt(jnp.sum(q * q, axis=1, keepdims=True))  # (Bp, 1)
    cap = (jnp.full((Bp, 1), jnp.inf, jnp.float32) if lambda_cap is None
           else jnp.pad(jnp.asarray(lambda_cap, jnp.float32).reshape(B0, 1),
                        ((0, Bp - B0), (0, 0)), constant_values=jnp.inf))

    ipc = jnp.dot(q, tree.leaf_centers.T,
                  precision=bounds.EXACT)  # (Bp, L)
    lb = bounds.node_ball_bound(ipc, qn, tree.leaf_radii[None, :])
    # per-query-block center preference: a tile is as promising as its most
    # interested query in the block
    pref = jnp.min(jnp.abs(ipc).reshape(Bp // bq, bq, L), axis=1)  # (nqb, L)
    visit = jnp.argsort(pref, axis=1).astype(jnp.int32)
    n_visit = max(1, min(L, int(round(frac * L))))
    visit = visit[:, :n_visit]

    ops = dict(
        pts_tiles=_pad_cols(tree.points, dp).reshape(L, n0, dp),
        ids_tiles=tree.point_ids.reshape(L, n0),
        rx_tiles=tree.rx.reshape(L, n0),
        xc_tiles=tree.xcos.reshape(L, n0),
        xs_tiles=tree.xsin.reshape(L, n0),
        leaf_cnorm=tree.leaf_cnorm.reshape(L, 1),
        queries=_pad_cols(q, dp),
        qnorm=qn,
        cap=cap,
        leaf_ip=ipc,
        leaf_lb=lb,
        visit=visit,
    )
    return ops, B0


@functools.partial(
    jax.jit,
    static_argnames=("k", "frac", "bq", "use_ball", "use_cone", "use_ref",
                     "interpret"),
)
def _run(tree: FlatTree, queries, lambda_cap, *, k, frac, bq, use_ball,
         use_cone, use_ref, interpret):
    ops, B0 = prepare_operands(
        tree, queries, frac=frac, bq=bq, lambda_cap=lambda_cap)
    fn = ref.p2h_sweep_ref if use_ref else functools.partial(
        p2h_scan.p2h_sweep, interpret=interpret)
    bd, bi, skips = fn(**ops, k=k, bq=bq, use_ball=use_ball,
                       use_cone=use_cone)
    order = jnp.argsort(bd, axis=1)  # kernel's top-k is unsorted
    bd = jnp.take_along_axis(bd, order, axis=1)[:B0]
    bi = jnp.take_along_axis(bi, order, axis=1)[:B0]
    # counters follow repro.core.search conventions where derivable.  Tile
    # skips/visits are *block-granular* here (one count per query block,
    # matching the kernel's pl.when DMA elision), not per query.
    n_visit = ops["visit"].shape[0] * ops["visit"].shape[1]
    nskip = jnp.sum(skips).astype(jnp.int32)
    counters = (jnp.zeros((8,), jnp.int32)
                .at[3].set(queries.shape[0] * tree.num_leaves)
                .at[2].set(jnp.int32(n_visit) - nskip)
                .at[7].set(nskip))
    return bd, bi, counters


def sweep_search_pallas(tree: FlatTree, queries, k: int = 1, *, frac: float = 1.0,
                        bq: int = 8, use_ball: bool = True, use_cone: bool = True,
                        lambda_cap=None, use_ref: bool = False,
                        interpret: bool | None = None):
    """Exact (frac=1) / budgeted P2HNNS via the fused Pallas sweep kernel."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _run(tree, jnp.atleast_2d(queries), lambda_cap, k=k, frac=frac,
                bq=bq, use_ball=use_ball, use_cone=use_cone, use_ref=use_ref,
                interpret=interpret)
