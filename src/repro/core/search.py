"""P2HNNS search schemes over :class:`~repro.core.balltree.FlatTree`.

Three schedules, one semantics (see DESIGN.md section 2):

``dfs_search``
    Paper-faithful branch-and-bound (Algorithms 3 & 5): depth-first with an
    explicit stack inside ``lax.while_loop``, node-level ball bound pruning,
    center/lower-bound branch preference, collaborative inner-product
    computing (Lemma 2), and point-level ball+cone pruning in leaves.
    Exact.  Best for single-query latency (the paper's measurement mode).

``sweep_search``
    TPU-native reformulation: node bounds for *all* leaves via one matmul,
    leaves visited in preference order while a running top-k threshold
    (lambda) prunes whole tiles and individual points.  Exact at
    ``frac=1.0``; ``frac<1`` gives the paper's candidate-fraction
    time/recall knob (this is ``beam_search``).  The Pallas kernel in
    ``repro.kernels`` implements the same schedule with real tile skipping;
    this module is the jnp reference/CPU path.

Counter conventions (returned stats, summed over the query batch):
  nodes_visited, nodes_pruned, leaves_scanned, ip_ops (O(d) center inner
  products -- Theorem 5's C_N), ball_pruned, cone_pruned, verified
  (candidates whose |<x,q>| was actually computed and compared).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import bounds
from repro.core.balltree import FlatTree

__all__ = ["dfs_search", "sweep_search", "beam_search", "merge_topk",
           "merge_topk_planes", "SearchStats"]

# counter indices
C_NODES, C_PRUNED, C_LEAVES, C_IP, C_BALL, C_CONE, C_VERIFIED, C_TILE_SKIP = range(8)
_COUNTER_NAMES = (
    "nodes_visited",
    "nodes_pruned",
    "leaves_scanned",
    "ip_ops",
    "ball_pruned",
    "cone_pruned",
    "verified",
    "tiles_skipped",
)


def SearchStats(counters) -> dict:
    c = jax.device_get(counters)
    return {k: int(v) for k, v in zip(_COUNTER_NAMES, c)}


def merge_topk(dists, ids, k: int):
    """Merge per-source candidate lists into a global top-k, de-duplicated
    by id.

    ``dists``/``ids`` are (B, M) -- the concatenation of any number of
    (B, k_i) partial top-k lists (invalid slots: id -1, dist +inf).  Rows
    are sorted by (id primary, dist secondary) so repeats of the same id
    keep only their smallest distance; the repeats are masked to +inf and
    a plain top-k finishes the merge.  This is the merge step of the
    sharded two-round exchange (``repro.core.distributed``), shared with
    the streaming index's segment fan-out (``repro.stream``).
    """
    B = dists.shape[0]
    order = jnp.lexsort((dists, ids), axis=1)
    md = jnp.take_along_axis(dists, order, axis=1)
    mi = jnp.take_along_axis(ids, order, axis=1)
    dup = jnp.concatenate(
        [jnp.zeros((B, 1), bool), mi[:, 1:] == mi[:, :-1]], axis=1
    )
    md = jnp.where(dup, jnp.inf, md)
    neg, arg = jax.lax.top_k(-md, k)
    return -neg, jnp.take_along_axis(mi, arg, axis=1)


def merge_topk_planes(dists, ids, k: int, extra_d=None, extra_i=None):
    """Cross-source :func:`merge_topk` over stacked per-source planes.

    ``dists``/``ids`` are ``(N, B, k_s)`` -- one partial top-k plane per
    source (a segment of the stacked sweep, a shard of the exchange) --
    flattened to ``(B, N * k_s)`` and merged with :func:`merge_topk`'s
    id-primary dedup/tie convention.  ``extra_d``/``extra_i`` (optional,
    ``(B, M)``) append one more candidate list (e.g. the delta scan's
    top-k) to the same merge.  Pure jnp, so it runs *inside* the stacked
    sweep's device program (the in-launch global merge) and on the host
    exchange path alike -- both share this one function, keeping the two
    merge sites bit-identical.
    """
    N, B, ks = dists.shape
    md = jnp.moveaxis(jnp.asarray(dists), 0, 1).reshape(B, N * ks)
    mi = jnp.moveaxis(jnp.asarray(ids), 0, 1).reshape(B, N * ks)
    if extra_d is not None:
        md = jnp.concatenate([md, jnp.asarray(extra_d)], axis=1)
        mi = jnp.concatenate([mi, jnp.asarray(extra_i)], axis=1)
    return merge_topk(md, mi, k)


# ======================================================================
# Exact DFS (paper Algorithms 3 / 5)
# ======================================================================


def _dfs_one(
    tree: FlatTree,
    q,
    cap,
    *,
    k: int,
    branch: str,
    use_collab: bool,
    use_ball: bool,
    use_cone: bool,
    max_candidates,
):
    n0, d = tree.n0, tree.d
    qn = jnp.sqrt(jnp.sum(q * q))
    stack_size = tree.max_depth + 3

    ip_root = jnp.dot(tree.centers[0], q, precision=bounds.EXACT)
    stack_n = jnp.zeros((stack_size,), jnp.int32)
    stack_ip = jnp.zeros((stack_size,), q.dtype).at[0].set(ip_root)
    best_d = jnp.full((k,), jnp.inf, q.dtype)
    best_i = jnp.full((k,), -1, jnp.int32)
    counters = jnp.zeros((8,), jnp.int32).at[C_IP].set(1)

    def _leaf(args):
        node, ip, lam, bd, bi, cnt = args
        slot = jnp.maximum(tree.node_leaf[node], 0)
        base = slot * n0
        blk = jax.lax.dynamic_slice(tree.points, (base, 0), (n0, d))
        ids = jax.lax.dynamic_slice(tree.point_ids, (base,), (n0,))
        valid = ids >= 0
        keep = valid
        if use_ball:
            rxs = jax.lax.dynamic_slice(tree.rx, (base,), (n0,))
            pb = bounds.point_ball_bound(ip, qn, rxs)
            ball_ok = pb < lam
            cnt = cnt.at[C_BALL].add(jnp.sum(valid & ~ball_ok).astype(jnp.int32))
            keep &= ball_ok
        if use_cone:
            xc = jax.lax.dynamic_slice(tree.xcos, (base,), (n0,))
            xs = jax.lax.dynamic_slice(tree.xsin, (base,), (n0,))
            qcos, qsin = bounds.query_angle_terms(ip, qn, tree.leaf_cnorm[slot])
            cb = bounds.point_cone_bound(qcos, qsin, xc, xs)
            cone_ok = cb < lam
            cnt = cnt.at[C_CONE].add(jnp.sum(keep & ~cone_ok).astype(jnp.int32))
            keep &= cone_ok
        absip = jnp.abs(jnp.dot(blk, q, precision=bounds.EXACT))
        cand = jnp.where(keep, absip, jnp.inf)
        cnt = cnt.at[C_VERIFIED].add(jnp.sum(keep).astype(jnp.int32))
        cnt = cnt.at[C_LEAVES].add(1)
        md = jnp.concatenate([bd, cand])
        mi = jnp.concatenate([bi, ids])
        neg, arg = jax.lax.top_k(-md, k)
        return -neg, jnp.take(mi, arg), cnt

    def _internal(args):
        node, ip, sp, sn, sip, cnt = args
        lc, rc = tree.left[node], tree.right[node]
        ip_lc = jnp.dot(tree.centers[lc], q, precision=bounds.EXACT)
        if use_collab:  # Lemma 2
            cN = tree.counts[node].astype(q.dtype)
            cL = tree.counts[lc].astype(q.dtype)
            cR = tree.counts[rc].astype(q.dtype)
            ip_rc = (cN * ip - cL * ip_lc) / cR
            cnt = cnt.at[C_IP].add(1)
        else:
            ip_rc = jnp.dot(tree.centers[rc], q, precision=bounds.EXACT)
            cnt = cnt.at[C_IP].add(2)
        if branch == "center":  # paper's default (Section III-C)
            left_first = jnp.abs(ip_lc) < jnp.abs(ip_rc)
        else:  # lower-bound preference (Fig. 7 ablation)
            lb_lc = bounds.node_ball_bound(ip_lc, qn, tree.radii[lc])
            lb_rc = bounds.node_ball_bound(ip_rc, qn, tree.radii[rc])
            left_first = lb_lc < lb_rc
        first_n = jnp.where(left_first, lc, rc)
        first_ip = jnp.where(left_first, ip_lc, ip_rc)
        sec_n = jnp.where(left_first, rc, lc)
        sec_ip = jnp.where(left_first, ip_rc, ip_lc)
        sn = sn.at[sp].set(sec_n).at[sp + 1].set(first_n)
        sip = sip.at[sp].set(sec_ip).at[sp + 1].set(first_ip)
        return sp + 2, sn, sip, cnt

    def cond(st):
        sp = st[0]
        ok = sp > 0
        if max_candidates is not None:
            ok &= st[5][C_VERIFIED] < max_candidates
        return ok

    def body(st):
        sp, sn, sip, bd, bi, cnt = st
        sp = sp - 1
        node, ip = sn[sp], sip[sp]
        lam = jnp.minimum(bd[k - 1], cap)
        lb = bounds.node_ball_bound(ip, qn, tree.radii[node])
        pruned = lb >= lam
        is_leaf = tree.left[node] < 0
        cnt = cnt.at[C_NODES].add(1)
        cnt = cnt.at[C_PRUNED].add(pruned.astype(jnp.int32))

        bd, bi, cnt = jax.lax.cond(
            is_leaf & ~pruned,
            _leaf,
            lambda a: (a[3], a[4], a[5]),
            (node, ip, lam, bd, bi, cnt),
        )
        sp, sn, sip, cnt = jax.lax.cond(
            (~is_leaf) & ~pruned,
            _internal,
            lambda a: (a[2], a[3], a[4], a[5]),
            (node, ip, sp, sn, sip, cnt),
        )
        return sp, sn, sip, bd, bi, cnt

    st = (jnp.int32(1), stack_n, stack_ip, best_d, best_i, counters)
    st = jax.lax.while_loop(cond, body, st)
    return st[3], st[4], st[5]


@functools.partial(
    jax.jit,
    static_argnames=(
        "k",
        "branch",
        "use_collab",
        "use_ball",
        "use_cone",
        "max_candidates",
    ),
)
def dfs_search(
    tree: FlatTree,
    queries,
    k: int = 1,
    *,
    branch: str = "center",
    use_collab: bool = True,
    use_ball: bool = True,
    use_cone: bool = True,
    max_candidates: int | None = None,
    lambda_cap=None,
):
    """Exact top-k P2HNNS via paper-faithful branch-and-bound.

    ``use_ball=use_cone=False`` gives the plain Ball-Tree of Algorithm 3;
    the defaults give BC-Tree (Algorithm 5).  Returns
    ``(dists (B,k), ids (B,k), counters (8,))``.

    ``lambda_cap`` (optional, (B,)): externally-known upper bound on each
    query's true global k-th distance (the same hook ``sweep_search``
    exposes, used by the serving engine's lambda cache and the distributed
    exchange).  Exact for any valid cap: pruning with ``min(running-kth,
    cap)`` only ever discards candidates whose lower bound exceeds an
    upper bound on the global k-th distance.
    """
    fn = functools.partial(
        _dfs_one,
        tree,
        k=k,
        branch=branch,
        use_collab=use_collab,
        use_ball=use_ball,
        use_cone=use_cone,
        max_candidates=max_candidates,
    )
    if lambda_cap is None:
        caps = jnp.full((queries.shape[0],), jnp.inf, queries.dtype)
    else:
        caps = jnp.asarray(lambda_cap, queries.dtype).reshape(-1)
    bd, bi, cnt = jax.vmap(fn)(queries, caps)
    return bd, bi, jnp.sum(cnt, axis=0)


# ======================================================================
# TPU-native sweep (jnp reference path; Pallas kernel in repro.kernels)
# ======================================================================


@functools.partial(
    jax.jit,
    static_argnames=("k", "order", "frac", "use_ball", "use_cone", "prefetch"),
)
def sweep_search(
    tree: FlatTree,
    queries,
    k: int = 1,
    *,
    order: str = "center",
    frac: float = 1.0,
    use_ball: bool = True,
    use_cone: bool = True,
    prefetch: int = 1,
    lambda_cap=None,
):
    """Exact (frac=1.0) or budgeted (frac<1) sweep search.

    Phase 1: node-level bounds for all leaves in one (B, L) matmul.
    Phase 2: visit leaves in preference order with a running per-query
    top-k threshold; tiles whose node bound >= lambda are skipped, points
    are pruned with the point-level ball+cone bounds.

    ``order="center"`` visits by ascending |<q, leaf.c>| (paper's center
    preference); ``order="bound"`` by ascending node bound (lower-bound
    preference, Fig. 7 ablation).

    ``lambda_cap`` (optional, (B,)): an externally-known upper bound on the
    true global k-th distance; pruning additionally uses it.  Used by the
    distributed two-round lambda-exchange (see ``repro.core.distributed``):
    exact because any candidate with lower bound >= cap >= global-kth can
    never enter the global top-k.
    """
    del prefetch  # reserved for the pallas backend
    B = queries.shape[0]
    L, n0, d = tree.num_leaves, tree.n0, tree.d
    dtype = queries.dtype
    qn = jnp.sqrt(jnp.sum(queries * queries, axis=1))  # (B,)
    ipc = jnp.dot(queries, tree.leaf_centers.T,
                  precision=bounds.EXACT)  # (B, L)
    lb_all = bounds.node_ball_bound(ipc, qn[:, None], tree.leaf_radii[None, :])
    # tiles with no valid point (pad_tree_leaves quantization pads,
    # fully-tombstoned tiles): force their bound to +inf so they sort
    # after every live tile (a budgeted sweep never spends visit slots
    # on them) and are unconditionally skipped by the lambda test
    tile_dead = ~(tree.point_ids.reshape(L, n0) >= 0).any(axis=1)  # (L,)
    lb_all = jnp.where(tile_dead[None, :], jnp.inf, lb_all)
    if order == "center":
        visit = jnp.argsort(
            jnp.where(tile_dead[None, :], jnp.inf, jnp.abs(ipc)), axis=1)
    else:
        visit = jnp.lexsort((jnp.abs(ipc), lb_all), axis=1)
    n_visit = max(1, min(L, int(round(frac * L))))
    visit = visit[:, :n_visit]  # (B, n_visit)

    pts = tree.points.reshape(L, n0, d)
    ids = tree.point_ids.reshape(L, n0)
    rx = tree.rx.reshape(L, n0)
    xcs = tree.xcos.reshape(L, n0)
    xsn = tree.xsin.reshape(L, n0)

    def step(carry, leaf):
        bd, bi, cnt = carry  # (B,k), (B,k), (8,)
        lam = bd[:, k - 1]  # (B,)
        if lambda_cap is not None:
            lam = jnp.minimum(lam, lambda_cap)
        lbt = jnp.take_along_axis(lb_all, leaf[:, None], axis=1)[:, 0]
        ipct = jnp.take_along_axis(ipc, leaf[:, None], axis=1)[:, 0]
        skip = lbt >= lam
        blk = pts[leaf]  # (B, n0, d)
        idst = ids[leaf]  # (B, n0)
        valid = idst >= 0
        keep = valid
        if use_ball:
            pb = bounds.point_ball_bound(ipct[:, None], qn[:, None], rx[leaf])
            ball_ok = pb < lam[:, None]
            cnt = cnt.at[C_BALL].add(
                jnp.sum((valid & ~ball_ok) & ~skip[:, None]).astype(jnp.int32)
            )
            keep &= ball_ok
        if use_cone:
            qcos, qsin = bounds.query_angle_terms(
                ipct, qn, tree.leaf_cnorm[leaf]
            )
            cb = bounds.point_cone_bound(
                qcos[:, None], qsin[:, None], xcs[leaf], xsn[leaf]
            )
            cone_ok = cb < lam[:, None]
            cnt = cnt.at[C_CONE].add(
                jnp.sum((keep & ~cone_ok) & ~skip[:, None]).astype(jnp.int32)
            )
            keep &= cone_ok
        keep &= ~skip[:, None]
        absip = jnp.abs(jnp.einsum("bnd,bd->bn", blk, queries,
                                   precision=bounds.EXACT))
        cand = jnp.where(keep, absip, jnp.inf)
        cnt = cnt.at[C_VERIFIED].add(jnp.sum(keep).astype(jnp.int32))
        # dead tiles are forced skips, not pruning wins: count neither
        # a skip nor a scanned leaf for them (their +inf bound already
        # guarantees skip=True above)
        cnt = cnt.at[C_TILE_SKIP].add(
            jnp.sum(skip & ~tile_dead[leaf]).astype(jnp.int32))
        cnt = cnt.at[C_LEAVES].add(jnp.sum(~skip).astype(jnp.int32))
        md = jnp.concatenate([bd, cand], axis=1)
        mi = jnp.concatenate([bi, idst], axis=1)
        neg, arg = jax.lax.top_k(-md, k)
        return (-neg, jnp.take_along_axis(mi, arg, axis=1), cnt), None

    init = (
        jnp.full((B, k), jnp.inf, dtype),
        jnp.full((B, k), -1, jnp.int32),
        jnp.zeros((8,), jnp.int32),
    )
    (bd, bi, cnt), _ = jax.lax.scan(step, init, visit.T)
    # phase-1 cost: one center IP per leaf per query
    cnt = cnt.at[C_IP].add(jnp.int32(B * L))
    return bd, bi, cnt


def beam_search(tree: FlatTree, queries, k: int = 1, *, frac: float = 0.1, **kw):
    """Budgeted sweep: the paper's candidate-fraction recall/time knob."""
    return sweep_search(tree, queries, k, frac=frac, **kw)
