"""Flat-array Ball-Tree / BC-Tree construction (paper Algorithms 1, 2, 4).

Construction runs on host in numpy (it is one-time O(d n log n) index-build
work, inherently sequential) and produces a :class:`FlatTree` of device
arrays laid out for TPU consumption:

  * nodes in preorder: ``centers (m,d)``, ``radii (m,)``, ``counts (m,)``,
    ``left/right (m,)`` child ids (-1 for leaves), ``node_leaf (m,)`` leaf
    slot (-1 for internal nodes);
  * leaves padded to exactly ``n0`` points each; leaf ``j`` owns rows
    ``[j*n0, (j+1)*n0)`` of the reordered ``points`` array (pad rows are
    zeros with ``point_ids == -1``) -- leaves are scan *tiles*;
  * BC-Tree cone tables aligned with ``points``: ``rx = ||x - N.c||``,
    ``xcos = ||x|| cos(phi_x)``, ``xsin = ||x|| sin(phi_x)``; within a leaf,
    points are sorted by descending ``rx`` (paper Alg. 4 line 9) so the
    point-level ball bound prunes in batches / whole remaining tiles.

Internal-node centers are computed via the linearity of the centroid
(Lemma 1) from the children's centers, exactly as BC-Tree's Alg. 4 line 16.
"""
from __future__ import annotations

import dataclasses
import sys
from typing import Any

import jax
import numpy as np

__all__ = ["FlatTree", "build_tree", "append_ones", "normalize_query",
           "leaf_pad_quantum", "pad_tree_leaves", "built_leaves"]


def append_ones(data: np.ndarray) -> np.ndarray:
    """Paper Section II: x = (p; 1)."""
    n = data.shape[0]
    return np.concatenate([data, np.ones((n, 1), dtype=data.dtype)], axis=1)


def normalize_query(q: np.ndarray) -> np.ndarray:
    """Rescale hyperplane coefficients so ||q[:-1]|| = 1 (paper Section II)."""
    q = np.asarray(q, dtype=np.float64)
    scale = np.linalg.norm(q[..., :-1], axis=-1, keepdims=True)
    scale = np.where(scale == 0, 1.0, scale)
    return (q / scale).astype(np.float32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlatTree:
    """Flattened Ball/BC-Tree. Array fields are pytree leaves."""

    # --- node arrays (length m, preorder) ---
    centers: Any  # (m, d) f32
    radii: Any  # (m,) f32
    counts: Any  # (m,) i32  -- |N|
    left: Any  # (m,) i32  -- child node id or -1
    right: Any  # (m,) i32
    node_leaf: Any  # (m,) i32  -- leaf slot or -1
    # --- leaf arrays (length L = num leaves) ---
    leaf_centers: Any  # (L, d) f32  (duplicated rows of `centers` for sweep)
    leaf_radii: Any  # (L,) f32
    leaf_cnorm: Any  # (L,) f32  -- ||leaf center|| (clamped)
    # --- point arrays (length L * n0, leaf-tiled) ---
    points: Any  # (L*n0, d) f32, zero pad rows
    point_ids: Any  # (L*n0,) i32, -1 for pad
    rx: Any  # (L*n0,) f32, descending within each leaf (pad = -1)
    xcos: Any  # (L*n0,) f32
    xsin: Any  # (L*n0,) f32
    # --- static metadata ---
    n0: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    d: int = dataclasses.field(metadata=dict(static=True))
    num_nodes: int = dataclasses.field(metadata=dict(static=True))
    num_leaves: int = dataclasses.field(metadata=dict(static=True))
    max_depth: int = dataclasses.field(metadata=dict(static=True))

    # ------------------------------------------------------------------
    def index_bytes(self, bc: bool = True) -> int:
        """Index size in bytes (Table III metric).

        The Ball-Tree index stores nodes + the reordered data layout
        bookkeeping; BC-Tree adds the three n-sized cone/radius tables
        (paper Theorem 6: O(nd + 3n)).  The raw data points themselves are
        counted as *data*, not index, matching the paper's accounting.
        """
        node_bytes = (
            self.centers.nbytes
            + self.radii.nbytes
            + self.counts.nbytes
            + self.left.nbytes
            + self.right.nbytes
            + self.node_leaf.nbytes
            + self.point_ids.nbytes
        )
        if bc:
            node_bytes += self.rx.nbytes + self.xcos.nbytes + self.xsin.nbytes
        return int(node_bytes)


# ----------------------------------------------------------------------
# Construction
# ----------------------------------------------------------------------


def _sqdist(sub: np.ndarray, v: np.ndarray) -> np.ndarray:
    """``((sub - v) ** 2).sum(axis=1)`` with one temporary, squared in
    place (the same float64 values)."""
    t = sub - v
    np.multiply(t, t, out=t)
    return t.sum(axis=1)


def _split(sub: np.ndarray, idx: np.ndarray, rng: np.random.Generator):
    """Paper Algorithm 2 (seed-grow rule) with a degenerate-split guard,
    over the node's rows ``sub`` (already gathered: ``data[idx]``)."""
    v = sub[rng.integers(len(idx))]
    dl = _sqdist(sub, v)
    xl = sub[np.argmax(dl)]
    dl = _sqdist(sub, xl)  # distances to xl: the seed pick and the split
    xr = sub[np.argmax(dl)]
    dr = _sqdist(sub, xr)
    left_mask = dl <= dr
    if left_mask.all() or (~left_mask).all():
        # all points coincide (duplicates) -- split in half arbitrarily
        half = len(idx) // 2
        left_mask = np.zeros(len(idx), dtype=bool)
        left_mask[:half] = True
    return idx[left_mask], idx[~left_mask]


def build_tree(
    data: np.ndarray,
    n0: int = 256,
    *,
    seed: int = 0,
    append_one: bool = True,
    dtype=np.float32,
) -> FlatTree:
    """Build a flat BC-Tree (superset of Ball-Tree) from raw data.

    Args:
      data: (n, d-1) raw points, or (n, d) if ``append_one=False``.
      n0: max leaf size == scan tile size (multiples of 128 recommended).
    """
    data = np.asarray(data, dtype=np.float64)
    if append_one:
        data = append_ones(data)
    n, d = data.shape
    rng = np.random.default_rng(seed)

    nodes = []  # (center, radius, count, left, right, leaf_slot, depth)
    #: per leaf slot: point ids, their rows and squared distances to the
    #: leaf center (rows gathered once, reused for the cone tables)
    leaf_point_idx: list[tuple] = []

    sys.setrecursionlimit(max(10000, sys.getrecursionlimit()))
    max_depth = [0]

    def rec(idx: np.ndarray, depth: int) -> int:
        node_id = len(nodes)
        nodes.append(None)  # reserve preorder slot
        max_depth[0] = max(max_depth[0], depth)
        sub = data[idx]
        if len(idx) <= n0:  # leaf
            center = sub.mean(axis=0)
            d2 = _sqdist(sub, center)
            radius = float(np.sqrt(d2.max()))
            slot = len(leaf_point_idx)
            leaf_point_idx.append((idx, sub, d2))
            nodes[node_id] = (center, radius, len(idx), -1, -1, slot, depth)
        else:
            li, ri = _split(sub, idx, rng)
            lid = rec(li, depth + 1)
            rid = rec(ri, depth + 1)
            # Lemma 1: centroid linearity (BC-Tree Alg. 4 line 16)
            cl, _, nl = nodes[lid][0], nodes[lid][1], nodes[lid][2]
            cr, nr = nodes[rid][0], nodes[rid][2]
            center = (cl * nl + cr * nr) / (nl + nr)
            radius = float(np.sqrt(_sqdist(sub, center).max()))
            nodes[node_id] = (center, radius, len(idx), lid, rid, -1, depth)
        return node_id

    rec(np.arange(n), 0)

    m = len(nodes)
    L = len(leaf_point_idx)
    centers = np.zeros((m, d), dtype=dtype)
    radii = np.zeros((m,), dtype=dtype)
    counts = np.zeros((m,), dtype=np.int32)
    left = np.full((m,), -1, dtype=np.int32)
    right = np.full((m,), -1, dtype=np.int32)
    node_leaf = np.full((m,), -1, dtype=np.int32)
    for i, (c, r, cnt, lc, rc, slot, _) in enumerate(nodes):
        centers[i] = c
        radii[i] = r
        counts[i] = cnt
        left[i] = lc
        right[i] = rc
        node_leaf[i] = slot

    points = np.zeros((L * n0, d), dtype=dtype)
    point_ids = np.full((L * n0,), -1, dtype=np.int32)
    rx = np.full((L * n0,), -1.0, dtype=dtype)  # pad sorts to the end (desc)
    xcos = np.zeros((L * n0,), dtype=dtype)
    xsin = np.zeros((L * n0,), dtype=dtype)
    leaf_centers = np.zeros((L, d), dtype=dtype)
    leaf_radii = np.zeros((L,), dtype=dtype)

    leaf_node_ids = np.where(node_leaf >= 0)[0]
    for node_id in leaf_node_ids:
        slot = int(node_leaf[node_id])
        idx, sub, d2 = leaf_point_idx[slot]
        c = np.asarray(nodes[node_id][0])
        r_x = np.sqrt(d2)
        order = np.argsort(-r_x, kind="stable")  # descending rx (Alg. 4 l.9)
        idx, sub, r_x = idx[order], sub[order], r_x[order]
        xn = np.sqrt((sub**2).sum(axis=1))
        cn = max(float(np.sqrt((c**2).sum())), 1e-12)
        x_cos = (sub @ c) / cn  # ||x|| cos(phi_x)
        x_sin = np.sqrt(np.maximum(xn**2 - x_cos**2, 0.0))
        s, e = slot * n0, slot * n0 + len(idx)
        points[s:e] = sub
        point_ids[s:e] = idx
        rx[s:e] = r_x
        xcos[s:e] = x_cos
        xsin[s:e] = x_sin
        leaf_centers[slot] = c
        leaf_radii[slot] = nodes[node_id][1]

    leaf_cnorm = np.maximum(
        np.sqrt((leaf_centers.astype(np.float64) ** 2).sum(axis=1)), 1e-12
    ).astype(dtype)

    return FlatTree(
        centers=centers,
        radii=radii,
        counts=counts,
        left=left,
        right=right,
        node_leaf=node_leaf,
        leaf_centers=leaf_centers,
        leaf_radii=leaf_radii,
        leaf_cnorm=leaf_cnorm,
        points=points,
        point_ids=point_ids,
        rx=rx,
        xcos=xcos,
        xsin=xsin,
        n0=n0,
        n=n,
        d=d,
        num_nodes=m,
        num_leaves=L,
        max_depth=max_depth[0],
    )


def built_leaves(tree: FlatTree) -> int:
    """Leaf count of the *built* tree, excluding any
    :func:`pad_tree_leaves` padding.  Pad leaves own no node, so the
    largest leaf slot referenced by the node array is the last real
    leaf -- heuristics that reason about wasted tiles (e.g. the stacked
    dispatch's density floor) should divide by this, not ``num_leaves``.
    """
    return int(np.asarray(tree.node_leaf).max()) + 1


def leaf_pad_quantum(num_leaves: int) -> int:
    """Leaf-count quantum for :func:`pad_tree_leaves`: coarser as trees
    grow, so a churning index's freshly-compacted segments keep landing
    on already-compiled sweep shapes (the same ladder shape as the
    stacked launch's tile quantum)."""
    if num_leaves <= 128:
        return 8
    if num_leaves <= 512:
        return 16
    return 32


def pad_tree_leaves(tree: FlatTree, num_leaves: int) -> FlatTree:
    """Pad ``tree``'s leaf/point arrays to ``num_leaves`` leaf slots.

    Pad leaves replicate leaf 0's geometry but hold no valid points
    (``point_ids == -1``, ``rx == -1``) -- the repo-wide empty-tile
    convention, so every search scheme treats them as skippable and
    results are bit-identical to the unpadded tree on exact paths.  The
    node arrays are untouched: no node references a pad leaf, so tree
    walks (dfs) never see them; only the flat leaf sweeps (whose jit
    programs are keyed on the leaf count -- the point of padding) do.
    """
    pl = num_leaves - tree.num_leaves
    if pl <= 0:
        return tree
    n0 = tree.n0

    def padl(a):  # leaf arrays: replicate row 0 geometry
        rep = np.broadcast_to(np.asarray(a)[:1], (pl,) + np.shape(a)[1:])
        return np.concatenate([np.asarray(a), rep], axis=0)

    def padp(a, fill):  # point rows: empty tiles
        w = [(0, pl * n0)] + [(0, 0)] * (np.asarray(a).ndim - 1)
        return np.pad(np.asarray(a), w, constant_values=fill)

    return dataclasses.replace(
        tree,
        leaf_centers=padl(tree.leaf_centers),
        leaf_radii=padl(tree.leaf_radii),
        leaf_cnorm=padl(tree.leaf_cnorm),
        points=padp(tree.points, 0.0),
        point_ids=padp(tree.point_ids, -1),
        rx=padp(tree.rx, -1.0),  # pad sorts to the end (desc)
        xcos=padp(tree.xcos, 0.0),
        xsin=padp(tree.xsin, 0.0),
        num_leaves=num_leaves,
    )
