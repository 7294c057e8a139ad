"""``build_tree`` against a frozen copy of the version it replaced: the
same Algorithm 2 in float64 must give the same ``FlatTree``, bit for
bit, so every cell's tiles and pruning stay where they were."""
import dataclasses
import sys

import numpy as np
import pytest

from repro.core.balltree import FlatTree, append_ones, build_tree


def _frozen_split(points, idx, rng):
    sub = points[idx]
    v = sub[rng.integers(len(idx))]
    xl = sub[np.argmax(((sub - v) ** 2).sum(axis=1))]
    xr = sub[np.argmax(((sub - xl) ** 2).sum(axis=1))]
    dl = ((sub - xl) ** 2).sum(axis=1)
    dr = ((sub - xr) ** 2).sum(axis=1)
    left_mask = dl <= dr
    if left_mask.all() or (~left_mask).all():
        half = len(idx) // 2
        left_mask = np.zeros(len(idx), dtype=bool)
        left_mask[:half] = True
    return idx[left_mask], idx[~left_mask]


def _frozen_build_tree(data, n0=256, *, seed=0, append_one=True,
                       dtype=np.float32):
    """``build_tree`` as it stood before rows were gathered once per node."""
    data = np.asarray(data, dtype=np.float64)
    if append_one:
        data = append_ones(data)
    n, d = data.shape
    rng = np.random.default_rng(seed)
    nodes = []
    leaf_point_idx = []
    sys.setrecursionlimit(max(10000, sys.getrecursionlimit()))
    max_depth = [0]

    def rec(idx, depth):
        node_id = len(nodes)
        nodes.append(None)
        max_depth[0] = max(max_depth[0], depth)
        sub = data[idx]
        if len(idx) <= n0:
            center = sub.mean(axis=0)
            radius = float(np.sqrt(((sub - center) ** 2).sum(axis=1).max()))
            slot = len(leaf_point_idx)
            leaf_point_idx.append(idx)
            nodes[node_id] = (center, radius, len(idx), -1, -1, slot, depth)
        else:
            li, ri = _frozen_split(data, idx, rng)
            lid = rec(li, depth + 1)
            rid = rec(ri, depth + 1)
            cl, nl = nodes[lid][0], nodes[lid][2]
            cr, nr = nodes[rid][0], nodes[rid][2]
            center = (cl * nl + cr * nr) / (nl + nr)
            radius = float(np.sqrt(((sub - center) ** 2).sum(axis=1).max()))
            nodes[node_id] = (center, radius, len(idx), lid, rid, -1, depth)
        return node_id

    rec(np.arange(n), 0)
    m, L = len(nodes), len(leaf_point_idx)
    centers = np.zeros((m, d), dtype=dtype)
    radii = np.zeros((m,), dtype=dtype)
    counts = np.zeros((m,), dtype=np.int32)
    left = np.full((m,), -1, dtype=np.int32)
    right = np.full((m,), -1, dtype=np.int32)
    node_leaf = np.full((m,), -1, dtype=np.int32)
    for i, (c, r, cnt, lc, rc, slot, _) in enumerate(nodes):
        centers[i], radii[i], counts[i] = c, r, cnt
        left[i], right[i], node_leaf[i] = lc, rc, slot
    points = np.zeros((L * n0, d), dtype=dtype)
    point_ids = np.full((L * n0,), -1, dtype=np.int32)
    rx = np.full((L * n0,), -1.0, dtype=dtype)
    xcos = np.zeros((L * n0,), dtype=dtype)
    xsin = np.zeros((L * n0,), dtype=dtype)
    leaf_centers = np.zeros((L, d), dtype=dtype)
    leaf_radii = np.zeros((L,), dtype=dtype)
    for node_id in np.where(node_leaf >= 0)[0]:
        slot = int(node_leaf[node_id])
        idx = leaf_point_idx[slot]
        c = np.asarray(nodes[node_id][0])
        sub = data[idx]
        r_x = np.sqrt(((sub - c) ** 2).sum(axis=1))
        order = np.argsort(-r_x, kind="stable")
        idx, sub, r_x = idx[order], sub[order], r_x[order]
        xn = np.sqrt((sub ** 2).sum(axis=1))
        cn = max(float(np.sqrt((c ** 2).sum())), 1e-12)
        x_cos = (sub @ c) / cn
        x_sin = np.sqrt(np.maximum(xn ** 2 - x_cos ** 2, 0.0))
        s, e = slot * n0, slot * n0 + len(idx)
        points[s:e], point_ids[s:e], rx[s:e] = sub, idx, r_x
        xcos[s:e], xsin[s:e] = x_cos, x_sin
        leaf_centers[slot] = c
        leaf_radii[slot] = nodes[node_id][1]
    leaf_cnorm = np.maximum(
        np.sqrt((leaf_centers.astype(np.float64) ** 2).sum(axis=1)), 1e-12
    ).astype(dtype)
    return FlatTree(centers=centers, radii=radii, counts=counts, left=left,
                    right=right, node_leaf=node_leaf,
                    leaf_centers=leaf_centers, leaf_radii=leaf_radii,
                    leaf_cnorm=leaf_cnorm, points=points,
                    point_ids=point_ids, rx=rx, xcos=xcos, xsin=xsin, n0=n0,
                    n=n, d=d, num_nodes=m, num_leaves=L,
                    max_depth=max_depth[0])


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("dim,n,n0", [(8, 3000, 16), (100, 2500, 64),
                                      (960, 1200, 128)])
def test_build_tree_is_bit_identical_to_the_frozen_copy(dim, n, n0,
                                                           seed):
    rng = np.random.default_rng(100 * dim + seed)
    centers = 4.0 * rng.normal(size=(max(4, dim // 8), dim))
    x = (centers[rng.integers(0, len(centers), n)]
         + 0.5 * rng.normal(size=(n, dim))).astype(np.float32)
    if seed == 2:
        x[::7] = x[0]  # duplicate rows: the degenerate-split guard
    got = build_tree(x, n0=n0, seed=seed)
    want = _frozen_build_tree(x, n0=n0, seed=seed)
    for f in dataclasses.fields(FlatTree):
        a, b = getattr(got, f.name), getattr(want, f.name)
        if isinstance(b, np.ndarray):
            assert a.dtype == b.dtype and a.shape == b.shape, f.name
            assert a.tobytes() == b.tobytes(), f.name
        else:
            assert a == b, f.name
