"""Plain reference for exact point-to-hyperplane top-k, narrowed by
blocks of points: ``p2h_exact``'s definition and float64 ranking for
pools whose float32 error allowance holds thousands of points.

``p2h_exact`` keeps its ``WIDE`` nearest float32 candidates a query and,
when all of them lie within the allowance ``2 tol`` of the float32 k-th,
narrows the whole live set again on the host, one query at a time.  At
a million 960-d points the allowance holds ~10,000-25,000 points, so
every query takes that path.  Here the points are cut into blocks of
``ROWS`` rows and the device keeps each block's ``WIDE`` nearest live
points per query: the union holds every point within the allowance
unless some block's ``WIDE``-th value lies within it, and only such a
query is narrowed again on the host.  The candidates are then ranked in
float64 on the host as in ``p2h_exact``.

Distances, the allowance and the control are ``p2h_exact``'s own; the
control runs a micro-batch at a time (``CONTROL_BLOCK`` queries), so its
temporaries stay small beside a million-point store on one chip.
"""
from __future__ import annotations

import functools

import numpy as np

import harness

_base = harness.load_named("configs", "p2h_exact")
normalize = _base.normalize
distances = _base.distances
max_norm1 = _base.max_norm1
tolerance = _base.tolerance

#: rows of a block of points
ROWS = 65536
#: float32 candidates kept per query and block
WIDE = 8192
#: queries per call of the control
CONTROL_BLOCK = 8


def control_topk(points_dev, live, queries, k: int):
    """``p2h_exact.control_topk`` in blocks of ``CONTROL_BLOCK`` queries."""
    return _base.control_topk(points_dev, live, queries, k,
                              block=CONTROL_BLOCK)


@functools.lru_cache(maxsize=None)
def _narrow(rows: int, wide: int):
    import jax
    import jax.numpy as jnp

    def f(x, birth, death, qn, epoch, start):
        x = jax.lax.dynamic_slice_in_dim(x, start, rows)
        birth = jax.lax.dynamic_slice_in_dim(birth, start, rows)
        death = jax.lax.dynamic_slice_in_dim(death, start, rows)
        x1 = jnp.concatenate([x, jnp.ones((rows, 1), x.dtype)], 1)
        d = jnp.abs(jnp.dot(qn, x1.T, precision=jax.lax.Precision.HIGHEST))
        live = ((birth[None, :] <= epoch[:, None])
                & (death[None, :] > epoch[:, None]))
        neg, idx = jax.lax.top_k(-jnp.where(live, d, jnp.inf), wide)
        return -neg, idx + start

    return jax.jit(f)


def _host_narrow(points, birth, death, qn, epoch, k, tol):
    """Every live point within ``2 tol`` of the float32 k-th, from a
    float32 pass over the whole live set on the host."""
    live = (birth <= epoch) & (death > epoch)
    d32 = np.abs(points @ qn[:-1].astype(np.float32) + np.float32(qn[-1]))
    d32[~live] = np.inf
    kth = np.partition(d32, k - 1)[k - 1]
    return np.nonzero(d32 <= kth + 2 * tol)[0]


def exact_topk(points, birth, death, queries, epochs, k: int, *,
               block: int = 64):
    """float64 top-k over the points live at each query's epoch
    (``birth <= epoch < death``).  Returns ``(dists (m, k), rows (m, k))``
    ascending, ties broken by row; missing slots are ``inf`` / ``-1``."""
    import jax.numpy as jnp

    points = np.asarray(points, np.float32)
    birth, death = np.asarray(birth), np.asarray(death)
    n, m = len(points), len(queries)
    qn = normalize(queries)
    tol = tolerance(points, qn)
    rows = min(ROWS, n)
    wide = min(WIDE, rows)
    starts = range(0, n, rows)
    pad = len(starts) * rows - n
    # pad rows are never live: born after every epoch
    xd = jnp.asarray(np.concatenate(
        [points, np.zeros((pad, points.shape[1]), np.float32)]))
    bd = jnp.asarray(np.concatenate(
        [_base._epochs32(birth), np.full(pad, 2 ** 31 - 1, np.int32)]))
    dd = jnp.asarray(np.concatenate(
        [_base._epochs32(death), np.full(pad, 2 ** 31 - 1, np.int32)]))
    out_d = np.full((m, k), np.inf)
    out_i = np.full((m, k), -1, np.int64)
    for s in range(0, m, block):
        e = min(m, s + block)
        qb = jnp.asarray(_base._padded(qn[s:e], block), jnp.float32)
        eb = jnp.asarray(_base._padded(_base._epochs32(epochs[s:e]), block))
        parts = [_narrow(rows, wide)(xd, bd, dd, qb, eb, st)
                 for st in starts]
        vals = np.stack([np.asarray(v, np.float64) for v, _ in parts], 1)
        idx = np.stack([np.asarray(i) for _, i in parts], 1)
        for r in range(e - s):  # vals[r], idx[r]: (blocks, wide)
            b = s + r
            fin = np.isfinite(vals[r])
            if fin.sum() < k:  # fewer live points than k
                cand = idx[r][fin]
            else:
                kth = np.partition(vals[r][fin], k - 1)[k - 1]
                lim = kth + 2 * tol[b]
                if wide < rows and np.any(vals[r][:, -1] <= lim):
                    # a block's list may be cut short: narrow the whole
                    # live set again on the host
                    cand = _host_narrow(points, birth, death, qn[b],
                                        epochs[b], k, tol[b])
                else:
                    cand = idx[r][vals[r] <= lim]
            dist = distances(points[cand], qn[b])
            order = np.lexsort((cand, dist))[:k]
            out_d[b, :len(order)] = dist[order]
            out_i[b, :len(order)] = cand[order]
    return out_d, out_i
