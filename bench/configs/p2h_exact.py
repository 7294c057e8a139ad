"""Plain reference for exact point-to-hyperplane top-k.

Written from the definition and independent of the program: for a point
``x`` and a hyperplane ``(w, b)`` the distance is
``|<(x, 1), (w, b)>| / ||w||``, and the answer to a query is the ``k``
live points of smallest distance.

``exact_topk`` is exact in float64.  It first narrows each query to
candidates with one float32 pass on the device, then ranks the
candidates in float64 on the host.  The narrowing keeps every point
whose float32 distance lies within twice a float32 error bound of the
float32 k-th: ``tol = 2 (D + 1) 2^-24 R ||q||`` (``D = d + 1`` terms,
``R`` the largest ``||(x, 1)||``; a dot product's forward error is at
most ``D 2^-24 ||x|| ||q||``, the query's rounding to float32 adds one
more ``2^-24 ||x|| ||q||``, and the factor 2 covers a chip's float32
matmul passes), so no point of the true top-k is dropped.  A query whose
``WIDE`` candidates might not hold them all is narrowed again on the
host, in float32 numpy over its whole live set.

``control_topk`` is the same search computed one precision below the
configuration's float32 at ``HIGHEST``: three bfloat16 passes (the
``high`` precision), written out so that it is the same on any backend.
"""
from __future__ import annotations

import functools

import numpy as np

U32 = 2.0 ** -24
#: candidates kept per query by the float32 narrowing pass
WIDE = 4096


def normalize(queries) -> np.ndarray:
    """``(w, b) / ||w||`` in float64, so distances are Euclidean."""
    q = np.asarray(queries, np.float64)
    return q / np.linalg.norm(q[:, :-1], axis=1, keepdims=True)


def with_ones(points) -> np.ndarray:
    p = np.asarray(points, np.float64)
    return np.concatenate([p, np.ones((len(p), 1))], axis=1)


def max_norm1(points) -> float:
    """``R``: the largest ``||(x, 1)||`` (float32 sums: a scale, not a
    distance)."""
    p = np.asarray(points, np.float32)
    return float(np.sqrt(np.einsum("ij,ij->i", p, p).max() + 1.0))


def tolerance(points, qn) -> np.ndarray:
    """Per query, the float32 narrowing's error allowance (module
    docstring)."""
    D = points.shape[1] + 1
    return (2 * (D + 1) * U32 * max_norm1(points)
            * np.linalg.norm(qn, axis=1))


def distances(points, qn) -> np.ndarray:
    """float64 ``|<(x, 1), q>|`` of ``points`` (m, d) for one query."""
    return np.abs(with_ones(points) @ np.asarray(qn, np.float64))


@functools.lru_cache(maxsize=None)
def _narrow(wide: int):
    import jax
    import jax.numpy as jnp

    def f(x, birth, death, qn, epoch):
        x1 = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], 1)
        d = jnp.abs(jnp.dot(qn, x1.T, precision=jax.lax.Precision.HIGHEST))
        live = ((birth[None, :] <= epoch[:, None])
                & (death[None, :] > epoch[:, None]))
        neg, idx = jax.lax.top_k(-jnp.where(live, d, jnp.inf), wide)
        return -neg, idx

    return jax.jit(f)


def _epochs32(a):
    return np.minimum(np.asarray(a, np.int64), 2 ** 31 - 1).astype(np.int32)


def _padded(a, rows: int):
    """``a`` with its first row repeated up to ``rows`` rows, so every
    block runs one compiled shape."""
    return np.concatenate([a, np.repeat(a[:1], rows - len(a), axis=0)])


def exact_topk(points, birth, death, queries, epochs, k: int, *,
               block: int = 64):
    """float64 top-k over the points live at each query's epoch
    (``birth <= epoch < death``).  Returns ``(dists (m, k), rows (m, k))``
    ascending, ties broken by row; missing slots are ``inf`` / ``-1``."""
    import jax.numpy as jnp

    points = np.asarray(points, np.float32)
    qn = normalize(queries)
    m = len(qn)
    tol = tolerance(points, qn)
    wide = min(WIDE, len(points))
    xd = jnp.asarray(points)
    bd, dd = jnp.asarray(_epochs32(birth)), jnp.asarray(_epochs32(death))
    out_d = np.full((m, k), np.inf)
    out_i = np.full((m, k), -1, np.int64)
    for s in range(0, m, block):
        e = min(m, s + block)
        vals, idx = _narrow(wide)(
            xd, bd, dd, jnp.asarray(_padded(qn[s:e], block), jnp.float32),
            jnp.asarray(_padded(_epochs32(epochs[s:e]), block)))
        vals, idx = np.asarray(vals, np.float64), np.asarray(idx)
        for r in range(e - s):
            b = s + r
            fin = np.isfinite(vals[r])
            if fin.sum() < min(k, wide):  # fewer live points than k
                cand = idx[r][fin]
            else:
                kth = vals[r][k - 1]
                keep = vals[r] <= kth + 2 * tol[b]
                if keep.all() and wide < len(points):
                    # the list may be cut short: narrow the whole live
                    # set again on the host
                    live = ((np.asarray(birth) <= epochs[b])
                            & (np.asarray(death) > epochs[b]))
                    d32 = np.abs(points @ qn[b, :-1].astype(np.float32)
                                 + np.float32(qn[b, -1]))
                    d32[~live] = np.inf
                    kth = np.partition(d32, k - 1)[k - 1]
                    cand = np.nonzero(d32 <= kth + 2 * tol[b])[0]
                else:
                    cand = idx[r][keep]
            dist = distances(points[cand], qn[b])
            order = np.lexsort((cand, dist))[:k]
            out_d[b, :len(order)] = dist[order]
            out_i[b, :len(order)] = cand[order]
    return out_d, out_i


@functools.lru_cache(maxsize=None)
def _control(k: int):
    import jax
    import jax.numpy as jnp

    def split(a):
        # hi keeps the top 8 significand bits, cut by a bit mask: a
        # compiler may drop an f32 -> bf16 -> f32 round trip of
        # ``astype`` as excess precision, which would zero the low part
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                          jnp.float32)
        return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)

    def dot(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def f(x, live, qn):
        x1 = jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], 1)
        (qh, ql), (xh, xl) = split(qn), split(x1)
        d = jnp.abs(dot(qh, xh) + dot(qh, xl) + dot(ql, xh))
        neg, idx = jax.lax.top_k(-jnp.where(live[None, :], d, jnp.inf), k)
        return -neg, idx

    return jax.jit(f)


def control_topk(points_dev, live, queries, k: int, *, block: int = 256):
    """The control: top-k at three bfloat16 passes.  ``points_dev`` is
    the (n, d) float32 point store on the device, ``live`` a host mask;
    returns ``(dists (m, k) f32, rows (m, k))``."""
    import jax.numpy as jnp

    qn = normalize(queries).astype(np.float32)
    lv = jnp.asarray(np.asarray(live, bool))
    ds, ids = [], []
    for s in range(0, len(qn), block):
        n = min(block, len(qn) - s)
        d, i = _control(k)(points_dev, lv,
                           jnp.asarray(_padded(qn[s:s + n], block)))
        ds.append(np.asarray(d)[:n])
        ids.append(np.asarray(i, np.int64)[:n])
    return np.concatenate(ds), np.concatenate(ids)
