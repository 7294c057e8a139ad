"""Brute-force P2HNNS oracle: argmin_x |<x, q>| (paper Definition 1).

Used as the ground-truth for recall computation and as the correctness
oracle for every search scheme and kernel in this repo.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.bounds import EXACT

__all__ = ["exact_search", "p2h_dists"]


def p2h_dists(points, queries):
    """|<x, q>| for all pairs -> (num_queries, n)."""
    return jnp.abs(jnp.dot(queries, points.T, precision=EXACT))


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def exact_search(points, queries, k: int = 1, chunk: int = 65536):
    """Exact top-k P2HNNS by chunked scan.

    Args:
      points: (n, d) with the appended 1-coordinate.
      queries: (b, d) hyperplane queries.
    Returns:
      (dists (b,k), ids (b,k)) sorted ascending by distance.
    """
    n = points.shape[0]
    b = queries.shape[0]
    pad = (-n) % chunk
    pts = jnp.pad(points, ((0, pad), (0, 0)))
    nchunks = pts.shape[0] // chunk
    pts = pts.reshape(nchunks, chunk, -1)

    def step(carry, xc):
        best_d, best_i, off = carry
        d = jnp.abs(jnp.dot(queries, xc.T, precision=EXACT))  # (b, chunk)
        ids = off + jnp.arange(chunk, dtype=jnp.int32)
        d = jnp.where(ids[None, :] < n, d, jnp.inf)
        md = jnp.concatenate([best_d, d], axis=1)
        mi = jnp.concatenate([best_i, jnp.broadcast_to(ids, (b, chunk))], axis=1)
        neg, arg = jax.lax.top_k(-md, k)
        return (-neg, jnp.take_along_axis(mi, arg, axis=1), off + chunk), None

    init = (
        jnp.full((b, k), jnp.inf, dtype=points.dtype),
        jnp.full((b, k), -1, dtype=jnp.int32),
        jnp.int32(0),
    )
    (best_d, best_i, _), _ = jax.lax.scan(step, init, pts)
    return best_d, best_i
