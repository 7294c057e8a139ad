"""Lower bounds for the absolute inner product |<x, q>| from the paper.

All bounds operate on the *simplified* P2HNNS problem (paper Eq. 2): data
``x`` already has the appended 1-coordinate and the query ``q`` is the
(rescaled) hyperplane coefficient vector, so the P2H distance is ``|<x,q>|``.

Implemented bounds:
  * :func:`node_ball_bound`   -- Theorem 2  (node-level ball bound)
  * :func:`point_ball_bound`  -- Corollary 1 (point-level ball bound)
  * :func:`point_cone_bound`  -- Theorem 3  (point-level cone bound)

Everything is pure ``jnp`` and broadcasts: these functions are shared by the
exact DFS search, the TPU-native sweep search, and the Pallas kernels'
reference oracles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "EXACT",
    "node_ball_bound",
    "point_ball_bound",
    "query_angle_terms",
    "point_cone_bound",
]


#: contraction precision of every f32 inner product that a bound or a
#: distance is built from.  A TPU runs an f32 matmul at DEFAULT precision
#: as one bf16 pass, whose ~2^-8 relative error would break both the
#: pruning bounds and the exact answers; HIGHEST keeps f32 accuracy (and
#: is what the CPU does anyway).
EXACT = jax.lax.Precision.HIGHEST


def node_ball_bound(ip_qc, q_norm, radius):
    """Theorem 2: ``min_{x in N} |<x,q>| >= max(|<q,N.c>| - ||q||*N.r, 0)``.

    Args:
      ip_qc:  inner product(s) ``<q, N.c>`` (any broadcastable shape).
      q_norm: ``||q||`` (broadcastable).
      radius: node radius/radii ``N.r`` (broadcastable).
    """
    return jnp.maximum(jnp.abs(ip_qc) - q_norm * radius, 0.0)


def point_ball_bound(ip_qc, q_norm, r_x):
    """Corollary 1: same form as Theorem 2 with the per-point radius r_x.

    All points of a leaf share the leaf center, so ``ip_qc`` is the *leaf*
    center inner product and ``r_x = ||x - N.c||``.
    """
    return jnp.maximum(jnp.abs(ip_qc) - q_norm * r_x, 0.0)


def query_angle_terms(ip_qc, q_norm, c_norm, eps=1e-12):
    """Decompose q against the leaf center direction.

    Returns ``(q_cos, q_sin)`` where ``q_cos = ||q|| cos(theta)`` and
    ``q_sin = ||q|| sin(theta) >= 0`` for ``theta`` the angle between ``q``
    and ``N.c``.  Both are O(1) given the already-computed ``<q, N.c>``
    (paper Section IV-B).
    """
    c_norm = jnp.maximum(c_norm, eps)
    q_cos = ip_qc / c_norm
    q_sin = jnp.sqrt(jnp.maximum(q_norm * q_norm - q_cos * q_cos, 0.0))
    return q_cos, q_sin


def _cone_cases(q_cos, q_sin, x_cos, x_sin):
    """RHS of Inequality 10 for a fixed sign of q.

    ``x_cos = ||x|| cos(phi_x)`` and ``x_sin = ||x|| sin(phi_x)`` are the
    precomputed per-point cone tables (paper Alg. 4, lines 7-8).

      a = ||x|| ||q|| cos(theta + phi_x) = q_cos*x_cos - q_sin*x_sin
      b = ||x|| ||q|| cos(theta - phi_x) = q_cos*x_cos + q_sin*x_sin
    """
    a = q_cos * x_cos - q_sin * x_sin
    b = q_cos * x_cos + q_sin * x_sin
    zero = jnp.zeros_like(a)
    # Theorem 3, case order matters: case (a) requires cos(theta+phi)>0 AND
    # cos(theta)>0 AND cos(phi)>0; else case (b) requires cos(theta-phi)<0;
    # else the cone may contain a direction orthogonal to q -> bound 0.
    return jnp.where(
        (a > 0) & (q_cos > 0) & (x_cos > 0),
        a,
        jnp.where(b < 0, -b, zero),
    )


def point_cone_bound(q_cos, q_sin, x_cos, x_sin, symmetric: bool = False):
    """Theorem 3: point-level cone bound.

    With ``symmetric=True`` we additionally evaluate the bound for ``-q``
    (which bounds the same quantity because ``|<x,-q>| = |<x,q>|``) and take
    the max.  The paper's bound is *not* sign-symmetric: e.g. for
    ``cos(theta)>0, cos(phi_x)<0, cos(theta-phi_x)<0`` the bound for ``q`` is
    positive while the bound for ``-q`` is 0.  The symmetrized form is a
    strictly-tighter beyond-paper refinement measured in
    ``benchmarks/bench_bounds.py``.
    """
    lb = _cone_cases(q_cos, q_sin, x_cos, x_sin)
    if symmetric:
        lb = jnp.maximum(lb, _cone_cases(-q_cos, q_sin, x_cos, x_sin))
    return lb
