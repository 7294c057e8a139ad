"""The clustered generator: a Gaussian mixture of points and random
hyperplane queries through data points, made on the device in one
jitted call from the seed.

``max(4, d // 8)`` centers ~ N(0, 16 I), points ~ center + N(0, I / 4);
hyperplanes have N(0, 1) coefficients and a bias that puts the plane
through a data point, plus N(0, 0.01) -- the paper's random hyperplane
queries.  Besides the points and queries a run may ask for a pool of
points to insert (same mixture) and step directions to move
hyperplanes along.
"""
from __future__ import annotations

import functools

import numpy as np

import harness


@functools.lru_cache(maxsize=None)
def _program(n, d, n_queries, n_pool, n_dead, n_steps):
    import jax
    import jax.numpy as jnp

    def gen(key):
        kc, kx, kp, kq, ka, kb, kv, ks = jax.random.split(key, 8)
        n_c = max(4, d // 8)
        centers = 4.0 * jax.random.normal(kc, (n_c, d), jnp.float32)

        def clustered(k, m):
            ki, kn = jax.random.split(k)
            idx = jax.random.randint(ki, (m,), 0, n_c)
            return centers[idx] + 0.5 * jax.random.normal(
                kn, (m, d), jnp.float32)

        x = clustered(kx, n)
        pool = clustered(kp, n_pool)
        w = jax.random.normal(kq, (n_queries, d + 1), jnp.float32)
        anchor = x[jax.random.randint(ka, (n_queries,), 0, n)]
        bias = (-jnp.sum(w[:, :d] * anchor, axis=1)
                + 0.1 * jax.random.normal(kb, (n_queries,), jnp.float32))
        q = w.at[:, d].set(bias)
        dead = jax.random.permutation(kv, n)[:n_dead]
        steps = jax.random.normal(ks, (n_steps, d + 1), jnp.float32)
        return x, pool, q, dead, steps

    return jax.jit(gen)


def make(config: dict, seed: int, *, n_queries: int, n_pool: int = 0,
         n_steps: int = 0) -> harness.Data:
    """The run's data: ``config["n"]`` points of ``config["d"]``, of
    which ``config["delete_frac"]`` are deleted at set-up, and the sizes
    the traffic mix asks for (``loops/<loop>.py`` ``shape``)."""
    n, d = config["n"], config["d"]
    n_dead = int(n * config["delete_frac"])
    out = _program(n, d, n_queries, n_pool, n_dead, n_steps)(
        harness.seed_key(seed))
    x, pool, q, dead, steps = (np.asarray(a) for a in out)
    return harness.Data(points=x, dead=dead.astype(np.int64), queries=q,
                        pool=pool, steps=steps)
