"""Pure-jnp oracle for the Pallas P2H sweep kernel.

Mirrors :func:`repro.kernels.p2h_scan.p2h_sweep` *exactly* -- same operands,
same visit order, same block-granular skip semantics, same pruning math --
so every kernel behaviour (including which tiles are skipped) can be
asserted against it in ``interpret=True`` tests.  Results are additionally
cross-checked against the global brute-force oracle
(:func:`repro.core.exact.exact_search`) because the sweep is *exact* at any
visit order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bounds import EXACT, _cone_cases

__all__ = ["p2h_sweep_ref", "stacked_sweep_ref"]


def p2h_sweep_ref(
    pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm,
    queries, qnorm, cap, leaf_ip, leaf_lb, visit,
    *, k: int, bq: int = 8, use_ball: bool = True, use_cone: bool = True,
    seed_d=None, seed_i=None, probe_dtype: str = "f32",
    sq=None, tile_scale=None, slack_a=None, slack_b=None,
):
    """Reference with identical semantics. Returns (dists, ids, skips);
    dists/ids are sorted ascending here (callers sort kernel output before
    comparing) and ``skips`` (nqb, 1) counts block-granular tile skips
    exactly like the kernel's counter.  ``seed_d``/``seed_i`` (optional,
    (B, k)) seed the running top-k -- the probe-pass handoff of the
    two-pass stacked sweep (pass B resumes from pass A's state instead of
    rescanning probed tiles); ``None`` starts cold (+inf / -1).

    ``probe_dtype`` != "f32" is the quantized probe pass: ``pts_tiles``
    and ``queries`` arrive pre-quantized (bf16, or int8 with ``sq``
    (B, 1) per-query and ``tile_scale`` (L, 1) per-tile dequantization
    scales) and every scored candidate is *widened* by the per-tile
    conservative slack ``qnorm * slack_a[leaf] + sq * slack_b[leaf]``
    before top-k insertion -- the resulting k-th upper-bounds the true
    k-th over the scanned set, so it remains a valid pruning cap.  The
    f32 pruning bounds (``leaf_ip``/``leaf_lb``/ball/cone) are
    untouched: only the scoring matmul is low-precision."""
    pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm = (
        jnp.asarray(a) for a in
        (pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm))
    B = queries.shape[0]
    nqb, n_visit = visit.shape
    assert B == nqb * bq
    if seed_d is None:
        seed_d = jnp.full((B, k), jnp.inf, jnp.float32)
        seed_i = jnp.full((B, k), -1, jnp.int32)
    if sq is None:
        sq = jnp.zeros((B, 1), jnp.float32)
    if tile_scale is None:
        tile_scale = jnp.ones((pts_tiles.shape[0], 1), jnp.float32)
    if slack_a is None:
        slack_a = jnp.zeros((pts_tiles.shape[0], 1), jnp.float32)
    if slack_b is None:
        slack_b = jnp.zeros((pts_tiles.shape[0], 1), jnp.float32)
    tile_scale, slack_a, slack_b = (jnp.asarray(a, jnp.float32) for a in
                                    (tile_scale, slack_a, slack_b))
    _dn = (((1,), (1,)), ((), ()))

    def one_block(qb, qnb, sqb, capb, ipb, lbb, order, sd, si):
        # qb (bq, dp); ipb/lbb (bq, L); order (n_visit,); sd/si (bq, k)
        topd = jnp.asarray(sd, jnp.float32)
        topi = jnp.asarray(si, jnp.int32)

        def step(carry, leaf):
            td, ti, ns = carry
            lam = jnp.minimum(jnp.max(td, axis=1), capb[:, 0])
            active = lbb[:, leaf] < lam
            ns = ns + jnp.where(jnp.any(active), 0, 1).astype(jnp.int32)
            ids = ids_tiles[leaf]
            keep = (ids >= 0)[None, :] & active[:, None]
            ip = ipb[:, leaf]
            qn = qnb[:, 0]
            if use_ball:
                pb = jnp.maximum(
                    jnp.abs(ip)[:, None] - qn[:, None] * rx_tiles[leaf][None, :], 0.0)
                keep &= pb < lam[:, None]
            if use_cone:
                cn = jnp.maximum(leaf_cnorm[leaf, 0], 1e-12)
                qcos = ip / cn
                qsin = jnp.sqrt(jnp.maximum(qn * qn - qcos * qcos, 0.0))
                cb = _cone_cases(qcos[:, None], qsin[:, None],
                                 xc_tiles[leaf][None, :], xs_tiles[leaf][None, :])
                keep &= cb < lam[:, None]
            if probe_dtype == "f32":
                absip = jnp.abs(jnp.dot(qb, pts_tiles[leaf].T,
                                        precision=EXACT))
                cand = jnp.where(keep, absip, jnp.inf)
            else:
                if probe_dtype == "bf16":
                    raw = jax.lax.dot_general(
                        qb, pts_tiles[leaf], dimension_numbers=_dn,
                        preferred_element_type=jnp.float32)
                else:  # int8 -> int32 exact; dequant = query x tile scale
                    acc = jax.lax.dot_general(
                        qb, pts_tiles[leaf], dimension_numbers=_dn,
                        preferred_element_type=jnp.int32)
                    raw = (acc.astype(jnp.float32)
                           * (sqb * tile_scale[leaf, 0]))
                err = qn * slack_a[leaf, 0] + sqb[:, 0] * slack_b[leaf, 0]
                # keep=False masks +inf in (NaN-free: pads/dead tiles
                # never reach the dequant product)
                cand = jnp.where(keep, jnp.abs(raw) + err[:, None],
                                 jnp.inf)
            md = jnp.concatenate([td, cand], axis=1)
            mi = jnp.concatenate(
                [ti, jnp.broadcast_to(ids, (bq, ids.shape[0]))], axis=1)
            neg, arg = jax.lax.top_k(-md, k)
            return (-neg, jnp.take_along_axis(mi, arg, axis=1), ns), None

        (td, ti, ns), _ = jax.lax.scan(step, (topd, topi, jnp.int32(0)),
                                       order)
        return td, ti, ns

    qb = queries.reshape(nqb, bq, -1)
    qn = qnorm.reshape(nqb, bq, 1)
    sqv = jnp.asarray(sq, jnp.float32).reshape(nqb, bq, 1)
    cp = cap.reshape(nqb, bq, 1)
    ipb = leaf_ip.reshape(nqb, bq, -1)
    lbb = leaf_lb.reshape(nqb, bq, -1)
    sd = jnp.asarray(seed_d).reshape(nqb, bq, k)
    si = jnp.asarray(seed_i).reshape(nqb, bq, k)
    td, ti, ns = jax.vmap(one_block)(qb, qn, sqv, cp, ipb, lbb, visit,
                                     sd, si)
    return td.reshape(B, k), ti.reshape(B, k), ns.reshape(nqb, 1)


def stacked_sweep_ref(
    pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm,
    queries, qnorm, cap, leaf_ip, leaf_lb, visit,
    *, k: int, bq: int = 8, use_ball: bool = True, use_cone: bool = True,
    seed_d=None, seed_i=None, global_seed=None, probe_dtype: str = "f32",
    sq=None, tile_scale=None, slack_a=None, slack_b=None,
):
    """Oracle for :func:`repro.kernels.stacked_sweep.stacked_sweep`:
    :func:`p2h_sweep_ref` scanned over the leading segment axis with the
    kernel's **in-launch global top-k** threaded through the carry.  Tile
    operands carry a leading ``N``; queries / qnorm / the entry cap are
    shared across segments.  Per segment, the global running k-th is
    folded into the effective cap (the kernel reads its ``glob`` scratch
    -- constant within a segment on both paths, because the fold happens
    at each segment's last tile), and the segment's resulting top-k
    *values* are merged into the carry.  ``seed_d``/``seed_i`` (optional,
    (N, B, k)) seed each segment's running top-k -- pass B of the
    two-pass sweep resumes from pass A's per-segment state --
    ``global_seed`` ((B, k)) seeds the global values (pass B gets pass
    A's merged planes).  Returns ``(dists (N, B, k) ascending, global
    ids (N, B, k), skips (N, B//bq, 1))`` with the same block-granular
    skip semantics as the stacked kernel (pad tiles enter with a ``+inf``
    node bound, so they are always skipped and always counted)."""
    N, B = pts_tiles.shape[0], queries.shape[0]
    L = pts_tiles.shape[1]
    if seed_d is None:
        seed_d = jnp.full((N, B, k), jnp.inf, jnp.float32)
        seed_i = jnp.full((N, B, k), -1, jnp.int32)
    if global_seed is None:
        global_seed = jnp.full((B, k), jnp.inf, jnp.float32)
    if sq is None:
        sq = jnp.zeros((B, 1), jnp.float32)
    if tile_scale is None:
        tile_scale = jnp.ones((N, L, 1), jnp.float32)
    if slack_a is None:
        slack_a = jnp.zeros((N, L, 1), jnp.float32)
    if slack_b is None:
        slack_b = jnp.zeros((N, L, 1), jnp.float32)

    def seg_step(glob, seg):
        pts, ids, rx, xc, xs, cn, ip, lb, vis, sd, si, ts, sa, sb = seg
        # the kernel's per-tile threshold min's in the global running
        # k-th; glob only updates at segment end, so folding it into the
        # cap here is bit-identical
        capg = jnp.minimum(cap, jnp.max(glob, axis=1, keepdims=True))
        td, ti, ns = p2h_sweep_ref(
            pts, ids, rx, xc, xs, cn, queries, qnorm, capg, ip, lb, vis,
            k=k, bq=bq, use_ball=use_ball, use_cone=use_cone,
            seed_d=sd, seed_i=si, probe_dtype=probe_dtype, sq=sq,
            tile_scale=ts, slack_a=sa, slack_b=sb)
        merged = jnp.concatenate([glob, td], axis=1)
        glob = -jax.lax.top_k(-merged, k)[0]  # k smallest values
        return glob, (td, ti, ns)

    _, (td, ti, ns) = jax.lax.scan(
        seg_step, jnp.asarray(global_seed, jnp.float32),
        (pts_tiles, ids_tiles, rx_tiles, xc_tiles, xs_tiles, leaf_cnorm,
         leaf_ip, leaf_lb, visit, jnp.asarray(seed_d),
         jnp.asarray(seed_i), jnp.asarray(tile_scale, jnp.float32),
         jnp.asarray(slack_a, jnp.float32),
         jnp.asarray(slack_b, jnp.float32)))
    return td, ti, ns
