"""The comparison that decides ``correct``.

Every answer the run produced -- warm-up and window, each against the
live set as it stood when its batch was served -- is judged against the
configuration's plain reference (``exact_topk``, float64).  Three numbers
are compared, each with its limit from the configuration's ``limits``:

* ``bad_answers``: answers that never came (rejected, shed, raised) or
  that say something impossible -- an id that was not live at that
  epoch, a repeated id, fewer than ``min(k, live)`` ids, a distance that
  is not finite.  Exact: limit 0.
* ``dist_err_u``: the widest gap between a returned distance and the
  reference's float64 distance of the returned point.
* ``rank_gap_u``: the widest gap, rank by rank, between the returned
  points' float64 distances (sorted) and the reference's top-k.

Gaps are in units of ``u = 2^-24 R ||q||`` (``R`` the largest
``||(x, 1)||`` of any point stored, ``q`` the normalized query): the
scale of one float32 rounding of a distance.
"""
from __future__ import annotations

import numpy as np

U32 = 2.0 ** -24
NAMES = ("bad_answers", "dist_err_u", "rank_gap_u")


def compare(log, reference, k: int) -> dict:
    points, birth, death = log.arrays()
    queries = np.stack(log.q_raw)
    epochs = np.asarray(log.q_epoch, np.int64)
    ref_d, _ = reference.exact_topk(points, birth, death, queries, epochs, k)
    qn = reference.normalize(queries)
    unit = U32 * reference.max_norm1(points) * np.linalg.norm(qn, axis=1)
    bad, dist_err, rank_gap = 0, 0.0, 0.0
    for b, ans in enumerate(log.answers):
        if ans is None:
            bad += 1
            continue
        d, ids = (np.asarray(a).reshape(-1) for a in ans)
        want = int(np.isfinite(ref_d[b]).sum())
        rows = np.array([log.row_of(int(g)) for g in ids[:want]], np.int64)
        ok = (len(ids) == k and np.all(ids[want:] < 0)
              and np.all((rows >= 0) & (rows < len(points)))
              and len(set(rows.tolist())) == want
              and np.all(np.isfinite(d[:want])))
        if ok:
            ok = bool(np.all((birth[rows] <= epochs[b])
                             & (death[rows] > epochs[b])))
        if not ok:
            bad += 1
            continue
        true = reference.distances(points[rows], qn[b])
        dist_err = max(dist_err, float(
            np.max(np.abs(d[:want] - true), initial=0.0) / unit[b]))
        rank_gap = max(rank_gap, float(
            np.max(np.sort(true) - ref_d[b, :want], initial=0.0) / unit[b]))
    return {"bad_answers": bad, "dist_err_u": dist_err,
            "rank_gap_u": rank_gap, "answers": len(log.answers)}


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``: correct iff every
    number is within its limit."""
    checks = {n: {"value": readings[n], "limit": limits[n]} for n in NAMES}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
