"""The comparison that decides `correct`.

Every answer the window's serve calls returned is held against the
configuration's plain reference (`bench/references/<reference>.py`), after
the window has closed:

  unanswered    queries handed to the server that came back without a full
                answer (no result, or a truncated one); limit 0
  recall_<t>    mean recall@k, against the reference's exact top-k, of the
                answers whose declared target was t; at least t minus the
                configuration's `recall_tolerance` (the guarantee it states)
  dist_gap      the widest gap between a reported distance and the
                reference's distance of the reported id, over the query's
                exact k-th neighbour distance. The reference distance is
                taken at float64 and from bfloat16-rounded operands (the
                precision the configuration states) and the nearer of the
                two counts, so an answer at the stated precision or above
                reads near 0. A reported id outside the collection reads
                infinite. Limit from the configuration's `limits`.
"""
from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np

CHUNK = 512            # answers per host block of the distance check


class Answers(NamedTuple):
    pool_idx: np.ndarray   # i64[A] pool row of each query handed
    targets: np.ndarray    # f32[A] declared target
    ids: np.ndarray        # i64[A, k] reported ids (-1 when unanswered)
    dists: np.ndarray      # f64[A, k] reported squared distances
    answered: np.ndarray   # bool[A] full answer returned


def collect(pool_idx, targets, results, truncated: int, k: int) -> Answers:
    """Answers of one serve call. `results[j]` is (dists, ids) or None.
    The server does not say which of its results were truncated, so a
    call with truncations marks all of its answers unanswered."""
    n = len(results)
    ids = np.full((n, k), -1, np.int64)
    dists = np.full((n, k), np.nan)
    ok = np.array([r is not None for r in results], bool)
    if ok.any():
        ids[ok] = np.stack([np.asarray(r[1]) for r in results if r is not None])
        dists[ok] = np.stack([np.asarray(r[0]) for r in results
                              if r is not None])
    if truncated:
        ok[:] = False
    return Answers(np.asarray(pool_idx, np.int64),
                   np.asarray(targets, np.float32), ids, dists, ok)


def concat(parts: List[Answers]) -> Answers:
    return Answers(*(np.concatenate([getattr(p, f) for p in parts])
                     for f in Answers._fields))


def compare(ans: Answers, pool: np.ndarray, base: np.ndarray, ref,
            cfg: dict, targets) -> Dict[str, Dict[str, float]]:
    """Each compared number with its limit: {"name": {"value", "min" or
    "max"}}."""
    k = cfg["k"]
    checks: Dict[str, Dict[str, float]] = {
        "unanswered": {"value": int((~ans.answered).sum()), "max": 0}}
    a = ans.answered
    rows, inv = np.unique(ans.pool_idx[a], return_inverse=True)
    true_ids = ref.topk(pool[rows], base, k) if rows.size else \
        np.zeros((0, k), np.int64)
    kth = ref.distances(pool[rows], base, true_ids[:, -1:])[:, 0]
    found = ans.ids[a]
    hits = (found[:, :, None] == true_ids[inv][:, None, :]).any(axis=2)
    recall = hits.sum(axis=1) / k
    tol = cfg["guarantee"]["recall_tolerance"]
    for t in sorted({float(np.float32(t)) for t in targets}):
        sel = np.isclose(ans.targets[a], t)
        checks[f"recall_{t:.2f}"] = {
            "value": float(recall[sel].mean()) if sel.any() else None,
            "min": round(t - tol, 6)}
    gap = 0.0
    for lo in range(0, found.shape[0], CHUNK):
        sl = slice(lo, lo + CHUNK)
        q = pool[rows[inv[sl]]]
        d = ans.dists[a][sl]
        g = np.minimum(
            np.abs(d - ref.distances(q, base, found[sl])),
            np.abs(d - ref.distances(q, base, found[sl],
                                     operands=ref.BFLOAT16)))
        g = np.where(np.isfinite(g), g, np.inf) / kth[inv[sl], None]
        gap = max(gap, float(g.max()))
    checks["dist_gap"] = {"value": gap if found.size else None,
                          "max": cfg["limits"]["dist_gap"]}
    return checks


def holds(check: Dict[str, float]) -> bool:
    v = check["value"]
    if v is None or not np.isfinite(v):
        return False
    if "max" in check:
        return v <= check["max"]
    return v >= check["min"]
