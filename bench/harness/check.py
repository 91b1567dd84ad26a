"""What decides ``correct``: the timed path's answers against the reference.

Each number compared has its own limit, in ``bench/limits/<cell>.json``;
a run is correct when every number is at or under its limit.

* ``unanswered``: queries due in the window that got no answer (a missing
  row, a failed or refused query, a degraded answer). Exact: limit 0.
* ``bad_answers``: answers that hold an id that does not exist, an id twice,
  a row that fails the query's template, or scores out of best-first order.
  Exact: limit 0.
* ``score_gap``: the widest gap between a returned score and the float64
  score of the same (query, row), relative to |q||v| (``ip``) or
  |q|^2 + |v|^2 (``l2``). Its limit is set from readings of sound runs and
  of the bf16 control (``PERF.md``).
* ``recall_miss``: 1 - recall@k of every answer against the exact filtered
  top-k. Real rows with exact scores in best-first order can still be the
  wrong rows (a merge that keeps the wrong k, a scan that skips rows); this
  is the number that sees it. Its limit is set between the sound runs'
  readings and those of such planted faults (``PERF.md``).
"""
from __future__ import annotations

import sys
from typing import Dict, Optional

import numpy as np

from harness.reference import exact_scores, score_scale


def bad_answers(
    ids: np.ndarray,  # i64 [A, k]
    scores: np.ndarray,  # f32 [A, k]
    admitted: np.ndarray,  # bool [A, k]: the row exists and passes the template
) -> int:
    valid = ids >= 0
    bad = valid & ~admitted
    # a valid slot after an empty one, or a non-finite score on a row
    bad[:, 1:] |= valid[:, 1:] & ~valid[:, :-1]
    bad |= valid & ~np.isfinite(scores)
    # the same row twice in one answer
    srt = np.sort(np.where(valid, ids, -1 - np.arange(ids.shape[1])), axis=1)
    dup = np.zeros(ids.shape[0], dtype=bool)
    dup |= (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    # best first: scores never rise along the valid slots
    both = valid[:, 1:] & valid[:, :-1]
    rising = both & (scores[:, 1:] > scores[:, :-1])
    return int((bad.any(axis=1) | dup | rising.any(axis=1)).sum())


def score_gap(vectors, metric, queries, ids, scores) -> float:
    """Widest relative gap between returned and float64 scores (0 if none)."""
    valid = ids >= 0
    if not valid.any():
        return 0.0
    want = exact_scores(vectors, metric, queries, ids)
    scale = np.maximum(score_scale(vectors, metric, queries, ids), 1e-30)
    gap = np.abs(scores.astype(np.float64) - want) / scale
    gap = np.where(valid, gap, 0.0)
    gap = np.where(np.isfinite(gap), gap, np.inf)
    return float(gap.max())


def recall_hits(ids: np.ndarray, truth: np.ndarray):
    """(hits, totals): per answer, true ids found and true ids that exist."""
    tvalid = truth >= 0
    hit = (ids[:, :, None] == truth[:, None, :]) & (ids[:, :, None] >= 0)
    hits = (hit.any(axis=1) & tvalid).sum(axis=1)
    return hits, tvalid.sum(axis=1)


def recall_miss(ids: np.ndarray, truth: np.ndarray) -> float:
    """1 - recall: the share of the true ids, over all answers, not returned."""
    hits, totals = recall_hits(ids, truth)
    return 1.0 - float(hits.sum()) / max(int(totals.sum()), 1)


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(correct, {name: {"value", "limit"}}); a number without a limit, or a
    limit without a number, is not correct."""
    compared = {}
    ok = set(numbers) == set(limits)
    for name in sorted(set(numbers) | set(limits)):
        value: Optional[float] = numbers.get(name)
        limit: Optional[float] = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if value is None or limit is None or not (value <= limit):
            ok = False
    return ok, compared


def print_compared(compared: dict) -> None:
    """The numbers compared, one per line, as the last lines on stderr."""
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
