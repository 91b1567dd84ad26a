"""The useful work of a filtered IVF scan, counted by the benchmark.

The index answers each query exactly over the posting lists it probes: in
every partition that holds a row passing the query's template, the
``nprobe`` lists whose centroids score best. The useful work of one pass is
then

* pairs: (query, row) pairs with the row in one of the query's probed
  lists and passing its template; FLOPs = 2 d per pair;
* bytes: every row of some probed list that passes the template of some
  query probing that list, read once, plus the queries (f32).

This reads the index's partition rows, list centroids and list membership,
and counts with its own arithmetic, so padding, tiling, where the rows live
or how the scan is computed cannot change it. A scan that did less than
this would not give the index's answers, so the least time it implies
(``peaks.least_time``) bounds any scan of it from below.
"""
from __future__ import annotations

import numpy as np


def scan_work(index, masks: np.ndarray, queries: np.ndarray, template_of: np.ndarray,
              nprobe: int, metric: str) -> dict:
    """{"pairs", "rows", "flops", "bytes"} of one pass of the query log."""
    d = queries.shape[1]
    T = masks.shape[0]
    q_of_t = [np.nonzero(template_of == t)[0] for t in range(T)]
    pairs = 0
    rows_read = 0
    for part in index.partitions:
        rows = np.asarray(part.rows)
        ivf = part.ivf
        offsets = np.asarray(ivf.offsets)
        n_lists = len(offsets) - 1
        list_of_local = np.empty(len(rows), dtype=np.int64)
        list_of_local[np.asarray(ivf.order)] = np.repeat(np.arange(n_lists), np.diff(offsets))
        cents = np.asarray(ivf.centroids, dtype=np.float32)[:n_lists]
        probed_any = np.zeros((T, n_lists), dtype=bool)
        local_masks = masks[:, rows]  # [T, |P|]
        for t in range(T):
            qs = q_of_t[t]
            if len(qs) == 0 or not local_masks[t].any():
                continue
            q = queries[qs]
            if metric == "ip":
                score = q @ cents.T
            else:
                score = 2.0 * (q @ cents.T) - np.sum(cents * cents, axis=1)[None, :]
            npr = min(int(nprobe), n_lists)
            probed = np.argpartition(-score, npr - 1, axis=1)[:, :npr]
            per_list = np.bincount(list_of_local[local_masks[t]], minlength=n_lists)
            pairs += int(per_list[probed].sum())
            probed_any[t, np.unique(probed)] = True
        useful = (probed_any[:, list_of_local] & local_masks).any(axis=0)
        rows_read += int(useful.sum())
    nbytes = 4 * d * (rows_read + len(queries))
    return {"pairs": pairs, "rows": rows_read, "flops": 2.0 * d * pairs, "bytes": float(nbytes)}
