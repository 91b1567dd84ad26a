"""Faults planted under the timed path, which ``correct`` has to catch.

The CPU tests (``tests/bench/test_bench_faults.py``) plant each one in a
whole run at a tiny size; ``bench/control.py --fault <name>`` plants one
in runs at the cell's own size on the chip, where the limits are read. The
benchmark's own runs never plant one.

Each fault takes ``setattr`` (``pytest.MonkeyPatch.setattr``, or the
builtin where the process ends with the run) and patches the program in
this process:

* ``half_the_batch``: the search answers the first half of the log only;
* ``altered_answer``: every returned id is moved to the next row;
* ``merge_shifted``: the segmented and final merges keep ranks 2..k+1 of
  each query's candidates, not 1..k: real rows, exact scores, best first,
  the wrong rows;
* ``scan_skips_rows``: the f32 scan skips every other row of each work
  unit's posting-list rows.
"""
from __future__ import annotations

import numpy as np


def half_the_batch(setattr) -> None:
    from repro.core.hqi import HQIIndex

    real = HQIIndex.search

    def search(self, wl, **kw):
        return real(self, wl.subset(np.arange(wl.m // 2)), **kw)

    setattr(HQIIndex, "search", search)


def altered_answer(setattr) -> None:
    from repro.core.hqi import HQIIndex

    real = HQIIndex.search

    def search(self, wl, **kw):
        res = real(self, wl, **kw)
        res.ids = np.where(res.ids >= 0, (res.ids + 1) % self.db.n, res.ids)
        return res

    setattr(HQIIndex, "search", search)


def merge_shifted(setattr) -> None:
    from repro.kernels import ops

    segmented, final = ops.segmented_merge_topk, ops.merge_topk

    def segmented_merge_topk(flat_s, flat_i, seg_of, n_segments, k):
        s, i = segmented(flat_s, flat_i, seg_of, n_segments, k + 1)
        return s[:, 1:], i[:, 1:]

    def merge_topk(scores, idx, k):
        s, i = final(scores, idx, k + 1)
        return s[:, 1:], i[:, 1:]

    setattr(ops, "segmented_merge_topk", segmented_merge_topk)
    setattr(ops, "merge_topk", merge_topk)


def scan_skips_rows(setattr) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops

    real = ops.workunit_topk

    def workunit_topk(q, v, valid, k, **kw):
        keep = (jnp.arange(valid.shape[1]) % 2 == 0)[None, :]
        return real(q, v, jnp.logical_and(valid, keep), k, **kw)

    setattr(ops, "workunit_topk", workunit_topk)


FAULTS = {f.__name__: f for f in (half_the_batch, altered_answer, merge_shifted, scan_skips_rows)}
