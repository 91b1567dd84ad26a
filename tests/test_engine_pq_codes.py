"""The codes-only IVF-PQ layout against a plain reference, at a small seeded
size on the CPU.

``HQIIndex.search(scan_mode="pq")`` keeps the PQ codes and the codebook on
the device and the f32 rows in host memory, read only by the exact re-rank.
Its answers must be those of a plain filtered IVF-PQ search with refine,
written here in numpy: the same probed lists, ADC scores through
``decode_pq``, the k' best per query, an exact re-rank of those, top k —
for an M that is not a multiple of 8 (d = 40, M = 10), both merge layouts
and both backends. The layout itself is checked on the tracer's copy spans:
one ``arena.h2d`` of N·M code bytes per arena, no f32 rows uploaded, per
bucket a ``scan.h2d`` of list starts, LUT rows and bitmap only, and a
snapshot that holds no device array.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import HQIConfig, HQIIndex, PackedArena, PlanConfig
from repro.core.plan import _next_pow2
from repro.core.pq import decode_pq
from repro.core.predicates import evaluate_filter
from repro.obs import trace

from conftest import small_db, small_workload

D, M, K, NPROBE, REFINE = 40, 10, 10, 3, 4


def build(db, wl):
    cfg = HQIConfig(min_partition_size=256, max_leaves=8, scan_mode="pq", pq_m=M,
                    refine_factor=REFINE, plan=PlanConfig(tq_unit=16))
    return HQIIndex.build(db, wl, cfg)


@pytest.fixture(scope="module", params=["ip", "l2"])
def built(request):
    db = small_db(n=3000, d=D, seed=11, metric=request.param)
    wl = small_workload(db, n_queries=120, seed=12, k=K)
    return db, wl, build(db, wl)


def _score(metric, x, q):
    """float64 scores, higher is better: x @ q (ip) or -|q - x|^2 (l2)."""
    x, q = x.astype(np.float64), q.astype(np.float64)
    return x @ q if metric == "ip" else -((x - q) ** 2).sum(axis=1)


def plain_ivfpq_refine(index, db, wl, nprobe, kprime):
    """Filtered IVF-PQ with an exact refine stage, in numpy: per query, in
    every partition that holds a row passing its template, the ``nprobe``
    lists whose centroids score best; their passing rows scored by ADC
    (the query against each row's decoded code); the k' best re-scored
    exactly on the f32 rows; the top k of those."""
    arena = index.arena
    codes = np.empty((db.n, arena.codes.shape[1]), np.uint8)
    codes[arena.gid] = arena.codes  # the index's stored codes, by row
    approx = decode_pq(index.pq, codes)
    masks = [evaluate_filter(t, db) for t in wl.templates]
    ids = np.full((wl.m, wl.k), -1, np.int64)
    scores = np.full((wl.m, wl.k), -np.inf, np.float32)
    for qi, (q, t) in enumerate(zip(wl.vectors, wl.template_of)):
        cand = []
        for part in index.partitions:
            ivf, rows = part.ivf, np.asarray(part.rows)
            if not masks[t][rows].any():
                continue
            offsets = np.asarray(ivf.offsets)
            cents = np.asarray(ivf.centroids)[: len(offsets) - 1]
            for li in np.argsort(-_score(db.metric, cents, q), kind="stable")[:nprobe]:
                members = rows[np.asarray(ivf.order)[offsets[li]:offsets[li + 1]]]
                cand.append(members[masks[t][members]])
        cand = np.concatenate(cand) if cand else np.zeros(0, np.int64)
        best = cand[np.argsort(-_score(db.metric, approx[cand], q), kind="stable")[:kprime]]
        exact = _score(db.metric, db.vectors[best], q)
        top = np.argsort(-exact, kind="stable")[: wl.k]
        ids[qi, : len(top)] = best[top]
        scores[qi, : len(top)] = exact[top]
    return ids, scores


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("layout", ["segmented", "dense"])
def test_pq_search_is_the_plain_ivfpq_refine(built, layout, use_pallas):
    db, wl, index = built
    plan = index.cfg.plan
    index.cfg.plan = dataclasses.replace(plan, merge_layout=layout, use_pallas=use_pallas)
    try:
        res = index.search(wl, nprobe=NPROBE)
    finally:
        index.cfg.plan = plan
    want_i, want_s = plain_ivfpq_refine(index, db, wl, NPROBE, REFINE * K)
    np.testing.assert_array_equal(res.ids, want_i)
    live = want_i >= 0
    np.testing.assert_allclose(res.scores[live], want_s[live], rtol=1e-5, atol=1e-4)


def _h2d(events, name):
    return [ev for ev in events if ev.get("ph") == "X" and ev["name"] == name]


def test_codes_only_layout(built, monkeypatch):
    db, wl, _ = built
    index = build(db, wl)  # a fresh arena, which uploads inside the traced search

    def no_rows(self):
        raise AssertionError("f32 rows uploaded in pq mode")

    monkeypatch.setattr(PackedArena, "device_rows", no_rows)
    tracer = trace.enable()
    try:
        first = index.search(wl, nprobe=NPROBE)
        again = index.search(wl, nprobe=NPROBE)
    finally:
        trace.disable()
    np.testing.assert_array_equal(first.ids, again.ids)
    events = tracer.events()
    arena = index.arena
    n, m = arena.codes.shape
    # the codes ship once per arena, N·M bytes; the rows never do
    assert [ev["args"]["bytes"] for ev in _h2d(events, "arena.h2d")] == [n * m]
    assert arena._rows_dev is None and arena._codes_dev is not None
    # each bucket ships its list starts (i32), LUT rows (i32) and bitmap
    # (bool): no code and no table crosses per bucket
    ships = [ev["args"]["bytes"] for ev in _h2d(events, "scan.h2d")]
    scans = [ev["args"] for ev in events if ev.get("ph") == "X" and ev["name"] == "dispatch.scan"]
    tq = index.cfg.plan.tq_unit
    want = [_next_pow2(a["units"], 1) * (4 + 4 * tq + a["lp"]) for a in scans]
    assert ships == want and len(ships) >= 2
    # the tables are built on the device from the queries and the resident
    # codebook, once an execute
    assert len(_h2d(events, "codebook.h2d")) == 1
    assert len([ev for ev in events if ev.get("ph") == "X" and ev["name"] == "scan.adc"]) == 2
    # the only f32 rows that cross are the re-rank's candidates
    rerank = _h2d(events, "rerank.h2d")
    assert len(rerank) == 2
    mp = _next_pow2(wl.m, 1)
    assert rerank[0]["args"]["bytes"] == mp * D * 4 + mp * REFINE * K * (D * 4 + 1)
    # the snapshot holds host arrays only
    leaves = jax.tree_util.tree_leaves(index.to_state())
    assert not any(isinstance(x, jax.Array) for x in leaves)
