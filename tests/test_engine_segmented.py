"""Segmented candidate pipeline: the flat CSR merge must be BIT-IDENTICAL
to the dense slot-rectangular layout it replaces.

The acceptance bar is exact equality of ids AND scores (``np.array_equal``,
not the tie-tolerant conftest helper): the segmented scatter preserves each
query's slot-major candidate order and the segmented merge's first-in-order
tie break reproduces ``lax.top_k``'s smallest-index tie rule, so nothing — not even
exact-tie ordering — may diverge.

Covered: {ip, l2} × {f32, pq} engine parity with forced score ties and
bitmap pushdown; skewed per-template routing through HQIIndex (1-vs-all
nprobe dicts → ragged segment widths); empty segments (templates matching
nothing); k larger than every segment; the adaptive executor's extras
folding (batch_vec="auto"); the resident-LUT invariant (segmented pq never
materializes a [W, TQ, M, 256] operand: DispatchStats.lut_expand_bytes == 0);
kernel-level oracle checks for ``segmented_merge_topk`` and the streamed
Pallas ADC grid; and a hypothesis property over random segment shapes.
Mesh parity for the segmented layout lives in test_engine_sharded.py
(test_sharded_merge_layout_parity) — jax device pools need a subprocess.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import HQIConfig, HQIIndex
from repro.core.ivf import IVFIndex, ScanStats
from repro.core.plan import PlanConfig
from repro.core.planner import batch_search_ivf
from repro.core.pq import train_pq
from repro.core.types import Workload
from repro.kernels import ops, ref

from conftest import small_db, small_workload


def _tied_db(metric, seed=0):
    """small_db with duplicated vector blocks so exact score ties occur."""
    db = small_db(n=900, seed=seed, metric=metric)
    db.vectors[100:120] = db.vectors[0]  # 21 identical rows -> guaranteed ties
    db.vectors[400:408] = db.vectors[3]
    return db


def _cfg(layout, mode):
    return PlanConfig(
        tq_unit=8,
        min_list_pad=8,
        use_pallas=False,
        scan_mode=mode,
        refine_factor=2,
        merge_layout=layout,
    )


def assert_exact(a, b, ctx=""):
    (a_s, a_i), (b_s, b_i) = a, b
    assert np.array_equal(a_s, b_s), f"scores diverge: {ctx}"
    assert np.array_equal(a_i, b_i), f"ids diverge: {ctx}"


@pytest.mark.parametrize("metric", ["ip", "l2"])
@pytest.mark.parametrize("mode", ["f32", "pq"])
def test_segmented_vs_dense_engine_parity(metric, mode):
    """batch_search_ivf: segmented == dense bit-for-bit, with ties and
    bitmap pushdown, across metrics and both scan modes."""
    rng = np.random.default_rng(17)
    db = _tied_db(metric)
    ivf = IVFIndex.build(db.vectors, metric=metric, n_centroids=16, seed=0)
    pq = train_pq(db.vectors, 4, metric=metric, iters=4, seed=0) if mode == "pq" else None
    q = rng.normal(size=(23, db.d)).astype(np.float32)
    q[5] = db.vectors[0]  # lands on the duplicated block: top-k is all ties
    for bitmap in (None, rng.random(db.n) < 0.4):
        dense = batch_search_ivf(
            ivf, q, nprobe=6, k=5, bitmap=bitmap, cfg=_cfg("dense", mode), pq=pq
        )
        seg = batch_search_ivf(
            ivf, q, nprobe=6, k=5, bitmap=bitmap, cfg=_cfg("segmented", mode), pq=pq
        )
        assert_exact(seg, dense, f"{metric}/{mode} bitmap={bitmap is not None}")


def _search_layout(hqi, wl, layout, **kw):
    prev = hqi.cfg.plan.merge_layout
    hqi.cfg.plan.merge_layout = layout
    try:
        return hqi.search(wl, **kw)
    finally:
        hqi.cfg.plan.merge_layout = prev


@pytest.mark.parametrize("mode", ["f32", "pq"])
def test_segmented_hqi_skewed_routing_parity(mode):
    """Skewed per-template nprobe (one heavy template, the rest nprobe=1)
    makes segment widths ragged — exactly the shape the dense layout pads
    for. Results must still be bit-identical, through the full HQI path
    (multi-partition arena, template bitmaps, final fold)."""
    db = small_db(n=1500, seed=4)
    wl = small_workload(db, n_queries=48, seed=2)
    hqi = HQIIndex.build(
        db,
        wl,
        HQIConfig(
            min_partition_size=128, max_leaves=32,
            scan_mode=mode, refine_factor=2,
        ),
    )
    nprobe = {t: (12 if t == 0 else 1) for t in range(len(wl.templates))}
    for batch_vec in (True, "auto"):
        dense = _search_layout(hqi, wl, "dense", nprobe=nprobe, batch_vec=batch_vec)
        seg = _search_layout(hqi, wl, "segmented", nprobe=nprobe, batch_vec=batch_vec)
        assert np.array_equal(dense.scores, seg.scores), (mode, batch_vec)
        assert np.array_equal(dense.ids, seg.ids), (mode, batch_vec)
    # the skewed plan really is ragged: raggedness is what this test is about
    st = ScanStats()
    tasks, _, _ = hqi._engine_tasks(wl, nprobe=nprobe, batch_vec=True, stats=st)
    from repro.core.plan import build_plan

    plan = build_plan(hqi.arena, tasks, wl.vectors, m=wl.m, k=wl.k, cfg=hqi.cfg.plan)
    counts = plan.seg_counts
    assert counts.max() > counts.min(), "nprobe dict failed to skew segments"


def test_segmented_empty_segments():
    """Queries whose template matches nothing contribute zero-width segments
    and must come back as exactly (-inf, -1) rows — same as dense."""
    from repro.core.predicates import Between, make_filter

    db = small_db(n=600, seed=9)
    wl = small_workload(db, n_queries=24, seed=3)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=128, max_leaves=16))
    templates = [make_filter(Between("A", 5.0, 6.0)), make_filter()]  # A in [0,1): empty
    wl2 = Workload(
        vectors=wl.vectors[:10],
        templates=templates,
        template_of=(np.arange(10) % 2).astype(np.int32),
        k=4,
    )
    dense = _search_layout(hqi, wl2, "dense", nprobe=6)
    seg = _search_layout(hqi, wl2, "segmented", nprobe=6)
    assert np.array_equal(dense.scores, seg.scores)
    assert np.array_equal(dense.ids, seg.ids)
    empty = np.arange(10) % 2 == 0
    assert (seg.ids[empty] == -1).all()
    assert np.isneginf(seg.scores[empty]).all()


@pytest.mark.parametrize("mode", ["f32", "pq"])
def test_segmented_k_exceeds_segment_width(mode):
    """k larger than any posting list: every segment is narrower than k, so
    the merge must pad — identically in both layouts."""
    db = small_db(n=300, seed=5)
    ivf = IVFIndex.build(db.vectors, metric=db.metric, n_centroids=32, seed=0)
    pq = train_pq(db.vectors, 8, metric=db.metric, seed=0) if mode == "pq" else None
    rng = np.random.default_rng(5)
    q = rng.normal(size=(9, db.d)).astype(np.float32)
    k = 64  # lists average ~10 rows
    dense = batch_search_ivf(ivf, q, nprobe=3, k=k, cfg=_cfg("dense", mode), pq=pq)
    seg = batch_search_ivf(ivf, q, nprobe=3, k=k, cfg=_cfg("segmented", mode), pq=pq)
    assert_exact(seg, dense, f"k>width {mode}")
    assert (seg[1] == -1).any()  # padding must actually occur


def test_segmented_pq_never_expands_lut():
    """The resident-LUT invariant: segmented pq dispatch indexes the [U, M,
    256] table in-kernel and must NEVER materialize the dense [W, TQ, M, 256]
    expansion — lut_expand_bytes stays 0 (and is nonzero for dense)."""
    db = small_db(n=900, seed=1)
    wl = small_workload(db, n_queries=32, seed=1)
    hqi = HQIIndex.build(
        db, wl,
        HQIConfig(min_partition_size=128, max_leaves=32, scan_mode="pq", refine_factor=2),
    )
    ops.reset_dispatch_stats()
    res_seg = _search_layout(hqi, wl, "segmented", nprobe=6)
    st = ops.dispatch_stats()
    assert st.lut_expand_bytes == 0, st.lut_expand_bytes
    assert st.peak_candidate_bytes > 0
    # the per-search observability surfaces through SearchResult
    assert res_seg.peak_candidate_bytes > 0
    assert res_seg.lut_bytes > 0  # resident table bytes are still accounted

    ops.reset_dispatch_stats()
    res_dense = _search_layout(hqi, wl, "dense", nprobe=6)
    st = ops.dispatch_stats()
    assert st.lut_expand_bytes > 0  # dense pays the expanded operand
    assert res_dense.lut_bytes > res_seg.lut_bytes


def test_build_plan_emits_seg_counts():
    """build_plan's seg_counts are the per-query REAL slot counts: they sum
    to the total routed (query, list) pairs and max out at n_slots."""
    from repro.core.plan import build_plan

    db = small_db(n=800, seed=2)
    wl = small_workload(db, n_queries=30, seed=2)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=128, max_leaves=16))
    st = ScanStats()
    nprobe = {t: (10 if t == 0 else 2) for t in range(len(wl.templates))}
    tasks, _, _ = hqi._engine_tasks(wl, nprobe=nprobe, batch_vec=True, stats=st)
    plan = build_plan(hqi.arena, tasks, wl.vectors, m=wl.m, k=wl.k, cfg=hqi.cfg.plan)
    counts = plan.seg_counts
    assert counts.shape == (wl.m,)
    assert counts.max() == plan.n_slots
    # slots are allocated per probed list (a bitmap-killed or empty list still
    # consumes its slot as -inf padding), so seg_counts bounds the emitted
    # work-unit rows from above and every unit's slot lands inside its segment
    total = sum(len(u.qrows) for units in plan.buckets.values() for u in units)
    assert counts.sum() >= total > 0
    for units in plan.buckets.values():
        for u in units:
            assert (u.slots < counts[u.qrows]).all()


# --------------------------------------------------------------------------
# kernel-level oracles


def _dense_merge_emulation(flat_s, flat_i, counts, k):
    """Scatter flat rows into the dense [m, n_slots, kk] layout and reduce
    with lax.top_k — the exact computation the dense merge performs."""
    m = len(counts)
    kk = flat_s.shape[1]
    n_slots = int(max(counts.max(), 1)) if m else 1
    ds = np.full((m, n_slots, kk), -np.inf, np.float32)
    di = np.full((m, n_slots, kk), -1, np.int64)
    r = 0
    for q in range(m):
        for sl in range(counts[q]):
            ds[q, sl], di[q, sl] = flat_s[r], flat_i[r]
            r += 1
    ds, di = ds.reshape(m, -1), di.reshape(m, -1)
    keff = min(k, ds.shape[1])
    top, pos = jax.lax.top_k(jnp.asarray(ds), keff)
    oi = jnp.take_along_axis(jnp.asarray(di), pos.astype(jnp.int64), axis=1)
    top, oi = ref.normalize_merge_sentinels(top, oi)
    if keff < k:
        top = jnp.pad(top, ((0, 0), (0, k - keff)), constant_values=-np.inf)
        oi = jnp.pad(oi, ((0, 0), (0, k - keff)), constant_values=-1)
    return np.asarray(top), np.asarray(oi)


def _random_segments(rng, m, kk):
    """Random ragged candidate rows with sentinel flavors and heavy ties."""
    counts = rng.integers(0, 5, size=m)
    C = int(counts.sum())
    flat_s = rng.choice(
        [-np.inf, float(-3.4e38), 0.0, 1.0, 2.0], size=(C, kk)
    ).astype(np.float32)
    flat_i = rng.integers(-1, 50, size=(C, kk)).astype(np.int64)
    flat_i = np.where(np.isneginf(flat_s), -1, flat_i)
    seg_of = np.repeat(np.arange(m), counts).astype(np.int32)
    return counts, flat_s, flat_i, seg_of


@pytest.mark.parametrize("merge", [ops.segmented_merge_topk, ref.segmented_merge_topk_ref])
def test_segmented_merge_matches_dense_merge(merge):
    """segmented_merge_topk (and the sort-based oracle it replaces on the
    device) == the dense scatter + lax.top_k emulation, bit-for-bit, over
    random ragged shapes with ties and both sentinel flavors (incl. empty
    segments and k > width)."""
    rng = np.random.default_rng(1)
    for trial in range(60):
        m = int(rng.integers(1, 6))
        k = int(rng.integers(1, 5))
        kk = int(rng.integers(1, 4))
        counts, flat_s, flat_i, seg_of = _random_segments(rng, m, kk)
        want_s, want_i = _dense_merge_emulation(flat_s, flat_i, counts, k)
        got_s, got_i = merge(
            jnp.asarray(flat_s), jnp.asarray(flat_i), jnp.asarray(seg_of), m, k
        )
        assert np.array_equal(np.asarray(got_i), want_i), trial
        assert np.array_equal(np.asarray(got_s), want_s), trial


def test_segmented_merge_pad_rows_dropped():
    """Rows tagged seg >= n_segments (flat-buffer pow2 padding) never leak
    into any segment's result."""
    flat_s = np.array([[5.0], [9.0]], np.float32)
    flat_i = np.array([[7], [8]], np.int64)
    seg_of = np.array([0, 1], np.int32)  # row 1 belongs to pad segment
    s, i = ops.segmented_merge_topk(
        jnp.asarray(flat_s), jnp.asarray(flat_i), jnp.asarray(seg_of), 1, 2
    )
    assert np.asarray(i).tolist() == [[7, -1]]
    assert np.asarray(s)[0, 0] == 5.0 and np.isneginf(np.asarray(s)[0, 1])


def test_pq_streamed_kernel_matches_ref():
    """The DMA-streamed ADC grid == the expanded-LUT reference:
    per-row DMA from the resident table must not change a single score."""
    from repro.core.pq import PQIndex, adc_tables
    from repro.kernels import pq_scan
    from repro.kernels import ref as kref

    rng = np.random.default_rng(7)
    m, d, w, tq, nv, k = 4, 32, 3, 5, 90, 6
    vecs = rng.normal(size=(400, d)).astype(np.float32)
    idx = PQIndex.build(vecs, m=m)
    U = 11
    table = np.stack(
        [adc_tables(idx.cb, rng.normal(size=(1, d)).astype(np.float32))[0] for _ in range(U)]
    )
    lut_idx = rng.integers(0, U, size=(w, tq)).astype(np.int32)
    codes = np.stack([idx.codes[rng.integers(0, len(vecs), nv)] for _ in range(w)])
    valid = rng.random((w, nv)) > 0.3
    luts_expanded = table[lut_idx]  # [W, TQ, M, 256]
    s_ref, i_ref = kref.workunit_pq_topk_ref(
        jnp.asarray(luts_expanded), jnp.asarray(codes), jnp.asarray(valid), k
    )
    s_st, i_st = pq_scan.workunit_pq_scan_streamed(
        jnp.asarray(table), jnp.asarray(lut_idx), jnp.asarray(codes),
        jnp.asarray(valid), k=k, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(s_st), np.asarray(s_ref), rtol=1e-4, atol=1e-4)
    for w_ in range(w):
        for r in range(tq):
            a, b = np.asarray(i_ref)[w_, r], np.asarray(i_st)[w_, r]
            assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist()), (w_, r)


def test_segmented_merge_property():
    """Hypothesis: over arbitrary segment shapes / scores / duplicate ids,
    segmented merge == dense emulation bit-for-bit."""
    hyp = pytest.importorskip("hypothesis", reason="property tests need the [test] extra")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=40)
    @given(
        seed=st.integers(0, 2**31 - 1),
        m=st.integers(1, 7),
        k=st.integers(1, 6),
        kk=st.integers(1, 4),
    )
    def check(seed, m, k, kk):
        rng = np.random.default_rng(seed)
        counts, flat_s, flat_i, seg_of = _random_segments(rng, m, kk)
        want_s, want_i = _dense_merge_emulation(flat_s, flat_i, counts, k)
        got_s, got_i = ops.segmented_merge_topk(
            jnp.asarray(flat_s), jnp.asarray(flat_i), jnp.asarray(seg_of), m, k
        )
        assert np.array_equal(np.asarray(got_i), want_i)
        assert np.array_equal(np.asarray(got_s), want_s)

    check()
