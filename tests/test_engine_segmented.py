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
folding (batch_vec="auto"); the merge buffer laid out on the device from
each bucket's results against the host remap and scatter it replaced; the
resident-LUT invariant (segmented pq never
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

from repro.core import HQIConfig, HQIIndex, planner
from repro.core.arena import PackedArena
from repro.core.ivf import IVFIndex, ScanStats
from repro.core.plan import EngineTask, PlanConfig, _next_pow2, build_plan
from repro.core.planner import batch_search_ivf, execute_plan
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


def _gather_on_host(monkeypatch, arena, q):
    """Put the host gather back in the executor's place: each bucket's Q and
    V tiles built in numpy from the rows ``_assemble_bucket`` lays out,
    then copied, as the executor did before its rows were resident."""
    assembled = []
    assemble = planner._assemble_bucket

    def recording(*args, **kw):
        assembled.append(assemble(*args, **kw))
        return assembled[-1]

    def host_gather(rows, q_dev, starts, qrow_of_d, lp):
        Vrows, _, qrow_of, _ = assembled[-1]
        Q = np.zeros(qrow_of.shape + (q.shape[1],), np.float32)
        live = qrow_of >= 0
        Q[live] = q[qrow_of[live]]
        return jnp.asarray(Q), jnp.asarray(arena.packed[Vrows])

    monkeypatch.setattr(planner, "_assemble_bucket", recording)
    monkeypatch.setattr(ops, "gather_unit_operands", host_gather)


@pytest.mark.parametrize("layout", ["dense", "segmented"])
@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "interpret"])
def test_resident_gather_matches_host_gather(layout, use_pallas, monkeypatch):
    """f32 execute_plan over the arena's resident rows answers bit for bit
    as the host gather does: two partitions, a task with a packed bitmap,
    and a unit whose padded rows run past the arena's last row."""
    rng = np.random.default_rng(29)
    d, m, k = 16, 19, 5
    parts = []
    for p, n in enumerate((300, 261)):
        vecs = rng.normal(size=(n, d)).astype(np.float32)
        ivf = IVFIndex.build(vecs, metric="l2", n_centroids=6, kmeans_iters=4, seed=p)
        parts.append((300 * p + np.arange(n, dtype=np.int64), ivf))
    arena = PackedArena.from_partitions(parts)
    q = rng.normal(size=(m, d)).astype(np.float32)
    tasks = [
        EngineTask(part=0, qrows=np.arange(0, 12, dtype=np.int64), nprobe=4,
                   packed_bitmap=arena.packed_bitmap(0, rng.random(300) < 0.5)),
        # every list of the last partition, so the arena's last list is scanned
        EngineTask(part=1, qrows=np.arange(7, m, dtype=np.int64), nprobe=6,
                   packed_bitmap=None),
    ]
    cfg = PlanConfig(tq_unit=8, min_list_pad=8, merge_layout=layout,
                     use_pallas=use_pallas, interpret=True if use_pallas else None)
    plan = build_plan(arena, tasks, q, m=m, k=k, cfg=cfg)
    tail = [int(arena.list_start[u.glist]) + lp - arena.n
            for lp, units in plan.buckets.items() for u in units]
    assert max(tail) > 0, "no unit's rows run past the arena's last row"
    got = execute_plan(plan, arena, q, cfg=cfg)
    _gather_on_host(monkeypatch, arena, q)
    want = execute_plan(plan, arena, q, cfg=cfg)
    assert_exact(got, want, f"{layout} pallas={use_pallas}")
    assert (got[1] >= 0).all()


def _host_remap_scatter(arena, plan, buckets, extra, layout, mode, kk):
    """The candidate buffer as the executor built it on the host before its
    scatter moved to the device: each bucket's top-k read back, the local
    indices mapped through the unit's packed rows (and ``arena.gid`` in
    f32), scattered at offsets[q] + slot (segmented) or [q, slot] (dense),
    the extras in the rows after each query's plan slots. Returns (scores,
    ids, seg_of) as the merge reads them (seg_of None in dense)."""
    m = plan.m
    extra_slots = np.zeros(m, dtype=np.int64)
    for qrows, _, _ in extra:
        extra_slots[qrows] += 1
    if layout == "segmented":
        counts = plan.seg_counts + extra_slots
        offsets = np.concatenate([[0], np.cumsum(counts)])
        C_pad = _next_pow2(int(offsets[-1]), 1)
        out_s = np.full((C_pad, kk), -np.inf, dtype=np.float32)
        out_i = np.full((C_pad, kk), -1, dtype=np.int64)
        seg_of = np.full(C_pad, m, dtype=np.int32)
        seg_of[: offsets[-1]] = np.repeat(np.arange(m, dtype=np.int32), counts)
    else:
        n_slots = plan.n_slots + int(extra_slots.max())
        out_s = np.full((m, n_slots, kk), -np.inf, dtype=np.float32)
        out_i = np.full((m, n_slots, kk), -1, dtype=np.int64)
        seg_of = None
    for Vrows, _, qrow_of, slot_of, s, i_loc in buckets:
        lp = Vrows.shape[1]
        w = s.shape[-1]
        wmask = qrow_of >= 0
        packed_rows = np.take_along_axis(
            np.broadcast_to(Vrows[:, None, :], i_loc.shape[:2] + (lp,)),
            np.maximum(i_loc, 0),
            axis=2,
        )
        ids = arena.gid[packed_rows] if mode == "f32" else packed_rows
        ids = np.where(i_loc < 0, -1, ids)
        qr, sl = qrow_of[wmask], slot_of[wmask]
        if layout == "segmented":
            out_s[offsets[qr] + sl, :w] = s[wmask]
            out_i[offsets[qr] + sl, :w] = ids[wmask]
        else:
            out_s[qr, sl, :w] = s[wmask]
            out_i[qr, sl, :w] = ids[wmask]
    next_extra = plan.seg_counts.copy() if layout == "segmented" else np.full(m, plan.n_slots)
    for qrows, es, ei in extra:
        w = min(kk, es.shape[1])
        slot = next_extra[qrows]
        next_extra[qrows] += 1
        if layout == "segmented":
            out_s[offsets[qrows] + slot, :w] = es[:, :w]
            out_i[offsets[qrows] + slot, :w] = ei[:, :w]
        else:
            out_s[qrows, slot, :w] = es[:, :w]
            out_i[qrows, slot, :w] = ei[:, :w]
    if layout == "dense":
        out_s, out_i = out_s.reshape(m, -1), out_i.reshape(m, -1)
    return out_s, out_i, seg_of


def _scatter_case(metric, mode, layout):
    """Two partitions, one task behind a bitmap and one over every list of
    the other, k above most list lengths (so results run out: i_loc = -1),
    queries that probe nothing, and host-side extras of two widths, one of
    them for a query with no plan slots."""
    rng = np.random.default_rng(31)
    d, m, k = 16, 21, 40
    parts = []
    for p, n in enumerate((300, 261)):
        vecs = rng.normal(size=(n, d)).astype(np.float32)
        ivf = IVFIndex.build(vecs, metric=metric, n_centroids=8, kmeans_iters=4, seed=p)
        parts.append((300 * p + np.arange(n, dtype=np.int64), ivf))
    arena = PackedArena.from_partitions(parts)
    if mode == "pq":
        arena.attach_pq(train_pq(arena.packed, 4, metric=metric, iters=4, seed=0))
    q = rng.normal(size=(m, d)).astype(np.float32)
    tasks = [
        EngineTask(part=0, qrows=np.arange(0, 12, dtype=np.int64), nprobe=4,
                   packed_bitmap=arena.packed_bitmap(0, rng.random(300) < 0.5)),
        # queries 17..20 probe nothing
        EngineTask(part=1, qrows=np.arange(7, 17, dtype=np.int64), nprobe=8,
                   packed_bitmap=None),
    ]

    def extras(qrows, width):
        s = -np.sort(rng.random((len(qrows), width)).astype(np.float32), axis=1)
        i = rng.integers(0, 561, size=(len(qrows), width)).astype(np.int64)
        i[:, -2:] = -1
        return np.asarray(qrows, dtype=np.int64), s, i

    extra = [extras([3, 17, 18], k), extras([3, 18], k - 5)]
    cfg = PlanConfig(tq_unit=8, min_list_pad=8, merge_layout=layout, scan_mode=mode,
                     refine_factor=2, use_pallas=False)
    plan = build_plan(arena, tasks, q, m=m, k=k, cfg=cfg)
    return arena, plan, q, cfg, extra


@pytest.mark.parametrize("layout", ["segmented", "dense"])
@pytest.mark.parametrize("mode", ["f32", "pq"])
@pytest.mark.parametrize("metric", ["ip", "l2"])
def test_device_scatter_matches_host_remap_and_scatter(metric, mode, layout, monkeypatch):
    """Each bucket's results kept on the device as candidate rows
    (``ops.unit_topk_rows``) and laid out as the merge buffer in one gather
    (``ops.gather_candidates``) fill it bit for bit as the host remap and
    scatter did: scores, values (arena rows, mapped to ids after the merge
    in f32; a host-side extra's id stored as N + id) and the segments the
    merge reads, so the answers are the host path's too. Pad units,
    results that run out, k above the list length, empty segments and
    host-side extras all occur."""
    arena, plan, q, cfg, extra = _scatter_case(metric, mode, layout)
    buckets, merges = [], []
    assemble, unit_rows = planner._assemble_bucket, ops.unit_topk_rows
    seg_merge, final_merge = ops.segmented_merge_topk, ops.merge_topk

    def recording_assemble(*args, **kw):
        buckets.append(list(assemble(*args, **kw)))
        return tuple(buckets[-1])

    def recording_rows(s, i_loc, *rest, **kw):
        buckets[-1] += [np.asarray(s), np.asarray(i_loc)]
        return unit_rows(s, i_loc, *rest, **kw)

    def recording_seg(flat_s, flat_i, seg_of, n_segments, k):
        merges.append((np.asarray(flat_s), np.asarray(flat_i), np.asarray(seg_of)))
        return seg_merge(flat_s, flat_i, seg_of, n_segments, k)

    def recording_final(scores, idx, k):
        merges.append((np.asarray(scores), np.asarray(idx), None))
        return final_merge(scores, idx, k)

    monkeypatch.setattr(planner, "_assemble_bucket", recording_assemble)
    monkeypatch.setattr(ops, "unit_topk_rows", recording_rows)
    monkeypatch.setattr(ops, "segmented_merge_topk", recording_seg)
    monkeypatch.setattr(ops, "merge_topk", recording_final)
    got = execute_plan(plan, arena, q, cfg=cfg, extra=extra)
    assert len(buckets) == len(plan.buckets) and all(len(b) == 6 for b in buckets)
    # f32 merges the extras with the scan; pq's stage A merges the ADC
    # candidates alone (its extras fold after the re-rank)
    got_s, got_v, got_seg = merges[0]
    k = plan.k * (2 if mode == "pq" else 1)
    want_s, want_i, want_seg = _host_remap_scatter(
        arena, plan, buckets, extra if mode == "f32" else (), layout, mode, k
    )
    if mode == "f32":
        n = arena.n
        got_i = np.where(got_v >= n, got_v - n, arena.gid[np.clip(got_v, 0, n - 1)])
        got_i = np.where(got_v < 0, -1, got_i)
    else:
        got_i = got_v
    assert np.array_equal(got_s, want_s), "scores diverge"
    assert np.array_equal(got_i, want_i), "ids diverge"
    if layout == "segmented":
        assert np.array_equal(got_seg, want_seg), "segments diverge"
        want = seg_merge(jnp.asarray(want_s), jnp.asarray(want_i), jnp.asarray(want_seg),
                         plan.m, plan.k)
    else:
        want = final_merge(jnp.asarray(want_s), jnp.asarray(want_i), plan.k)
    if mode == "f32":
        assert_exact(got, tuple(np.asarray(x) for x in want), f"{metric}/{layout}")
    assert (got[1][19:] == -1).all() and np.isneginf(got[0][19:]).all()
    # the cases the buffer has to get right all occur
    assert any((b[2] < 0).all(axis=1).any() for b in buckets), "no pad unit"
    assert any((b[5][b[2] >= 0] < 0).any() for b in buckets), "no result ran out"
    lens = arena.list_len[[u.glist for units in plan.buckets.values() for u in units]]
    assert (lens < plan.k).any(), "k within every list"
    assert (plan.seg_counts == 0).any(), "no empty segment"


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
