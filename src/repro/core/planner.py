"""Stage 2 of the execution engine: megabatched execution of a global plan.

``plan.py`` (stage 1) turns a whole workload into one ``ExecutionPlan`` whose
work units are bucketed by padded shape across every partition and template.
This module executes that plan:

  1. for each shape bucket, gather ALL its units' posting-list rows through
     the index-wide ``PackedArena`` (one gather serves every partition; in
     f32 mode on the device, from the arena's resident rows) and run them
     in a single ``kernels.ops.workunit_topk`` dispatch — the
     single-matmul-per-posting-list of Alg. 3 line 10, fused with the
     Section 4.2 bitmap pushdown, megabatched across the workload;
  2. keep each bucket's per-unit top-k on the device as a block of
     candidate rows (scores and arena rows, ``ops.unit_topk_rows``), and
     lay the blocks out as the candidate buffer in one device gather
     (``ops.gather_candidates``) from the buffer row of every unit slot,
     which the host knows from the plan. By default the buffer is a flat
     segmented (CSR-style) [Σ seg_counts, k] buffer whose per-query segment
     widths come from ``ExecutionPlan.seg_counts``
     (``merge_layout="segmented"``); ``merge_layout="dense"`` keeps the
     legacy [m, n_slots, k] layout padded to the widest query. Any per-query
     scan results the adaptive executor produced host-side are one more
     block;
  3. reduce candidates to the final per-query top-k with ONE device-side
     reduction (``ops.segmented_merge_topk`` / ``ops.merge_topk``) — Alg. 3
     line 12 for the whole workload, replacing the per-(template ×
     partition) numpy merge loop — map its arena rows to ids through the
     arena's resident ``gid``, and read back only that top-k, once an
     execute. Both layouts are bit-identical: the segmented merge's
     first-in-order tie break reproduces ``lax.top_k``'s tie rule over the
     same slot-major candidate order (tests/test_engine_segmented.py).

Compressed execution (``PlanConfig.scan_mode="pq"``): the scan stage reads
the arena's uint8 PQ codes instead of raw f32 vectors — the codes are
resident on the device (``PackedArena.device_codes``), each bucket's code
tiles are gathered there (``ops.gather_unit_codes``), and each bucket is one
ADC dispatch producing ``refine_factor · k`` candidates per (query, posting
list) against tables built on the device (``scan.adc``), kept on the device
as packed rows. Candidates from all buckets then merge per query (one
device merge), the survivors' rows are read back, their f32 rows are
gathered from host memory ONCE (the f32 rows never go to the device in this
mode), and a single ``workunit_topk`` dispatch re-ranks them exactly —
so dispatch cost stays O(#buckets) + 1 re-rank, never O(T×L), while scan HBM
traffic drops by d·4/M× (e.g. 32× at d=64, M=8). Bitmap pushdown composes
unchanged: the ADC stage applies the same ``valid`` mask, so re-rank
candidates already satisfy every predicate. The final merge still folds in
the adaptive executor's host-side (exact) candidates, which is sound because
re-ranked scores are exact too.

Dispatch cost is O(#buckets) ≤ ``PlanConfig.max_bucket_shapes`` instead of
O(T×L). In f32 mode every (query, posting-list) pair is evaluated exactly
once and each vector lives in exactly one list, so results are identical to
the per-query scan — tests assert equality of scores and candidate sets. In
pq mode that uniqueness also means the candidate union is duplicate-free.

Sharded execution (``execute_plan_sharded``): the same two stages across a
device mesh — each rank dispatches its shard's work units per bucket inside
one ``shard_map``, and the cross-rank merge is an all-gather of per-query
top-k candidates (``ops.sharded_merge_topk``, O(k·|model|) traffic). Results
are bit-identical to ``execute_plan``; ``core/distributed.py`` is the thin
mesh entry.

Memory: the segmented layout holds Σ seg_counts·k candidate rows instead of
m·n_slots·k, so queries routed to few partitions no longer pay for the
widest query's slots; on the sharded path each rank contributes only its
REAL segments to the pre-gather merge (Σ per-rank segments·k, vs the dense
[R, m, n_slots, k] stack). The pq path additionally keeps the workload's
ADC tables resident as one [U, M, 256] array and indexes them from inside
the kernel (``workunit_pq_topk_resident`` / the DMA-streamed Pallas
grid), never materializing the per-bucket [W, TQ, M, 256] expansion the
dense layout pays (``DispatchStats.lut_expand_bytes`` stays 0). Remaining
dense-stacking tax: sharded scan *operands* still ship [R, W, ...] per
bucket where W is the MAX per-rank unit count, so a shard-skewed unit
distribution transfers mostly-masked slices for the light ranks (ROADMAP).

``batch_search_ivf`` survives as the single-index entry point (used by the
baselines and benchmarks): it wraps the index in a one-partition arena,
builds a one-task plan, and executes it.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..obs.profile import get_profiler
from ..obs.trace import fence, get_tracer, to_device, to_host
from .arena import PackedArena, ShardedArena
from .ivf import IVFIndex, ScanStats
from .plan import (
    EngineTask,
    ExecutionPlan,
    PlanConfig,
    ShardedPlan,
    WorkUnit,
    build_plan,
    _next_pow2,
)
from ..kernels.pq_scan import subspace_pad
from .pq import PQCodebook, adc_tables, adc_tables_device

# Extra per-query candidates merged alongside the plan's output (the adaptive
# executor's host-side scans): (qrows i64 [mq], scores f32 [mq, k], ids i64 [mq, k])
ExtraCandidates = Tuple[np.ndarray, np.ndarray, np.ndarray]


def _account_candidates(stats: Optional[ScanStats], nbytes: int) -> None:
    """Record one candidate merge buffer allocation (scores + ids bytes):
    per-search peak in ScanStats, process-wide peak in DispatchStats — the
    figure the skewed-routing bench and the CI memory guard watch."""
    kops.dispatch_stats().record_candidate_bytes(nbytes)
    if stats is not None:
        stats.peak_candidate_bytes = max(stats.peak_candidate_bytes, int(nbytes))


def _account_lut(stats: Optional[ScanStats], nbytes: int, *, expanded: bool) -> None:
    """Record ADC LUT bytes materialized on device. ``expanded=True`` marks a
    per-unit [W, TQ, M, 256] expansion (the dense layout's gather operand) and
    also feeds ``DispatchStats.lut_expand_bytes`` — the counter the segmented
    path must leave untouched."""
    if expanded:
        kops.dispatch_stats().record_lut_expand(nbytes)
    if stats is not None:
        stats.lut_bytes += int(nbytes)


def _seg_offsets(
    plan_counts: np.ndarray, extra: Sequence[ExtraCandidates], m: int
) -> Tuple[np.ndarray, np.ndarray]:
    """CSR layout of the flat candidate buffer: (counts [m], offsets [m+1]).

    Query q owns flat rows offsets[q] .. offsets[q+1]-1 — its plan slots
    first (``plan_counts[q]`` of them, addressed as offsets[q] + slot), then
    one row per host-side extra. The per-query order matches the dense
    tensor's slot-major flattening, so the segmented merge selects the
    identical top-k (ties included)."""
    extra_counts = np.zeros(m, dtype=np.int64)
    for qrows, _, _ in extra:
        extra_counts[qrows] += 1
    counts = plan_counts + extra_counts
    offsets = np.zeros(m + 1, dtype=np.int64)
    np.cumsum(counts, out=offsets[1:])
    return counts, offsets


def _assemble_bucket(
    units: List[WorkUnit],
    lp: int,
    plan: ExecutionPlan,
    arena: PackedArena,
    w_pad: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Shared scan-stage assembly for one shape bucket.

    Returns (Vrows i64 [W, lp] packed rows to gather, valid bool [W, lp],
    qrow_of i64 [W, tq] workload query row per unit slot (-1 pad),
    slot_of i64 [W, tq] merge-tensor slot per unit slot). W is the unit count
    padded to a power of two so repeated workloads reuse a bounded set of
    compiled shapes (padding units are fully masked); the sharded executor
    passes ``w_pad`` so every rank assembles the same stacked width.
    """
    tq = plan.tq
    n_packed = arena.n
    W = _next_pow2(len(units), 1) if w_pad is None else w_pad
    with get_tracer().span("scan.assemble"):
        Vrows = np.zeros((W, lp), dtype=np.int64)
        valid = np.zeros((W, lp), dtype=bool)
        qrow_of = np.full((W, tq), -1, dtype=np.int64)
        slot_of = np.zeros((W, tq), dtype=np.int64)
        for w, u in enumerate(units):
            s0 = int(arena.list_start[u.glist])
            llen = int(arena.list_len[u.glist])
            rows = np.minimum(np.arange(lp) + s0, n_packed - 1)
            Vrows[w] = rows
            v_ok = np.arange(lp) < llen
            task = plan.tasks[u.task]
            if task.packed_bitmap is not None:
                pb = task.packed_bitmap
                local = np.minimum(rows - int(arena.part_row[task.part]), len(pb) - 1)
                v_ok = v_ok & pb[local]
            valid[w] = v_ok
            nq = len(u.qrows)
            qrow_of[w, :nq] = u.qrows
            slot_of[w, :nq] = u.slots
    return Vrows, valid, qrow_of, slot_of


def execute_plan(
    plan: ExecutionPlan,
    arena: Optional[PackedArena],  # None allowed iff the plan has no buckets
    q_vecs: np.ndarray,  # f32 [m, d]
    *,
    cfg: Optional[PlanConfig] = None,
    extra: Sequence[ExtraCandidates] = (),
    stats: Optional[ScanStats] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Returns (scores f32 [m, k] best-first, arena gids i64 [m, k]; -1 pad)."""
    cfg = PlanConfig() if cfg is None else cfg
    if cfg.scan_mode == "pq" and plan.buckets:
        if arena.codes is None or arena.pq is None:
            raise ValueError(
                "scan_mode='pq' needs a PQ-encoded arena: build the HQIIndex "
                "with HQIConfig(scan_mode='pq'), or pass pq= to "
                "batch_search_ivf; baseline indexes support scan_mode='f32' only"
            )
        return _execute_plan_pq(plan, arena, q_vecs, cfg=cfg, extra=extra, stats=stats)
    if cfg.scan_mode not in ("f32", "pq"):
        raise ValueError(f"unknown scan_mode {cfg.scan_mode!r}")
    if cfg.merge_layout not in ("segmented", "dense"):
        raise ValueError(f"unknown merge_layout {cfg.merge_layout!r}")
    m, k = plan.m, plan.k
    # extras get per-query-dense slot columns after the plan's own slots
    n_slots = plan.n_slots + _extra_slot_width(extra, m)
    if m == 0 or n_slots == 0:
        return (
            np.full((m, k), -np.inf, np.float32),
            np.full((m, k), -1, np.int64),
        )
    n_rows = 0 if arena is None else arena.n
    cand = _DeviceCandidates(plan, extra, k, segmented=cfg.merge_layout == "segmented")
    _scan_f32_buckets(cand, plan, arena, q_vecs, cfg, stats)
    cand.add_extras(extra, id_base=n_rows)
    top_s, top_v = cand.merge(k, stats)
    gid = arena.device_gid() if plan.buckets else None
    top_s, top_i = to_host("merge.d2h", top_s, kops.candidate_ids(top_v, n_rows, gid))
    return top_s.astype(np.float32, copy=False), top_i.astype(np.int64)


class _DeviceCandidates:
    """One execute's merge candidates, kept on the device until the merged
    top-k is read back.

    Each bucket's results stay on the device as a block of candidate rows
    (``ops.unit_topk_rows``: scores and arena rows), and the host notes
    which block row fills each row of the merge buffer (``src``, from the
    units' query slots alone). The merge's inputs are then one copy of
    ``src`` and one gather on the device (``ops.gather_candidates``).
    Values are arena rows, which the f32 path maps to ids after the merge
    (``ops.candidate_ids``: k per query, not every candidate); a host-side
    extra's id is stored as N + id, so one merge holds both (int32 on the
    device, as ids always were: ids below 2^31 - N).

    Query q owns buffer rows ``first[q] + slot``: its plan slots, then one
    row per host-side extra. Segmented layout: ``first`` is the CSR offset
    of q's segment (``_seg_offsets``) and the rows pad to a power of two.
    Dense layout: ``first[q] = q · n_slots``. Either way each query's
    candidates keep the slot-major order, so the two layouts' merges select
    the same top-k."""

    def __init__(self, plan, extra, width, *, segmented):
        m = plan.m
        if segmented:
            plan_counts = _plan_seg_counts(plan)
            _, offsets = _seg_offsets(plan_counts, extra, m)
            self.first, self.extra_base = offsets[:-1], plan_counts
            self.n_real = int(offsets[-1])
            self.rows = _next_pow2(self.n_real, 1)
            self._ends = offsets[1:].astype(np.int32)
        else:
            n_slots = plan.n_slots + _extra_slot_width(extra, m)
            self.first = np.arange(m, dtype=np.int64) * n_slots
            self.extra_base = np.full(m, plan.n_slots, dtype=np.int64)
            self.n_real = self.rows = m * n_slots
            self._ends = None
        self.m, self.width, self.segmented = m, width, segmented
        # a row no block fills reads past the blocks' end: (-inf, -1)
        self.src = np.full(self.rows, np.iinfo(np.int32).max, dtype=np.int32)
        self.blocks: list = []
        self._block_rows = 0

    def _add(self, block, dest: np.ndarray, at: np.ndarray) -> None:
        """Buffer rows ``dest`` take rows ``at`` of ``block``."""
        self.src[dest] = self._block_rows + at
        self._block_rows += int(block[0].shape[0])
        self.blocks.append(block)

    def add_bucket(self, s, i_loc, starts_d, qrow_of: np.ndarray, slot_of: np.ndarray, *,
                   n_rows: int) -> None:
        """One bucket's top-k (``ops.unit_topk_rows``), unit slot (w, t) at
        block row w·tq + t; pad slots fill no buffer row."""
        live = qrow_of >= 0
        with get_tracer().span("merge.scatter", rows=int(live.sum())):
            block = fence(*kops.unit_topk_rows(s, i_loc, starts_d, n_rows, width=self.width))
            dest = self.first[qrow_of[live]] + slot_of[live]
            self._add(block, dest, np.flatnonzero(live.ravel()))

    def add_extras(self, extra: Sequence[ExtraCandidates], *, id_base: int) -> None:
        """The adaptive executor's host-side candidates, one block copied
        to the device, in the rows after each query's plan slots in their
        order; each id stored as ``id_base + id``."""
        if not extra:
            return
        next_extra = self.extra_base.copy()
        dest, ss, vv = [], [], []
        for qrows, es, ei in extra:
            w = min(self.width, es.shape[1])
            dest.append(self.first[qrows] + next_extra[qrows])
            next_extra[qrows] += 1
            s_pad = np.full((len(qrows), self.width), -np.inf, dtype=np.float32)
            v_pad = np.full((len(qrows), self.width), -1, dtype=np.int32)
            s_pad[:, :w] = es[:, :w]
            v_pad[:, :w] = np.where(ei[:, :w] >= 0, id_base + ei[:, :w], -1)
            ss.append(s_pad)
            vv.append(v_pad)
        dest = np.concatenate(dest)
        block = tuple(to_device("merge.h2d", np.concatenate(ss), np.concatenate(vv)))
        self._add(block, dest, np.arange(len(dest)))

    def merge(self, k: int, stats: Optional[ScanStats]) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Every query's top-k of the buffer, (scores, values) [m, k] on the
        device: the buffer gathered from the blocks, then one
        ``segmented_merge_topk`` over the CSR segments or one ``merge_topk``
        over the dense [m, n_slots · width] rows."""
        m, width = self.m, self.width
        if not self.blocks:
            self.blocks.append((jnp.full((1, width), -jnp.inf, jnp.float32),
                                jnp.full((1, width), -1, jnp.int32)))
        blocks_s, blocks_v = (list(x) for x in zip(*self.blocks))
        src_d, *ends_d = to_device(
            "merge.h2d", self.src, *([self._ends] if self.segmented else [])
        )
        with get_tracer().span("merge.scatter", rows=int((self.src < self._block_rows).sum())):
            flat_s, flat_v, seg_of = fence(
                *kops.gather_candidates(blocks_s, blocks_v, src_d, *ends_d)
            )
        # the blocks are freed once the gather has read them
        del blocks_s, blocks_v
        self.blocks = []
        _account_candidates(stats, int(flat_s.nbytes + flat_v.nbytes))
        prof = get_profiler()
        tracer = get_tracer()
        t0 = prof.t0() if prof.enabled else 0
        if self.segmented:
            with tracer.span("merge.segmented", m=m, candidates=self.n_real):
                out = fence(*kops.segmented_merge_topk(flat_s, flat_v, seg_of, m, k))
            kind, cols = "segmented", self.rows
            rows = dict(rows=self.n_real, rows_padded=self.rows)
        else:
            cols = self.rows // m * width  # n_slots · width
            with tracer.span("merge.final", m=m, width=cols):
                out = fence(*kops.merge_topk(
                    flat_s.reshape(m, cols), flat_v.reshape(m, cols), k
                ))
            kind = "final"
            rows = dict(rows=m * cols, rows_padded=m * cols)
        if prof.enabled:
            prof.record_dispatch(
                "merge", kind, cols, t0,
                nbytes=int(flat_s.nbytes + flat_v.nbytes) + m * k * 12,
                flops=0.0, flops_padded=0.0,
                units=m, units_padded=m, **rows,
            )
        return out


def _wait_for(prev) -> None:
    """Before a bucket's copy and gather, wait for the previous bucket's
    scan: the host reads nothing back per bucket and would otherwise queue
    every bucket's gathered tiles on the device at once. One bucket's tiles
    are live at a time, as when each bucket was read back, and the host
    still assembles the next bucket while the device scans this one."""
    if prev is not None:
        jax.block_until_ready(prev)


def _scan_f32_buckets(cand, plan, arena, q_vecs, cfg, stats) -> None:
    """Run the f32 scan stage bucket by bucket (one ``workunit_topk``
    dispatch each) and add each bucket's top-k to ``cand``, on the device,
    the dense and segmented layouts alike.

    Nothing crosses per bucket but its unit table, and nothing comes back:
    the arena's rows are resident on the device (``PackedArena.device_rows``,
    one upload per arena), the queries are copied once per call
    (``query.h2d``), and each bucket ships its units' list starts, query
    rows and bitmap (``scan.h2d``) for ``ops.gather_unit_operands`` to
    gather the tiles (``scan.gather``); its results stay on the device as
    a block of merge candidates (``merge.scatter``)."""
    if not plan.buckets:
        return
    k, tq = plan.k, plan.tq
    d = q_vecs.shape[1]
    prof = get_profiler()
    tracer = get_tracer()
    rows_dev = arena.device_rows()
    q_dev = _device_queries(q_vecs)
    prev = None
    for lp in sorted(plan.buckets):
        units = plan.buckets[lp]
        Vrows, valid, qrow_of, slot_of = _assemble_bucket(units, lp, plan, arena)
        W = Vrows.shape[0]
        if stats is not None:
            # real work units only (pow2 pad excluded), so the figure is
            # comparable across configurations — the sharded executor counts
            # the same way per rank
            stats.bytes_scanned += len(units) * lp * d * 4
        _wait_for(prev)
        # a unit's rows are min(start + arange(lp), n - 1), so its first row
        # is its start as the gather reads it (pad units: row 0, all masked)
        starts_d, qrow_d, valid_d = to_device(
            "scan.h2d", Vrows[:, 0].astype(np.int32), qrow_of.astype(np.int32), valid
        )
        with tracer.span("scan.gather"):
            Q, V = fence(*kops.gather_unit_operands(rows_dev, q_dev, starts_d, qrow_d, lp=lp))
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span("dispatch.scan", mode="f32", lp=lp, units=len(units)):
            s, i_loc = kops.workunit_topk(
                Q,
                V,
                valid_d,
                min(k, lp),
                metric=arena.metric,
                use_pallas=cfg.use_pallas,
                interpret=cfg.interpret,
            )
            s, i_loc = fence(s, i_loc)  # device time is real iff tracing is on
        del qrow_d, valid_d, Q, V  # one bucket's operands at a time
        if prof.enabled:
            # real distance work: 2·d MACs per (query, live row) pair within
            # each unit; padded work covers the full [W, tq, lp] bucket
            nq_u = (qrow_of >= 0).sum(axis=1)
            rows_u = valid.sum(axis=1)
            # the kernel reads the Q and V tiles and the bitmap
            prof.record_dispatch(
                "scan", "f32", lp, t0,
                nbytes=W * (tq + lp) * d * 4 + valid.nbytes
                + W * tq * min(k, lp) * 12,
                flops=2.0 * d * float((nq_u * rows_u).sum()),
                flops_padded=2.0 * d * W * tq * lp,
                units=len(units), units_padded=W,
                rows=int(rows_u.sum()), rows_padded=W * lp,
            )
        # i_loc: index within the unit's lp rows (-1 = none)
        cand.add_bucket(s, i_loc, starts_d, qrow_of, slot_of, n_rows=arena.n)
        prev = s


def _plan_seg_counts(plan: ExecutionPlan) -> np.ndarray:
    """Per-query plan slot counts, tolerating plans built before the field
    existed (deserialized or hand-constructed): fall back to the dense
    assumption that every query owns ``n_slots`` slots."""
    if len(plan.seg_counts) == plan.m:
        return plan.seg_counts
    return np.full(plan.m, plan.n_slots, dtype=np.int64)


def _extra_slot_width(extra: Sequence[ExtraCandidates], m: int) -> int:
    """Max per-query count of host-side extra candidate columns."""
    extra_slots = np.zeros(m, dtype=np.int64)
    for qrows, _, _ in extra:
        extra_slots[qrows] += 1
    return int(extra_slots.max()) if m else 0


def _fold_extras_and_merge(
    out_scores: np.ndarray,  # f32 [m, n_slots, k] — base candidates filled in
    out_idx: np.ndarray,  # i64 [m, n_slots, k]
    extra: Sequence[ExtraCandidates],
    base_slots: int,  # extras occupy slot columns base_slots, base_slots+1, ...
    k: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Fold the adaptive executor's host-side candidates in, then final-merge.

    The host-side tail of the pq re-rank and the sharded paths, whose
    candidates are on the host already (the single-device f32 path merges
    its extras on the device: ``_DeviceCandidates.add_extras``).
    """
    m = out_scores.shape[0]
    with get_tracer().span("merge.scatter"):
        next_extra = np.full(m, base_slots, dtype=np.int64)
        for qrows, es, ei in extra:
            kk = min(k, es.shape[1])
            slot = next_extra[qrows]
            next_extra[qrows] += 1
            out_scores[qrows, slot, :kk] = es[:, :kk]
            out_idx[qrows, slot, :kk] = ei[:, :kk]
    top_s, top_i = _padded_merge(out_scores.reshape(m, -1), out_idx.reshape(m, -1), k)
    top_s, top_i = to_host("merge.d2h", top_s, top_i)
    return top_s.astype(np.float32, copy=False), top_i.astype(np.int64)


def _padded_merge(
    flat_s: np.ndarray, flat_i: np.ndarray, k: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """merge_topk with the candidate width padded to a power of two (so
    repeated workloads reuse a bounded set of compiled merge shapes)."""
    real_width = flat_s.shape[1]
    width = _next_pow2(real_width, k)
    tracer = get_tracer()
    if width > real_width:
        padc = width - real_width
        with tracer.span("merge.scatter"):
            flat_s = np.pad(flat_s, ((0, 0), (0, padc)), constant_values=-np.inf)
            flat_i = np.pad(flat_i, ((0, 0), (0, padc)), constant_values=-1)
    mq = flat_s.shape[0]
    operands = to_device("merge.h2d", flat_s, flat_i)
    prof = get_profiler()
    t0 = prof.t0() if prof.enabled else 0
    with tracer.span("merge.final", m=mq, width=width):
        s, i = kops.merge_topk(*operands, k)
        s, i = fence(s, i)
    if prof.enabled:
        prof.record_dispatch(
            "merge", "final", width, t0,
            nbytes=flat_s.nbytes + flat_i.nbytes + mq * k * 12,
            flops=0.0, flops_padded=0.0,
            units=mq, units_padded=mq,
            rows=mq * real_width, rows_padded=mq * width,
        )
    return s, i


def _execute_plan_pq(
    plan: ExecutionPlan,
    arena: PackedArena,
    q_vecs: np.ndarray,  # f32 [m, d]
    *,
    cfg: PlanConfig,
    extra: Sequence[ExtraCandidates] = (),
    stats: Optional[ScanStats] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Compressed two-stage execution: ADC scan over codes, then exact re-rank.

    Stage A — per shape bucket, ONE ``workunit_pq_topk`` dispatch scans uint8
    code tiles with each unit's VMEM-resident per-query LUTs, keeping
    k′ = refine_factor · k ADC candidates per (query, posting list).
    Stage B — candidates from all buckets merge to the per-query top-k′ (one
    device merge over ADC scores), their f32 rows are gathered from host
    memory once, and ONE ``workunit_topk`` dispatch re-scores them exactly. The
    final merge then folds in the adaptive executor's host-side candidates,
    exactly like the f32 path.
    """
    m, k = plan.m, plan.k
    kprime = max(k, int(cfg.refine_factor) * k)

    # The deployment's layout: the codes and the codebook live on the
    # device, the f32 rows stay in host memory for the exact re-rank. ADC
    # tables only for queries the plan actually scans (the adaptive executor
    # may have routed most of the workload to host-side extras), built on
    # the device as one resident [U, Mp, 256] array. The segmented layout
    # indexes it directly from the dispatch (per-unit-slot LUT rows
    # DMA-streamed on the Pallas path) so no per-bucket [W, tq, M, 256]
    # operand ever materializes; the dense layout keeps the device-side
    # gather expansion as the comparison baseline, which
    # ``DispatchStats.lut_expand_bytes`` meters.
    codes_dev = arena.device_codes()
    used = np.unique(
        np.concatenate(
            [u.qrows for units in plan.buckets.values() for u in units]
        )
    )
    lut_pos = np.zeros(m, dtype=np.int64)
    lut_pos[used] = np.arange(len(used))
    luts_dev = _resident_adc_tables(arena.pq, q_vecs[used])  # [U, Mp, 256]
    _account_lut(stats, int(luts_dev.nbytes), expanded=False)

    rows = _pq_stage_a(
        plan, arena, codes_dev, luts_dev, lut_pos, kprime, cfg=cfg, stats=stats
    )
    return _pq_rerank_and_fold(
        arena, q_vecs, rows, k=k, kprime=kprime, cfg=cfg, extra=extra, stats=stats
    )


def _device_queries(q_vecs: np.ndarray) -> jnp.ndarray:
    """The queries on the device, copied once an execute (``query.h2d``) and
    padded to a power of two, so the compiled shapes that read them do not
    follow every flush's batch size."""
    q_pad = np.zeros((_next_pow2(len(q_vecs), 8), q_vecs.shape[1]), dtype=np.float32)
    q_pad[: len(q_vecs)] = q_vecs
    (q_dev,) = to_device("query.h2d", q_pad)
    return q_dev


def _resident_adc_tables(pq: PQCodebook, q_used: np.ndarray) -> jnp.ndarray:
    """The ADC tables of ``q_used`` on the device, f32 [U', Mp, 256] (U'
    the queries' padded count): one jitted program builds every table from
    the resident queries and codebook (``scan.adc``)."""
    q_dev = _device_queries(q_used)
    with get_tracer().span("scan.adc", queries=len(q_used), m=pq.m):
        return fence(
            adc_tables_device(
                pq.device_centroids(), q_dev, metric=pq.metric, m_pad=subspace_pad(pq.m)
            )
        )


def _pq_stage_a(
    plan: ExecutionPlan,
    arena: PackedArena,
    codes_dev: jnp.ndarray,  # uint8 [N, M] — the arena's resident codes
    luts_dev: jnp.ndarray,  # f32 [U, Mp, 256]
    lut_pos: np.ndarray,  # i64 [m] — LUT row per workload query
    kprime: int,
    *,
    cfg: PlanConfig,
    stats: Optional[ScanStats],
) -> np.ndarray:
    """ADC stage A: per bucket one ADC dispatch keeping k' candidates per
    (query, posting list), kept on the device as merge candidates of packed
    rows (``_DeviceCandidates``: the re-rank gathers rows); one merge, and
    the per-query top-k' rows read back once. Returns them, global packed
    rows i64 [m, k'] (-1 pad).

    Each bucket ships only its units' list starts, LUT rows and bitmap
    (``scan.h2d``); its code tiles are gathered from the
    resident codes (``scan.gather``). The segmented layout dispatches
    ``workunit_pq_topk_resident``, which indexes the resident LUT table by
    per-slot row, so no per-bucket [W, tq, M, 256] operand materializes
    (``lut_expand_bytes`` stays 0); the dense layout expands it with a
    device gather for ``workunit_pq_topk``.
    """
    segmented = cfg.merge_layout == "segmented"
    mode = "pq-res" if segmented else "pq"
    M = int(codes_dev.shape[1])
    # stage A has no extras: they fold in after the re-rank
    cand = _DeviceCandidates(plan, (), kprime, segmented=segmented)
    prof = get_profiler()
    tracer = get_tracer()
    prev = None
    for lp in sorted(plan.buckets):
        units = plan.buckets[lp]
        Vrows, valid, qrow_of, slot_of = _assemble_bucket(units, lp, plan, arena)
        W = Vrows.shape[0]
        lut_idx = lut_pos[np.maximum(qrow_of, 0)]  # [W, tq]; pads -> LUT row 0
        _wait_for(prev)
        # a unit's rows are min(start + arange(lp), n - 1), so its first row
        # is its start as the gather reads it (pad units: row 0, all masked)
        starts_d, lut_idx_d, valid_d = to_device(
            "scan.h2d", Vrows[:, 0].astype(np.int32), lut_idx.astype(np.int32), valid
        )
        luts = None
        with tracer.span("scan.gather"):
            # [W, lp, M] uint8 from the resident codes; the dense layout also
            # expands the tables, [W, tq, Mp, 256], on the device
            codes_d = kops.gather_unit_codes(codes_dev, starts_d, lp=lp)
            if segmented:
                codes_d = fence(codes_d)
            else:
                codes_d, luts = fence(codes_d, jnp.take(luts_dev, lut_idx_d, axis=0))
                _account_lut(stats, int(luts.nbytes), expanded=True)
        if stats is not None:
            stats.bytes_scanned += len(units) * lp * M
        kk = min(kprime, lp)
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span("dispatch.scan", mode=mode, lp=lp, units=len(units)):
            if segmented:
                s, i_loc = kops.workunit_pq_topk_resident(
                    luts_dev, lut_idx_d, codes_d, valid_d, kk,
                    use_pallas=cfg.use_pallas, interpret=cfg.interpret,
                )
            else:
                s, i_loc = kops.workunit_pq_topk(
                    luts, codes_d, valid_d, kk,
                    use_pallas=cfg.use_pallas, interpret=cfg.interpret,
                )
            s, i_loc = fence(s, i_loc)
        del lut_idx_d, codes_d, valid_d
        if prof.enabled:
            # one-hot MXU contraction: 2·M·256 MACs per (query, live row); the
            # resident path streams one [M, 256] LUT row per LIVE query slot
            # instead of reading the expanded [W, tq, M, 256] operand
            nq_u = (qrow_of >= 0).sum(axis=1)
            rows_u = valid.sum(axis=1)
            lut_bytes = int(nq_u.sum()) * M * 256 * 4 if segmented else int(luts.nbytes)
            prof.record_dispatch(
                "scan", mode, lp, t0,
                nbytes=lut_bytes + W * lp * M + valid.nbytes
                + W * plan.tq * kk * 12,
                flops=2.0 * M * 256 * float((nq_u * rows_u).sum()),
                flops_padded=2.0 * M * 256 * W * plan.tq * lp,
                units=len(units), units_padded=W,
                rows=int(rows_u.sum()), rows_padded=W * lp,
            )
        del luts
        # i_loc: [W, tq, kk] index into the unit's lp rows
        cand.add_bucket(s, i_loc, starts_d, qrow_of, slot_of, n_rows=arena.n)
        prev = s

    # per-query top-k' ADC candidates across every bucket and probe slot
    _, top_rows = cand.merge(kprime, stats)
    (top_rows,) = to_host("merge.d2h", top_rows)
    return top_rows.astype(np.int64)


def _pq_rerank_and_fold(
    arena: PackedArena,
    q_vecs: np.ndarray,
    rows: np.ndarray,  # i64 [m, k'] surviving global packed rows (-1 pad)
    *,
    k: int,
    kprime: int,
    cfg: PlanConfig,
    extra: Sequence[ExtraCandidates],
    stats: Optional[ScanStats],
) -> Tuple[np.ndarray, np.ndarray]:
    """Stage B shared by both layouts: exact re-rank + extras fold."""
    m, d = q_vecs.shape

    # exact re-rank: one gather of the surviving f32 rows + one dispatch.
    # Units are per-query (TQ=1) so each query re-scores only ITS candidates;
    # m pads to a power of two for compile-shape reuse.
    mp = _next_pow2(m, 1)
    tracer = get_tracer()
    with tracer.span("rerank.gather"):
        Qr = np.zeros((mp, 1, d), dtype=np.float32)
        Qr[:m, 0] = q_vecs
        Vr = np.zeros((mp, kprime, d), dtype=np.float32)
        Vr[:m] = arena.packed[np.maximum(rows, 0)]
        valid_r = np.zeros((mp, kprime), dtype=bool)
        valid_r[:m] = rows >= 0
    if stats is not None:
        # real surviving candidates only (matches the sharded re-rank)
        stats.bytes_scanned += int((rows >= 0).sum()) * d * 4
    operands = to_device("rerank.h2d", Qr, Vr, valid_r)
    prof = get_profiler()
    t0 = prof.t0() if prof.enabled else 0
    with tracer.span("rerank.exact", m=m, kprime=kprime):
        s, i_loc = kops.workunit_topk(
            *operands,
            min(k, kprime),
            metric=arena.metric,
            use_pallas=cfg.use_pallas,
            interpret=cfg.interpret,
            tag="rerank",
        )
        s, i_loc = fence(s, i_loc)
    del operands
    if prof.enabled:
        n_real = int((rows >= 0).sum())
        prof.record_dispatch(
            "rerank", "f32", kprime, t0,
            nbytes=Qr.nbytes + Vr.nbytes + valid_r.nbytes
            + mp * min(k, kprime) * 12,
            flops=2.0 * d * n_real,
            flops_padded=2.0 * d * mp * kprime,
            units=m, units_padded=mp,
            rows=n_real, rows_padded=mp * kprime,
        )
    s, i_loc = to_host("rerank.d2h", s, i_loc)
    s = s[:m, 0]  # [m, kk] exact scores
    i_loc = i_loc[:m, 0]  # [m, kk] index into the k' candidates
    kk = s.shape[-1]
    with tracer.span("rerank.remap"):
        packed_rows = np.take_along_axis(rows, np.maximum(i_loc, 0).astype(np.int64), axis=1)
        gidx = np.where(i_loc < 0, -1, arena.gid[np.maximum(packed_rows, 0)])
        gidx = np.where(packed_rows < 0, -1, gidx)

    # final merge: re-ranked (exact) plan results in slot 0 + host-side exact
    # extras in the columns after it — the same tail as the f32 path
    n_slots = 1 + _extra_slot_width(extra, m)
    out_scores = np.full((m, n_slots, k), -np.inf, dtype=np.float32)
    out_idx = np.full((m, n_slots, k), -1, dtype=np.int64)
    _account_candidates(stats, out_scores.nbytes + out_idx.nbytes)
    with tracer.span("merge.scatter"):
        out_scores[:, 0, :kk] = np.where(gidx >= 0, s, -np.inf)
        out_idx[:, 0, :kk] = gidx
    return _fold_extras_and_merge(out_scores, out_idx, extra, 1, k)


# ----------------------------------------------------------------- sharded

@dataclasses.dataclass
class ShardStats:
    """Per-rank accounting of one sharded execution (the bench/test probe).

    ``per_rank_bytes`` counts arena bytes each rank's scan stages gathered
    for its REAL work units (stacking pad excluded) — the quantity that must
    shrink ~1/|model| per rank versus a single device. ``gathered_per_query``
    is the total candidate columns the all-gather merges moved per query:
    O(k · |model|) by construction, independent of DB size, which the parity
    suite asserts as the engine's entire cross-rank traffic.
    """

    n_shards: int
    per_rank_bytes: np.ndarray  # i64 [R] — arena bytes scanned by rank r
    per_rank_units: np.ndarray  # i64 [R] — real work units executed by rank r
    per_rank_dispatches: np.ndarray  # i64 [R] — stages rank r had live work in
    gathered_per_query: int = 0  # candidate columns all-gathered per query

    @staticmethod
    def zeros(n_shards: int) -> "ShardStats":
        return ShardStats(
            n_shards=int(n_shards),
            per_rank_bytes=np.zeros(n_shards, dtype=np.int64),
            per_rank_units=np.zeros(n_shards, dtype=np.int64),
            per_rank_dispatches=np.zeros(n_shards, dtype=np.int64),
        )


def execute_plan_sharded(
    splan: ShardedPlan,
    sharded: ShardedArena,
    q_vecs: np.ndarray,  # f32 [m, d]
    *,
    mesh,
    axis: str = "model",
    cfg: Optional[PlanConfig] = None,
    extra: Sequence[ExtraCandidates] = (),
    stats: Optional[ScanStats] = None,
    shard_stats: Optional[ShardStats] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Stage 2 across a device mesh — bit-identical to ``execute_plan``.

    Per shared shape bucket, every rank's work units stack along the mesh
    axis and run as ONE ``sharded_workunit_topk`` (or ``_pq_topk``) dispatch:
    rank r gathers rows/codes only from ITS arena shard, so per-rank scan
    traffic is its shard's share of the workload. Candidates then reduce in
    two hops: a rank-local top-k over each rank's own candidate tensor,
    followed by the all-gather merge (``sharded_merge_topk``) whose traffic
    is k·|model| (score, id) pairs per query — never distance rows, never
    O(n). Extras (the adaptive executor's host-side exact scans) fold into
    the final merge exactly like the single-device paths.

    Parity argument (what tests/test_engine_sharded.py asserts): every
    (query, posting-list) pair lives on exactly one rank and is evaluated
    with the same per-unit kernel math as the single-device engine, so the
    union of per-rank candidates equals the single-device candidate set and
    the two-hop top-k selects the identical result. Caveat: candidates with
    EXACTLY equal scores straddling the k (or pq k′) boundary may resolve in
    a different order than the single-device flat merge (top_k breaks ties
    by position, and the two layouts order candidates differently) — both
    answers are correct top-ks; on continuous data exact ties are duplicate
    vectors.
    """
    cfg = PlanConfig() if cfg is None else cfg
    if cfg.scan_mode not in ("f32", "pq"):
        raise ValueError(f"unknown scan_mode {cfg.scan_mode!r}")
    sstats = ShardStats.zeros(sharded.n_shards) if shard_stats is None else shard_stats
    sstats.per_rank_units += splan.per_rank_units
    m, k = splan.plan.m, splan.plan.k
    if m == 0 or splan.n_units == 0:
        n_slots = _extra_slot_width(extra, m)
        if m == 0 or n_slots == 0:
            return (
                np.full((m, k), -np.inf, np.float32),
                np.full((m, k), -1, np.int64),
            )
        out_scores = np.full((m, n_slots, k), -np.inf, dtype=np.float32)
        out_idx = np.full((m, n_slots, k), -1, dtype=np.int64)
        return _fold_extras_and_merge(out_scores, out_idx, extra, 0, k)
    if cfg.scan_mode == "pq":
        if sharded.base.codes is None or sharded.base.pq is None:
            raise ValueError(
                "scan_mode='pq' needs a PQ-encoded arena: build the HQIIndex "
                "with HQIConfig(scan_mode='pq'), or pass pq= to "
                "batch_search_ivf; baseline indexes support scan_mode='f32' only"
            )
        return _execute_sharded_pq(
            splan, sharded, q_vecs, mesh=mesh, axis=axis, cfg=cfg,
            extra=extra, stats=stats, sstats=sstats,
        )
    return _execute_sharded_f32(
        splan, sharded, q_vecs, mesh=mesh, axis=axis, cfg=cfg,
        extra=extra, stats=stats, sstats=sstats,
    )


def _assemble_bucket_stacked(
    splan: ShardedPlan,
    sharded: ShardedArena,
    lp: int,
    q_vecs: np.ndarray,
    with_q: bool = True,
) -> Tuple[np.ndarray, ...]:
    """Stack every rank's bucket assembly along the mesh axis (host side).

    Returns (unit_lists, Q [R,W,tq,d], valid [R,W,lp], qrow_of, slot_of,
    Vrows [R,W,lp], wmask). Assembly runs against the BASE arena — a rank's
    units reference only posting lists it owns, so slice r of ``Vrows``
    addresses rank r's rows (up to fully-masked clamp padding). Ranks
    without units in this bucket contribute fully-masked zero slices; W is
    the max rank unit count padded pow2 so all ranks share one dispatch
    shape. ``with_q=False`` (the ADC path, which scans with LUTs instead of
    query vectors) skips the query-tile allocation and gather and returns
    ``Q=None``.
    """
    R = sharded.n_shards
    tq, d = splan.plan.tq, q_vecs.shape[1]
    unit_lists = [splan.rank_buckets[r].get(lp, []) for r in range(R)]
    # XLA turns a batch of one unit into a plain matmul, which the CPU
    # backend rounds differently from a batched one: stack a single unit
    # only where the single-device bucket holds one too, so both round alike
    n_units = sum(len(u) for u in unit_lists)
    W = _next_pow2(max(len(u) for u in unit_lists), 1 if n_units == 1 else 2)
    valid = np.zeros((R, W, lp), dtype=bool)
    qrow_of = np.full((R, W, tq), -1, dtype=np.int64)
    slot_of = np.zeros((R, W, tq), dtype=np.int64)
    Vrows = np.zeros((R, W, lp), dtype=np.int64)
    for r in range(R):
        if not unit_lists[r]:
            continue
        vr, va, qr, sl = _assemble_bucket(
            unit_lists[r], lp, splan.plan, sharded.base, w_pad=W
        )
        Vrows[r], valid[r], qrow_of[r], slot_of[r] = vr, va, qr, sl
    wmask = qrow_of >= 0
    Q = None
    if with_q:
        with get_tracer().span("scan.gather"):
            Q = np.zeros((R, W, tq, d), dtype=np.float32)
            Q[wmask] = q_vecs[qrow_of[wmask]]
    return unit_lists, Q, valid, qrow_of, slot_of, Vrows, wmask


def _merge_with_extras(
    ms: np.ndarray,  # f32 [m, k] — the sharded gather merge's final top-k
    mi: np.ndarray,  # i64 [m, k]
    extra: Sequence[ExtraCandidates],
    k: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Shared tail of both sharded scan modes: fold the adaptive executor's
    host-side exact candidates (if any) into the merged device result —
    slot 0 holds the sharded top-k, extras take the columns after it."""
    if not extra:
        return ms, mi  # the gather merge already IS the final per-query top-k
    m = ms.shape[0]
    out_slots = 1 + _extra_slot_width(extra, m)
    out_scores = np.full((m, out_slots, k), -np.inf, dtype=np.float32)
    out_idx = np.full((m, out_slots, k), -1, dtype=np.int64)
    out_scores[:, 0] = ms
    out_idx[:, 0] = mi
    return _fold_extras_and_merge(out_scores, out_idx, extra, 1, k)


def _gather_merge(
    mesh,
    axis: str,
    cand_s: np.ndarray,  # f32 [R, m, n_slots, kk] per-rank candidate tensors
    cand_i: np.ndarray,  # i64 [R, m, n_slots, kk]
    k: int,
) -> Tuple[np.ndarray, np.ndarray]:
    """Two-hop reduction: rank-local top-k, then the k·|model| gather merge.
    Candidate width pads pow2 (≥ k) so repeated workloads reuse compiled
    merge shapes, like the single-device ``_padded_merge``."""
    R, m = cand_s.shape[:2]
    flat_s = cand_s.reshape(R, m, -1)
    flat_i = cand_i.reshape(R, m, -1)
    real_width = flat_s.shape[2]
    width = _next_pow2(real_width, k)
    tracer = get_tracer()
    if width > real_width:
        padc = width - real_width
        with tracer.span("merge.scatter"):
            flat_s = np.pad(flat_s, ((0, 0), (0, 0), (0, padc)), constant_values=-np.inf)
            flat_i = np.pad(flat_i, ((0, 0), (0, 0), (0, padc)), constant_values=-1)
    operands = to_device("merge.h2d", flat_s, flat_i)
    prof = get_profiler()
    t0 = prof.t0() if prof.enabled else 0
    with tracer.span("merge.gather", ranks=R, m=m, width=width):
        ms, mi = kops.sharded_merge_topk(mesh, axis, *operands, k)
        ms, mi = fence(ms, mi)
    del operands
    if prof.enabled:
        prof.record_dispatch(
            "gather", "sharded", width, t0,
            nbytes=flat_s.nbytes + flat_i.nbytes + m * k * 12,
            flops=0.0, flops_padded=0.0,
            units=R * m, units_padded=R * m,
            rows=R * m * real_width, rows_padded=R * m * width,
        )
    ms, mi = to_host("merge.d2h", ms, mi)
    return ms.astype(np.float32, copy=False), mi.astype(np.int64)


def _rank_segments(
    splan: ShardedPlan, R: int, m: int
) -> Tuple[int, List[np.ndarray], np.ndarray, np.ndarray]:
    """Per-rank CSR layout for the sharded segmented merge.

    Every (query, slot) pair lives in exactly one work unit, hence on exactly
    one rank — so each rank's candidate rows are the sorted set of its own
    ``q · S + slot`` keys (S spans the slot range). Returns
    (S, rank_keys [R sorted i64 arrays], base [R+1] flat-row offsets,
    seg_of [Σ|keys|] i32): rank r's candidates occupy flat rows
    base[r]..base[r+1]-1 with segment id r·m + q — ascending, because rows
    sort by (rank, query, slot). One segmented merge over R·m segments then
    equals every rank's local [m, k] top-k, with the light ranks paying for
    exactly their own segments instead of a dense [R, m, n_slots, k] stack.
    """
    S = max(splan.plan.n_slots, 1)
    rank_keys: List[np.ndarray] = []
    base = np.zeros(R + 1, dtype=np.int64)
    segs: List[np.ndarray] = []
    for r in range(R):
        ks = [
            u.qrows * S + u.slots
            for units in splan.rank_buckets[r].values()
            for u in units
        ]
        kr = np.sort(np.concatenate(ks)) if ks else np.zeros(0, dtype=np.int64)
        rank_keys.append(kr)
        base[r + 1] = base[r] + len(kr)
        segs.append(r * m + (kr // S).astype(np.int32))
    seg_of = (
        np.concatenate(segs).astype(np.int32)
        if int(base[-1])
        else np.zeros(0, dtype=np.int32)
    )
    return S, rank_keys, base, seg_of


def _execute_sharded_f32(
    splan: ShardedPlan,
    sharded: ShardedArena,
    q_vecs: np.ndarray,
    *,
    mesh,
    axis: str,
    cfg: PlanConfig,
    extra: Sequence[ExtraCandidates],
    stats: Optional[ScanStats],
    sstats: ShardStats,
) -> Tuple[np.ndarray, np.ndarray]:
    R = sharded.n_shards
    m, k = splan.plan.m, splan.plan.k
    d = q_vecs.shape[1]
    arena = sharded.base
    n_slots = splan.plan.n_slots
    segmented = cfg.merge_layout == "segmented"
    if segmented:
        S, rank_keys, base, seg_pref = _rank_segments(splan, R, m)
        C_pad = _next_pow2(int(base[-1]), 1)
        flat_s = np.full((C_pad, k), -np.inf, dtype=np.float32)
        flat_i = np.full((C_pad, k), -1, dtype=np.int64)
        seg_of = np.full(C_pad, R * m, dtype=np.int32)
        seg_of[: int(base[-1])] = seg_pref
        _account_candidates(stats, flat_s.nbytes + flat_i.nbytes)
    else:
        cand_s = np.full((R, m, n_slots, k), -np.inf, dtype=np.float32)
        cand_i = np.full((R, m, n_slots, k), -1, dtype=np.int64)
        _account_candidates(stats, cand_s.nbytes + cand_i.nbytes)

    tracer = get_tracer()
    for lp in splan.pads:
        unit_lists, Q, valid, qrow_of, slot_of, Vrows, wmask = _assemble_bucket_stacked(
            splan, sharded, lp, q_vecs
        )
        with tracer.span("scan.gather"):
            V = np.zeros(valid.shape + (d,), dtype=np.float32)
            for r in range(R):
                if not unit_lists[r]:
                    continue
                V[r] = arena.packed[Vrows[r]]
                sstats.per_rank_bytes[r] += len(unit_lists[r]) * lp * d * 4
                sstats.per_rank_dispatches[r] += 1
        if stats is not None:
            stats.bytes_scanned += int(sum(len(u) for u in unit_lists)) * lp * d * 4
        kk = min(k, lp)
        rank_units = [len(u) for u in unit_lists]
        operands = to_device("scan.h2d", Q, V, valid)
        prof = get_profiler()
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span(
            "dispatch.sharded", mode="f32", lp=lp, rank_units=rank_units,
        ):
            s, i_loc = kops.sharded_workunit_topk(
                mesh, axis,
                *operands, kk,
                metric=arena.metric,
                use_pallas=cfg.use_pallas, interpret=cfg.interpret,
            )
            s, i_loc = fence(s, i_loc)
        del operands
        if prof.enabled:
            W_ = valid.shape[1]
            tq_ = splan.plan.tq
            nq_rw = wmask.sum(axis=2)  # [R, W]
            rows_rw = valid.sum(axis=2)  # [R, W]
            prof.record_dispatch(
                "scan", "sharded-f32", lp, t0,
                nbytes=Q.nbytes + V.nbytes + valid.nbytes
                + R * W_ * tq_ * kk * 12,
                flops=2.0 * d * float((nq_rw * rows_rw).sum()),
                flops_padded=2.0 * d * R * W_ * tq_ * lp,
                units=int(sum(rank_units)), units_padded=R * W_,
                rows=int(rows_rw.sum()), rows_padded=R * W_ * lp,
                rank_units=rank_units,
                rank_bytes=[n * lp * d * 4 for n in rank_units],
            )
        # i_loc: [R, W, tq, kk] index into the unit's lp rows
        s, i_loc = to_host("scan.d2h", s, i_loc)
        live = [r for r in range(R) if unit_lists[r]]
        with tracer.span("scan.remap"):
            gidx = {}
            for r in live:
                packed_rows = np.take_along_axis(
                    np.broadcast_to(Vrows[r][:, None, :], i_loc[r].shape[:2] + (lp,)),
                    np.maximum(i_loc[r], 0),
                    axis=2,
                )
                gidx[r] = np.where(i_loc[r] < 0, -1, arena.gid[packed_rows])
        with tracer.span("merge.scatter"):
            for r in live:
                qr, sl = qrow_of[r][wmask[r]], slot_of[r][wmask[r]]
                if segmented:
                    rows = base[r] + np.searchsorted(rank_keys[r], qr * S + sl)
                    flat_s[rows, :kk] = s[r][wmask[r]]
                    flat_i[rows, :kk] = gidx[r][wmask[r]]
                else:
                    cand_s[r, qr, sl, :kk] = s[r][wmask[r]]
                    cand_i[r, qr, sl, :kk] = gidx[r][wmask[r]]

    if segmented:
        # one ragged merge over R·m segments = every rank's local top-k; the
        # gather merge's rank-local reduction over these already-sorted rows
        # is an identity, so the all-gather sees the dense path's operands
        operands = to_device("merge.h2d", flat_s, flat_i, seg_of)
        prof = get_profiler()
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span("merge.segmented", m=R * m, candidates=int(base[-1])):
            seg_s, seg_i = kops.segmented_merge_topk(*operands, R * m, k)
            seg_s, seg_i = fence(seg_s, seg_i)
        del operands
        if prof.enabled:
            prof.record_dispatch(
                "merge", "segmented", C_pad, t0,
                nbytes=flat_s.nbytes + flat_i.nbytes + seg_of.nbytes
                + R * m * k * 12,
                flops=0.0, flops_padded=0.0,
                units=R * m, units_padded=R * m,
                rows=int(base[-1]), rows_padded=C_pad,
            )
        seg_s, seg_i = to_host("merge.d2h", seg_s, seg_i)
        ms, mi = _gather_merge(
            mesh, axis,
            seg_s.astype(np.float32, copy=False).reshape(R, m, 1, k),
            seg_i.astype(np.int64).reshape(R, m, 1, k),
            k,
        )
    else:
        ms, mi = _gather_merge(mesh, axis, cand_s, cand_i, k)
    sstats.gathered_per_query += R * k
    return _merge_with_extras(ms, mi, extra, k)


def _execute_sharded_pq(
    splan: ShardedPlan,
    sharded: ShardedArena,
    q_vecs: np.ndarray,
    *,
    mesh,
    axis: str,
    cfg: PlanConfig,
    extra: Sequence[ExtraCandidates],
    stats: Optional[ScanStats],
    sstats: ShardStats,
) -> Tuple[np.ndarray, np.ndarray]:
    """Compressed two-stage execution across the mesh.

    Stage A mirrors the f32 path with uint8 code tiles: per shared bucket,
    one sharded ADC dispatch; each rank keeps k′ = refine_factor · k ADC
    candidates per (query, posting list) as GLOBAL packed rows. The ADC
    candidate gather (k′·|model| per query) then selects the same global
    top-k′ the single-device merge would — any global survivor is also a
    local survivor on its rank — and stage B re-ranks exactly: every rank
    gathers the f32 rows of the candidates IT stores, scores them in one
    sharded dispatch, and the final k·|model| gather merges the partial
    exact top-ks. Extras fold in last, as everywhere.
    """
    R = sharded.n_shards
    m, k = splan.plan.m, splan.plan.k
    d = q_vecs.shape[1]
    arena = sharded.base
    kprime = max(k, int(cfg.refine_factor) * k)
    M = arena.codes.shape[1]

    used = np.unique(
        np.concatenate(
            [u.qrows for units in splan.plan.buckets.values() for u in units]
        )
    )
    lut_pos = np.zeros(m, dtype=np.int64)
    lut_pos[used] = np.arange(len(used))
    with get_tracer().span("scan.gather"):
        luts = adc_tables(arena.pq, q_vecs[used])
    (luts_dev,) = to_device("scan.h2d", luts)  # [U, M, 256]
    _account_lut(stats, int(luts_dev.nbytes), expanded=False)

    n_slots = splan.plan.n_slots
    segmented = cfg.merge_layout == "segmented"
    if segmented:
        S, rank_keys, base, seg_pref = _rank_segments(splan, R, m)
        C_pad = _next_pow2(int(base[-1]), 1)
        flat_s = np.full((C_pad, kprime), -np.inf, dtype=np.float32)
        flat_rows = np.full((C_pad, kprime), -1, dtype=np.int64)
        seg_of = np.full(C_pad, R * m, dtype=np.int32)
        seg_of[: int(base[-1])] = seg_pref
        _account_candidates(stats, flat_s.nbytes + flat_rows.nbytes)
    else:
        cand_s = np.full((R, m, n_slots, kprime), -np.inf, dtype=np.float32)
        cand_rows = np.full((R, m, n_slots, kprime), -1, dtype=np.int64)
        _account_candidates(stats, cand_s.nbytes + cand_rows.nbytes)

    tracer = get_tracer()
    for lp in splan.pads:
        unit_lists, _, valid, qrow_of, slot_of, Vrows, wmask = _assemble_bucket_stacked(
            splan, sharded, lp, q_vecs, with_q=False
        )
        with tracer.span("scan.gather"):
            codes = np.zeros(valid.shape + (M,), dtype=np.uint8)
            for r in range(R):
                if not unit_lists[r]:
                    continue
                codes[r] = arena.codes[Vrows[r]]
                sstats.per_rank_bytes[r] += len(unit_lists[r]) * lp * M
                sstats.per_rank_dispatches[r] += 1
            lut_idx = lut_pos[np.maximum(qrow_of, 0)]  # padding slots -> LUT row 0
        if stats is not None:
            stats.bytes_scanned += int(sum(len(u) for u in unit_lists)) * lp * M
        kk = min(kprime, lp)
        rank_units = [len(u) for u in unit_lists]
        if not segmented:
            # the dense dispatch expands per-unit [W, tq, M, 256] LUT operands
            # on every rank; the segmented (stream=True) dispatch indexes the
            # resident table from the kernel instead
            W = valid.shape[1]
            tq = splan.plan.tq
            _account_lut(
                stats, R * W * tq * M * 256 * 4, expanded=True
            )
        operands = to_device("scan.h2d", lut_idx, codes, valid)
        prof = get_profiler()
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span(
            "dispatch.sharded", mode="pq", lp=lp, rank_units=rank_units
        ):
            s, i_loc = kops.sharded_workunit_pq_topk(
                mesh, axis,
                luts_dev, *operands, kk,
                use_pallas=cfg.use_pallas, interpret=cfg.interpret,
                stream=segmented,
            )
            s, i_loc = fence(s, i_loc)
        del operands
        if prof.enabled:
            W_ = valid.shape[1]
            tq_ = splan.plan.tq
            nq_rw = wmask.sum(axis=2)
            rows_rw = valid.sum(axis=2)
            lut_b = (int(nq_rw.sum()) * M * 256 * 4 if segmented
                     else R * W_ * tq_ * M * 256 * 4)
            prof.record_dispatch(
                "scan", "sharded-pq", lp, t0,
                nbytes=codes.nbytes + valid.nbytes + lut_b
                + R * W_ * tq_ * kk * 12,
                flops=2.0 * M * 256 * float((nq_rw * rows_rw).sum()),
                flops_padded=2.0 * M * 256 * R * W_ * tq_ * lp,
                units=int(sum(rank_units)), units_padded=R * W_,
                rows=int(rows_rw.sum()), rows_padded=R * W_ * lp,
                rank_units=rank_units,
                rank_bytes=[n * lp * M for n in rank_units],
            )
        s, i_loc = to_host("scan.d2h", s, i_loc)
        live = [r for r in range(R) if unit_lists[r]]
        with tracer.span("scan.remap"):
            packed = {}
            for r in live:
                packed_rows = np.take_along_axis(
                    np.broadcast_to(Vrows[r][:, None, :], i_loc[r].shape[:2] + (lp,)),
                    np.maximum(i_loc[r], 0),
                    axis=2,
                )
                packed[r] = np.where(i_loc[r] < 0, -1, packed_rows)  # global rows
        with tracer.span("merge.scatter"):
            for r in live:
                qr, sl = qrow_of[r][wmask[r]], slot_of[r][wmask[r]]
                if segmented:
                    rws = base[r] + np.searchsorted(rank_keys[r], qr * S + sl)
                    flat_s[rws, :kk] = s[r][wmask[r]]
                    flat_rows[rws, :kk] = packed[r][wmask[r]]
                else:
                    cand_s[r, qr, sl, :kk] = s[r][wmask[r]]
                    cand_rows[r, qr, sl, :kk] = packed[r][wmask[r]]

    # global top-k' ADC candidates: k'·|model| gather, identical selection to
    # the single-device merge (a global survivor survives locally too)
    if segmented:
        operands = to_device("merge.h2d", flat_s, flat_rows, seg_of)
        prof = get_profiler()
        t0 = prof.t0() if prof.enabled else 0
        with tracer.span("merge.segmented", m=R * m, candidates=int(base[-1])):
            seg_s, seg_i = kops.segmented_merge_topk(*operands, R * m, kprime)
            seg_s, seg_i = fence(seg_s, seg_i)
        del operands
        if prof.enabled:
            prof.record_dispatch(
                "merge", "segmented", C_pad, t0,
                nbytes=flat_s.nbytes + flat_rows.nbytes + seg_of.nbytes
                + R * m * kprime * 12,
                flops=0.0, flops_padded=0.0,
                units=R * m, units_padded=R * m,
                rows=int(base[-1]), rows_padded=C_pad,
            )
        seg_s, seg_i = to_host("merge.d2h", seg_s, seg_i)
        _, top_rows = _gather_merge(
            mesh, axis,
            seg_s.astype(np.float32, copy=False).reshape(R, m, 1, kprime),
            seg_i.astype(np.int64).reshape(R, m, 1, kprime),
            kprime,
        )
    else:
        _, top_rows = _gather_merge(mesh, axis, cand_s, cand_rows, kprime)
    sstats.gathered_per_query += R * kprime
    rows = top_rows  # [m, k'] global packed rows (-1 pad)

    # sharded exact re-rank: rank r rescans the surviving rows IT stores
    mp = _next_pow2(m, 1)
    with tracer.span("rerank.gather"):
        Qr = np.zeros((R, mp, 1, d), dtype=np.float32)
        Qr[:, :m, 0] = q_vecs[None]
        Vr = np.zeros((R, mp, kprime, d), dtype=np.float32)
        valid_r = np.zeros((R, mp, kprime), dtype=bool)
        owner = sharded.owner_of_row(np.maximum(rows, 0))
        for r in range(R):
            own = (owner == r) & (rows >= 0)
            if not own.any():
                continue
            sel = arena.packed[rows[own]]
            Vr[r, :m][own] = sel
            valid_r[r, :m] = own
            sstats.per_rank_bytes[r] += sel.nbytes
            sstats.per_rank_dispatches[r] += 1
            if stats is not None:
                stats.bytes_scanned += sel.nbytes
    kk = min(k, kprime)
    operands = to_device("rerank.h2d", Qr, Vr, valid_r)
    prof = get_profiler()
    t0 = prof.t0() if prof.enabled else 0
    with tracer.span("rerank.exact", mode="sharded", m=m, kprime=kprime):
        s, i_loc = kops.sharded_workunit_topk(
            mesh, axis,
            *operands, kk,
            metric=arena.metric,
            use_pallas=cfg.use_pallas, interpret=cfg.interpret,
        )
        s, i_loc = fence(s, i_loc)
    del operands
    if prof.enabled:
        n_real = int(valid_r.sum())
        prof.record_dispatch(
            "rerank", "sharded", kprime, t0,
            nbytes=Qr.nbytes + Vr.nbytes + valid_r.nbytes + R * mp * kk * 12,
            flops=2.0 * d * n_real,
            flops_padded=2.0 * d * R * mp * kprime,
            units=m, units_padded=R * mp,
            rows=n_real, rows_padded=R * mp * kprime,
        )
    s, i_loc = to_host("rerank.d2h", s, i_loc)
    s = s[:, :m, 0]  # [R, m, kk] exact partial scores
    i_loc = i_loc[:, :m, 0]  # [R, m, kk] index into the k' candidates
    with tracer.span("rerank.remap"):
        rows_b = np.broadcast_to(rows[None], (R, m, kprime))
        packed_rows = np.take_along_axis(
            rows_b, np.maximum(i_loc, 0).astype(np.int64), axis=2
        )
        gidx = np.where(i_loc < 0, -1, arena.gid[np.maximum(packed_rows, 0)])
        gidx = np.where(packed_rows < 0, -1, gidx)
        sc = np.where(gidx >= 0, s, -np.inf).astype(np.float32)

    ms, mi = _gather_merge(
        mesh, axis, sc[:, :, None, :], gidx[:, :, None, :], k
    )
    sstats.gathered_per_query += R * k
    return _merge_with_extras(ms, mi, extra, k)


def batch_search_ivf(
    ivf: IVFIndex,
    q_vecs: np.ndarray,  # [m, d] — one template group
    *,
    nprobe: int,
    k: int,
    bitmap: Optional[np.ndarray] = None,  # bool [n] in LOCAL vector order
    stats: Optional[ScanStats] = None,
    cfg: Optional[PlanConfig] = None,
    pq: Optional[PQCodebook] = None,  # required iff cfg.scan_mode == "pq"
    mesh=None,  # jax.sharding.Mesh: shard the scan over its model axis
    shard_spec=None,  # core.distributed.ShardSpec (default axes)
) -> Tuple[np.ndarray, np.ndarray]:
    """Plan + execute one IVF index: (scores f32 [m, k], local idx i64 [m, k]).

    With ``mesh=`` the index is a single qd-tree-less partition, so sharding
    falls back to posting-list-block granularity: the arena's packed rows
    split into contiguous row slices per model rank (the single partition is
    viewed as |model| pseudo-partitions along posting-list boundaries) and
    execution runs through ``core.distributed.execute_sharded`` — results
    stay bit-identical to ``mesh=None``.
    """
    cfg = PlanConfig() if cfg is None else cfg
    m = q_vecs.shape[0]
    if m == 0:
        return np.zeros((0, k), np.float32), np.zeros((0, k), np.int64)
    arena = PackedArena.from_ivf(ivf)
    if cfg.scan_mode == "pq":
        # explicit per-call codebook: the arena is memoized on the IVF, so
        # falling back to arena.pq would silently reuse whatever codebook a
        # PREVIOUS caller attached. Re-encoding is skipped when the same
        # codebook object is passed again (attach_pq is identity-idempotent).
        if pq is None:
            raise ValueError("batch_search_ivf(scan_mode='pq') needs an explicit pq=")
        arena.attach_pq(pq)
    packed_bitmap = None
    if bitmap is not None:
        packed_bitmap = arena.packed_bitmap(0, bitmap)
    task = EngineTask(
        part=0,
        qrows=np.arange(m, dtype=np.int64),
        nprobe=int(min(nprobe, ivf.n_lists)),
        packed_bitmap=packed_bitmap,
    )
    if mesh is not None:
        from .distributed import ShardSpec, execute_sharded

        spec = shard_spec or ShardSpec()
        sharded = PackedArena.sharded_from_ivf(ivf, spec.n_shards(mesh))
        s, i, _ = execute_sharded(
            sharded, [task], q_vecs,
            mesh=mesh, spec=spec, m=m, k=k, cfg=cfg, stats=stats,
        )
        return s, i
    plan = build_plan(arena, [task], q_vecs, m=m, k=k, cfg=cfg, stats=stats)
    return execute_plan(plan, arena, q_vecs, cfg=cfg, stats=stats)
