"""jit'd dispatch wrappers for the kernels package.

Every op has two implementations: the pure-jnp reference (``ref.py``) and a
Pallas TPU kernel. The platform picks the default (``resolve_backend``): on a
TPU every dispatch runs the compiled Pallas kernels; elsewhere the jnp path
runs, and a dispatch that asks for Pallas (per call, or process-wide with
``REPRO_USE_PALLAS=1`` — the CPU test jobs' switch) runs it in interpret
mode, the only way Pallas runs off a TPU.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading

import jax
import jax.numpy as jnp

from . import platform as _platform
from . import ref as _ref

# off a TPU only: run the Pallas kernels (in interpret mode) by default
_CPU_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"

# NV/NQ ratio above which the db-stationary grid wins (each DB tile read once
# from HBM while every query tile's top-k stays resident in VMEM scratch)
_DB_STATIONARY_RATIO = 4


def resolve_backend(
    use_pallas: bool | None, interpret: bool | None
) -> tuple[bool, bool]:
    """(use_pallas, interpret) for one dispatch; ``None`` means "the
    platform's choice": compiled Pallas on a TPU, jnp elsewhere."""
    if use_pallas is None:
        use_pallas = _platform.on_tpu() or _CPU_PALLAS
    return bool(use_pallas), _platform.default_interpret(interpret)


@dataclasses.dataclass
class DispatchStats:
    """Process-wide kernel-dispatch accounting (see core/planner.py).

    ``knn_calls`` counts similarity-scan dispatches (work-unit megabatches and
    the legacy batched path); ``merge_calls`` counts segmented top-k merges.
    ``shapes`` holds the distinct (W, TQ, TV, k) problem shapes seen — a proxy
    for XLA compile-cache pressure that the engine's shape budget bounds.

    ``peak_candidate_bytes`` is the largest candidate merge buffer any single
    execution materialized (scores + ids) — the memory the segmented layout
    exists to shrink on skewed routing. ``lut_expand_bytes`` accumulates the
    bytes of every expanded per-unit [W, TQ, M, 256] ADC LUT operand; the
    resident-table dispatch path never records here, so a zero delta across a
    compressed search is the "no LUT expansion" assertion the tests make.

    Thread-safe: the serving layer's scheduler thread (repro.service) and
    foreground callers both dispatch kernels, so all mutation goes through a
    lock; read a consistent copy with ``snapshot()``.
    """

    knn_calls: int = 0
    merge_calls: int = 0
    shapes: set = dataclasses.field(default_factory=set)
    peak_candidate_bytes: int = 0
    lut_expand_bytes: int = 0
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False
    )

    def record_knn(self, shape: tuple) -> None:
        with self._lock:
            self.knn_calls += 1
            self.shapes.add(shape)
        hook = _PROFILE_HOOK
        if hook is not None:
            hook("knn", shape)

    def record_merge(self) -> None:
        with self._lock:
            self.merge_calls += 1
        hook = _PROFILE_HOOK
        if hook is not None:
            hook("merge", None)

    def record_candidate_bytes(self, nbytes: int) -> None:
        with self._lock:
            self.peak_candidate_bytes = max(self.peak_candidate_bytes, int(nbytes))

    def record_lut_expand(self, nbytes: int) -> None:
        with self._lock:
            self.lut_expand_bytes += int(nbytes)

    def reset(self) -> None:
        with self._lock:
            self.knn_calls = 0
            self.merge_calls = 0
            self.shapes = set()
            self.peak_candidate_bytes = 0
            self.lut_expand_bytes = 0

    def snapshot(self) -> "DispatchStats":
        """Consistent point-in-time copy (counters + shape set)."""
        with self._lock:
            return DispatchStats(
                knn_calls=self.knn_calls,
                merge_calls=self.merge_calls,
                shapes=set(self.shapes),
                peak_candidate_bytes=self.peak_candidate_bytes,
                lut_expand_bytes=self.lut_expand_bytes,
            )

    def delta_since(self, prev: "DispatchStats") -> "DispatchStats":
        """What happened between two snapshots: ``after.delta_since(before)``.

        Running counters subtract; ``shapes`` is the set of shapes first seen
        in the interval; ``peak_candidate_bytes`` is a lifetime high-water
        mark, not a rate, so the delta carries the current value unchanged.
        """
        a, b = self.snapshot(), prev
        return DispatchStats(
            knn_calls=a.knn_calls - b.knn_calls,
            merge_calls=a.merge_calls - b.merge_calls,
            shapes=a.shapes - b.shapes,
            peak_candidate_bytes=a.peak_candidate_bytes,
            lut_expand_bytes=a.lut_expand_bytes - b.lut_expand_bytes,
        )


_DISPATCH = DispatchStats()


def dispatch_stats() -> DispatchStats:
    return _DISPATCH


def reset_dispatch_stats() -> None:
    _DISPATCH.reset()


# Issue-level profiler hook (obs.profile): called as hook(kind, shape) on
# every kernel dispatch — "knn" with the problem shape, "merge" with None —
# so the profiler can report attribution *coverage* (every dispatch its
# plan-level sites did not attribute shows up as issued-but-unattributed).
# One global load when disarmed; obs imports stay lazy from this side.
_PROFILE_HOOK = None


def set_profile_hook(cb) -> None:
    global _PROFILE_HOOK
    _PROFILE_HOOK = cb


@functools.partial(jax.jit, static_argnames=("metric",))
def pairwise_scores(q: jax.Array, v: jax.Array, metric: str = "ip") -> jax.Array:
    """Dense score matrix (no masking/top-k) — plain GEMM, XLA-optimal."""
    return _ref.pairwise_scores_ref(q, v, metric)


def masked_topk(
    q: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    metric: str = "ip",
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused masked similarity top-k. See fused_knn.py for the TPU kernel."""
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    if use_pallas:
        from .fused_knn import fused_knn

        return fused_knn(q, v, valid, k=k, metric=metric, interpret=interpret)
    return _masked_topk_jnp(q, v, valid, k, metric)


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _masked_topk_jnp(q, v, valid, k, metric):
    return _ref.masked_topk_ref(q, v, valid, k, metric)


def batched_masked_topk(
    q: jax.Array,  # [W, TQ, D]  padded work units (see core/planner.py)
    v: jax.Array,  # [W, TV, D]
    valid: jax.Array,  # bool [W, TV]
    k: int,
    *,
    metric: str = "ip",
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """vmapped work-unit execution: the device side of Algorithm 3.

    Each work unit is a (query-group tile × posting-list tile) pair assembled
    by the planner; one call evaluates all units in parallel. Alias of
    ``workunit_topk`` (the engine's entry point), kept for its callers.
    """
    return workunit_topk(
        q, v, valid, k, metric=metric, use_pallas=use_pallas, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("k", "metric"))
def _batched_masked_topk_jnp(q, v, valid, k, metric):
    return jax.vmap(lambda a, b, c: _ref.masked_topk_ref(a, b, c, k, metric))(q, v, valid)


def _unit_scan_fn(k: int, metric: str, use_pallas: bool, interpret: bool):
    """Per-rank/per-bucket work-unit scan body: the ONE place the kernel
    choice lives (db-stationary grid when the vector tile dominates the
    query tile), shared by ``workunit_topk`` and the sharded wrapper so the
    single-device and sharded paths can never diverge on dispatch
    heuristics."""

    def scan(q, v, valid):  # [W, TQ, D], [W, TV, D], [W, TV]
        if use_pallas:
            from .fused_knn import fused_knn, fused_knn_db_stationary

            if v.shape[1] >= _DB_STATIONARY_RATIO * max(int(q.shape[1]), 1):
                fn = functools.partial(
                    fused_knn_db_stationary, k=k, metric=metric, interpret=interpret
                )
            else:
                fn = functools.partial(fused_knn, k=k, metric=metric, interpret=interpret)
            return jax.vmap(fn)(q, v, valid)
        return jax.vmap(lambda a, b, c: _ref.masked_topk_ref(a, b, c, k, metric))(q, v, valid)

    return scan


def workunit_topk(
    q: jax.Array,  # [W, TQ, D]  one bucket's work units (see core/plan.py)
    v: jax.Array,  # [W, TV, D]
    valid: jax.Array,  # bool [W, TV]
    k: int,
    *,
    metric: str = "ip",
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    tag: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Work-unit entry point of the execution engine: one bucket, one dispatch.

    The engine hands every work unit of a shape bucket — across all partitions
    and templates — to a single call. On the Pallas path this picks the
    db-stationary grid of ``fused_knn`` when the vector tile dominates the
    query tile (NV ≫ NQ, the batch-serving shape), and the query-stationary
    grid otherwise (``_unit_scan_fn``). ``tag`` names a dispatch that is not
    a scan bucket in ``DispatchStats.shapes`` (the pq re-rank: ``"rerank"``),
    leading its shape as the ADC dispatches' tags lead theirs, so a list of
    one search's shapes still sorts.
    """
    shape = (q.shape[0], q.shape[1], v.shape[1], int(k))
    _DISPATCH.record_knn(shape if tag is None else (tag,) + shape)
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    if use_pallas:
        return _unit_scan_fn(int(k), metric, True, interpret)(q, v, valid)
    return _batched_masked_topk_jnp(q, v, valid, k, metric)


def workunit_pq_topk(
    luts: jax.Array,  # f32 [W, TQ, Mt >= M, 256]  per-query ADC tables per work unit
    codes: jax.Array,  # uint8 [W, TV, M]     gathered PQ code rows per unit
    valid: jax.Array,  # bool [W, TV]
    k: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Compressed (ADC) work-unit entry point — ``workunit_topk`` over codes.

    One bucket of the engine's compressed scan stage, one dispatch: each work
    unit's TQ lookup tables scan its uint8 code tile via a batched one-hot
    MXU contraction (kernels/pq_scan.py). Codes stay uint8 across the
    dispatch boundary and widen in-register — HBM traffic per scanned row is
    M bytes instead of d·4.
    """
    _DISPATCH.record_knn(
        ("pq", luts.shape[0], luts.shape[1], codes.shape[1], int(k))
    )
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    if use_pallas:
        from .pq_scan import workunit_pq_scan

        return workunit_pq_scan(luts, codes, valid, k=k, interpret=interpret)
    return _workunit_pq_topk_jnp(luts, codes, valid, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _workunit_pq_topk_jnp(luts, codes, valid, k):
    return _ref.workunit_pq_topk_ref(luts, codes, valid, k)


def workunit_pq_topk_resident(
    table: jax.Array,  # f32 [U, Mt >= M, 256] — the workload's resident ADC tables
    lut_idx: jax.Array,  # i32 [W, TQ] — per-slot row into ``table``
    codes: jax.Array,  # uint8 [W, TV, M]
    valid: jax.Array,  # bool [W, TV]
    k: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Compressed work-unit dispatch indexing the resident LUT table directly.

    ``workunit_pq_topk`` takes pre-expanded per-unit [W, TQ, M, 256] tables —
    an operand the caller must materialize per bucket. This entry point takes
    the workload's resident [U, M, 256] table once plus per-slot row indices:
    on the Pallas path the kernel streams each unit's LUT rows from HBM into
    VMEM by DMA, addressed per unit from SMEM (``workunit_pq_scan_streamed``), so
    no [W, TQ, M, 256] array ever exists; on the jnp path each score gathers
    its M table entries directly (``ref.workunit_pq_topk_resident_ref``).
    Numerics match ``workunit_pq_topk`` over ``take(table, lut_idx)`` exactly.
    """
    _DISPATCH.record_knn(
        ("pq-res", lut_idx.shape[0], lut_idx.shape[1], codes.shape[1], int(k))
    )
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    if use_pallas:
        from .pq_scan import workunit_pq_scan_streamed

        return workunit_pq_scan_streamed(
            table, lut_idx, codes, valid, k=int(k), interpret=interpret
        )
    return _workunit_pq_topk_resident_jnp(table, lut_idx, codes, valid, int(k))


@functools.partial(jax.jit, static_argnames=("k",))
def _workunit_pq_topk_resident_jnp(table, lut_idx, codes, valid, k):
    return _ref.workunit_pq_topk_resident_ref(table, lut_idx, codes, valid, k)


def _unit_tiles(rows: jax.Array, starts: jax.Array, lp: int) -> jax.Array:
    """[W, lp, ...] tiles of ``rows``: unit w's are rows
    ``min(starts[w] + arange(lp), N - 1)``, as ``_assemble_bucket`` lays a
    bucket's posting lists out."""
    n = rows.shape[0]
    idx = jnp.minimum(starts[:, None] + jnp.arange(lp, dtype=starts.dtype), n - 1)
    return jnp.take(rows, idx, axis=0, mode="clip")


@functools.partial(jax.jit, static_argnames=("lp",))
def gather_unit_operands(
    rows: jax.Array,  # f32 [N, D] — the arena's resident rows
    q: jax.Array,  # f32 [MQ, D] — the workload's resident queries
    starts: jax.Array,  # i32 [W] — first arena row of each unit's posting list
    qrow_of: jax.Array,  # i32 [W, TQ] — query row per unit slot (-1 pad)
    lp: int,
) -> tuple[jax.Array, jax.Array]:
    """One bucket's ``workunit_topk`` operands, gathered on the device.

    Returns Q f32 [W, TQ, D] (zero rows for pad slots) and V f32 [W, lp, D],
    unit w's rows being ``min(starts[w] + arange(lp), N - 1)``: the tiles a
    host gather from the same arrays builds, bit for bit. A program of its
    own, apart from the scan kernels, so the device trace times the two
    apart.
    """
    v = _unit_tiles(rows, starts, lp)
    live = (qrow_of >= 0)[..., None]
    qt = jnp.take(q, jnp.maximum(qrow_of, 0), axis=0, mode="clip")
    return jnp.where(live, qt, jnp.zeros((), q.dtype)), v


@functools.partial(jax.jit, static_argnames=("lp",))
def gather_unit_codes(
    codes: jax.Array,  # uint8 [N, M] — the arena's resident PQ codes
    starts: jax.Array,  # i32 [W] — first arena row of each unit's posting list
    lp: int,
) -> jax.Array:
    """One bucket's ADC code tiles, uint8 [W, lp, M], gathered on the device
    the way ``gather_unit_operands`` gathers the f32 rows (the same rows,
    bit for bit what ``codes[Vrows]`` gives on the host)."""
    return _unit_tiles(codes, starts, lp)


# --------------------------------------------------------------------------
# Sharded dispatch (device-mesh execution, see core/planner.py's sharded path)
#
# Each wrapper runs ONE shard_map over the mesh's model axis: the leading dim
# of every stacked operand is the rank axis, so rank r executes exactly its
# own slice with the same per-unit math as the single-device kernels (results
# are bit-identical, which the mesh-parity suite asserts). The scan/ADC
# wrappers are collective-free; the only cross-rank traffic in the engine is
# ``sharded_merge_topk``'s all-gather of per-query top-k candidates —
# O(k · |model|) floats+ids per query, never distance rows.

def _shard_map(local, mesh, axis, n_in, n_out, *, out_sharded: bool):
    """shard_map sharding the leading (rank) dim of every operand; outputs
    are rank-major sharded (scan stages) or replicated (the gather merge)."""
    from jax.sharding import PartitionSpec as P

    out = P(axis) if out_sharded else P(None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(P(axis) for _ in range(n_in)),
        out_specs=tuple(out for _ in range(n_out)),
        check_vma=False,
    )


def sharded_workunit_topk(
    mesh,
    axis: str,
    q: jax.Array,  # f32 [R, W, TQ, D] — rank r's work units at [r]
    v: jax.Array,  # f32 [R, W, TV, D]
    valid: jax.Array,  # bool [R, W, TV]
    k: int,
    *,
    metric: str = "ip",
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """``workunit_topk`` across the mesh: one dispatch, every rank its slice.

    The leading dim must equal ``mesh.shape[axis]``; other mesh axes (data,
    pod) replicate — batch parallelism splits the query stream host-side.
    Collective-free: outputs stay rank-major [R, W, TQ, kk] for the host-side
    scatter into per-rank candidate tensors.
    """
    R = q.shape[0]
    _DISPATCH.record_knn(("sh", R, q.shape[1], q.shape[2], v.shape[2], int(k)))
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    fn = _sharded_scan_fn(mesh, axis, int(k), metric, use_pallas, interpret)
    return fn(q, v, valid)


@functools.lru_cache(maxsize=None)
def _sharded_scan_fn(mesh, axis: str, k: int, metric: str, use_pallas: bool, interpret: bool):
    """The jitted program behind ``sharded_workunit_topk``."""
    scan = _unit_scan_fn(k, metric, use_pallas, interpret)

    def local(ql, vl, validl):  # leading dim R/R == 1 per rank
        s, i = scan(ql[0], vl[0], validl[0])
        return s[None], i[None]

    return jax.jit(_shard_map(local, mesh, axis, 3, 2, out_sharded=True))


def sharded_workunit_pq_topk(
    mesh,
    axis: str,
    luts: jax.Array,  # f32 [U, M, 256] — resident ADC tables, REPLICATED
    lut_idx: jax.Array,  # i64 [R, W, TQ] — per-slot row into ``luts``
    codes: jax.Array,  # uint8 [R, W, TV, M] — rank r's gathered code tiles
    valid: jax.Array,  # bool [R, W, TV]
    k: int,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    stream: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Compressed (ADC) sharded scan — ``workunit_pq_topk`` across the mesh.

    The workload's ADC tables ship once, replicated. With ``stream=False``
    (the dense merge layout) each rank expands its per-unit [W, TQ, M, 256]
    LUT operand with an on-device gather before the scan. With ``stream=True``
    (the segmented layout) the rank's kernel indexes the resident table
    directly — the Pallas kernel DMA-streams LUT rows addressed from a
    per-unit SMEM index block, the jnp path gathers table entries per score —
    so the expanded operand never exists. Collective-free either way.
    """
    R = codes.shape[0]
    _DISPATCH.record_knn(("sh-pq", R, codes.shape[1], lut_idx.shape[2], codes.shape[2], int(k)))
    use_pallas, interpret = resolve_backend(use_pallas, interpret)
    fn = _sharded_pq_fn(mesh, axis, int(k), use_pallas, interpret, bool(stream))
    return fn(luts, lut_idx, codes, valid)


@functools.lru_cache(maxsize=None)
def _sharded_pq_fn(mesh, axis: str, k: int, use_pallas: bool, interpret: bool, stream: bool):
    """The jitted program behind ``sharded_workunit_pq_topk``."""
    from jax.sharding import PartitionSpec as P

    def local(luts_l, idx_l, codes_l, valid_l):
        if stream and use_pallas:
            from .pq_scan import workunit_pq_scan_streamed

            s, i = workunit_pq_scan_streamed(
                luts_l, idx_l[0].astype(jnp.int32), codes_l[0], valid_l[0],
                k=k, interpret=interpret,
            )
        elif stream:
            s, i = _ref.workunit_pq_topk_resident_ref(luts_l, idx_l[0], codes_l[0], valid_l[0], k)
        elif use_pallas:
            from .pq_scan import workunit_pq_scan

            per_unit = jnp.take(luts_l, idx_l[0], axis=0)  # [W, TQ, M, 256]
            s, i = workunit_pq_scan(per_unit, codes_l[0], valid_l[0], k=k, interpret=interpret)
        else:
            per_unit = jnp.take(luts_l, idx_l[0], axis=0)
            s, i = _ref.workunit_pq_topk_ref(per_unit, codes_l[0], valid_l[0], k)
        return s[None], i[None]

    return jax.jit(jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    ))


def sharded_merge_topk(
    mesh,
    axis: str,
    scores: jax.Array,  # f32 [R, m, C] — rank r's candidate rows at [r]
    idx: jax.Array,  # i64 [R, m, C] — GLOBAL candidate ids (-1 = absent)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """The engine's only cross-rank step: per-query top-k candidate gather.

    Each rank first reduces its own C candidate columns to its local top-k —
    on-device, collective-free — then ONE all-gather over ``axis`` moves the
    [m, k] survivors (k·|model| candidates per query, independent of DB and
    candidate-tensor size) and a final fused top-k selects the global result,
    replicated to every rank. This is Alg. 3's merge lifted onto the mesh:
    distance rows never cross ranks.
    """
    _DISPATCH.record_merge()
    return _sharded_merge_fn(mesh, axis, int(k))(scores, idx)


@functools.lru_cache(maxsize=None)
def _sharded_merge_fn(mesh, axis: str, k: int):
    """The jitted program behind ``sharded_merge_topk``."""

    def local(sl, il):  # [1, m, C] per rank
        top, pos = jax.lax.top_k(sl[0], k)
        li = jnp.take_along_axis(il[0], pos.astype(il.dtype), axis=1)
        top, li = _ref.normalize_merge_sentinels(top, li)
        all_s = jax.lax.all_gather(top, axis)  # [R, m, k] — THE comm step
        all_i = jax.lax.all_gather(li, axis)
        m = sl.shape[1]
        cat_s = jnp.moveaxis(all_s, 0, 1).reshape(m, -1)
        cat_i = jnp.moveaxis(all_i, 0, 1).reshape(m, -1)
        t, p = jax.lax.top_k(cat_s, k)
        oi = jnp.take_along_axis(cat_i, p.astype(cat_i.dtype), axis=1)
        return _ref.normalize_merge_sentinels(t, oi)

    return jax.jit(_shard_map(local, mesh, axis, 2, 2, out_sharded=False))


def merge_topk(
    scores: jax.Array,  # f32 [m, C] — per-query candidate scores (-inf = absent)
    idx: jax.Array,  # i64 [m, C] — candidate ids (-1 = absent)
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Device-side segmented top-k reduction over per-query candidate rows.

    The engine's final cross-partition merge (Alg. 3 line 12 for the whole
    workload): every query's candidates from every partition, template, and
    probe slot reduce to its top-k in one op instead of a per-(template ×
    partition) numpy merge loop.
    """
    _DISPATCH.record_merge()
    return _merge_topk_jnp(scores, idx, k)


@functools.partial(jax.jit, static_argnames=("k",))
def _merge_topk_jnp(scores, idx, k):
    top, pos = jax.lax.top_k(scores, k)
    out_i = jnp.take_along_axis(idx, pos.astype(idx.dtype), axis=1)
    return _ref.normalize_merge_sentinels(top, out_i)


def segmented_merge_topk(
    flat_s: jax.Array,  # f32 [C, kk] — flat candidate rows (CSR layout)
    flat_i: jax.Array,  # i64 [C, kk] — candidate ids (-1 = absent)
    seg_of: jax.Array,  # i32 [C] — owning query per row, ascending; >= n_segments = pad
    n_segments: int,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Ragged per-query top-k reduction — the segmented ``merge_topk``.

    One dispatch reduces every query's variable-width candidate segment to
    its top-k: queries routed to few partitions no longer pay the widest
    query's ``n_slots`` columns, so the merge buffer is Σ segments·kk instead
    of m·n_slots·kk (and per RANK on the sharded path). Bit-identical to the
    dense merge over the same per-segment candidate order — see
    ``ref.segmented_merge_topk_ref``.
    """
    _DISPATCH.record_merge()
    return _segmented_merge_topk_jnp(flat_s, flat_i, seg_of, int(n_segments), int(k))


@functools.partial(jax.jit, static_argnames=("n_segments", "k"))
def _segmented_merge_topk_jnp(flat_s, flat_i, seg_of, n_segments, k):
    """``ref.segmented_merge_topk_ref`` without its sort: k rounds, each
    taking every segment's best remaining candidate (the first in candidate
    order among equal scores, as the reference's stable sort ranks them)
    with two segment reductions over row maxima. The TPU compiler takes
    about a minute over the reference's sort of a few million candidates,
    and a flush or search compiles one merge per shape; these rounds
    compile in seconds and stay in the [C, kk] layout."""
    C, kk = flat_s.shape
    if n_segments == 0 or C * kk == 0:
        return _ref.segmented_merge_topk_ref(flat_s, flat_i, seg_of, n_segments, k)
    s = flat_s.astype(jnp.float32)
    # pad rows (seg >= n_segments) reduce into one extra, discarded segment
    seg = jnp.minimum(seg_of.astype(jnp.int32), n_segments)
    pos = jnp.arange(C * kk, dtype=jnp.int32).reshape(C, kk)
    absent = jnp.int32(C * kk)

    def segment(reduce, x):
        return reduce(x, seg, num_segments=n_segments + 1, indices_are_sorted=True)

    def take_best(r, carry):
        alive, out_s, out_i = carry
        live_s = jnp.where(alive, s, -jnp.inf)
        best = segment(jax.ops.segment_max, live_s.max(axis=1))
        hit = alive & (live_s == best[seg][:, None])
        first = segment(jax.ops.segment_min, jnp.where(hit, pos, absent).min(axis=1))
        first = first[:n_segments]
        found = first < absent
        # a segment with nothing left points past the last row, each at its
        # own row so the scatter's indices stay unique: its gathers clamp
        # (and are masked), its scatter is dropped
        row = jnp.where(found, first // kk, C + jnp.arange(n_segments, dtype=jnp.int32))
        col = jnp.where(found, first % kk, 0)
        out_s = out_s.at[:, r].set(jnp.where(found, s.at[row, col].get(mode="clip"), -jnp.inf))
        out_i = out_i.at[:, r].set(jnp.where(found, flat_i.at[row, col].get(mode="clip"), -1))
        alive = alive.at[row, col].set(False, mode="drop", unique_indices=True)
        return alive, out_s, out_i

    out = (
        jnp.ones((C, kk), bool),
        jnp.full((n_segments, k), -jnp.inf, jnp.float32),
        jnp.full((n_segments, k), -1, flat_i.dtype),
    )
    _, out_s, out_i = jax.lax.fori_loop(0, k, take_best, out)
    return _ref.normalize_merge_sentinels(out_s, out_i)
