"""Pallas TPU kernels: fused ADC scan (PQ lookup-table distances) + top-k.

Two grids over compressed (PQ) code storage:

  * ``pq_scan`` — the original one-query grid: ONE query's [M, 256] LUT stays
    resident in VMEM while uint8 code tiles stream from HBM.
  * ``workunit_pq_scan`` / ``workunit_pq_scan_streamed`` — the engine's
    batched work-unit grid ``[W, TQ, TV]``: each unit carries TQ per-query
    LUTs (expanded per unit, or DMA-streamed from the resident [U, Mp, 256]
    table), VMEM-resident across that unit's whole code sweep, and scans
    uint8 code tiles with a loop over the M subspaces, one one-hot MXU
    contraction ``[TQ, 256] @ [256, TV]`` each, added in the jnp
    reference's order (``_adc_unit_scores``). Results fold into the same
    running-top-k VMEM scratch as fused_knn.

HBM traffic per scanned row is the CODE bytes (d·4/M× less than raw vectors —
the FAISS IVF-PQ family scan, TPU-shaped). Codes ship as uint8 end to end and
widen to int32 in-register; padding them to int32 host-side would quadruple
the code-tile traffic and defeat the point.

Any M works, not only multiples of 8: a tile's [TV, M] codes are turned
subspace-major in VMEM, and only the tables' subspace axis is padded (to
``subspace_pad(M)``, zero rows that are never read), so each row keeps M
bytes of code in HBM. VMEM per unit at the published width (M = 50,
TQ = 64): the streamed LUT rows and their subspace-major copy, 3.7 MB each;
per subspace step a [256, TV] one-hot (512 KB at TV = 512) — the loop
keeps it one subspace wide for any M.

Gather note: Mosaic supports small-table gathers via one-hot matmul when
dynamic gather is unavailable; we express the lookup as
``lut_j @ one_hot(codes_j)`` — an MXU-friendly contraction (interpret mode
validates numerics either way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .fused_knn import HIGHEST, NEG_INF, _merge_topk, fit_tile
from .platform import default_interpret
from .ref import adc_sum


def _pq_scan_kernel(
    lut_ref,  # [M, 256] f32 — ONE query's tables
    codes_ref,  # [TV, M] uint8
    valid_ref,  # [1, TV] int32
    out_s_ref,  # [1, K]
    out_i_ref,  # [1, K]
    acc_s_ref,  # scratch [1, K]
    acc_i_ref,  # scratch [1, K]
    *,
    k: int,
    tv: int,
    m: int,
    nv_tiles: int,
):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_s_ref[...] = jnp.full(acc_s_ref.shape, NEG_INF, jnp.float32)
        acc_i_ref[...] = jnp.full(acc_i_ref.shape, -1, jnp.int32)

    codes = codes_ref[...].astype(jnp.int32)  # [TV, M] — widen in-register
    lut = lut_ref[...]  # [M, 256]
    # LUT gather as one-hot matmul per subspace (MXU-friendly, Mosaic-safe)
    scores = jnp.zeros((codes.shape[0],), jnp.float32)
    for sub in range(m):
        onehot = (
            codes[:, sub][:, None] == jax.lax.broadcasted_iota(jnp.int32, (tv, 256), 1)
        ).astype(jnp.float32)
        scores = scores + jax.lax.dot_general(
            onehot, lut[sub], (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32,
        )
    valid = valid_ref[0, :] != 0
    scores = jnp.where(valid, scores, NEG_INF)[None, :]  # [1, TV]
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gidx = jnp.where(valid[None, :], col + j * tv, -1)

    new_s, new_i = _merge_topk(acc_s_ref[...], acc_i_ref[...], scores, gidx, k)
    acc_s_ref[...] = new_s
    acc_i_ref[...] = new_i

    @pl.when(j == nv_tiles - 1)
    def _flush():
        out_s_ref[...] = new_s
        out_i_ref[...] = new_i


@functools.partial(jax.jit, static_argnames=("k", "tv", "interpret"))
def pq_scan(
    lut: jax.Array,  # f32 [M, 256] — one query
    codes: jax.Array,  # uint8 [NV, M]
    valid: jax.Array,  # bool [NV]
    *,
    k: int,
    tv: int = 1024,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    nv, m = codes.shape
    nv_p = max(tv, ((nv + tv - 1) // tv) * tv)
    # keep the code tiles uint8 across the dispatch boundary — int32 padding
    # would 4× the HBM traffic the compressed scan exists to avoid
    codes_p = jnp.zeros((nv_p, m), jnp.uint8).at[:nv].set(codes.astype(jnp.uint8))
    valid_p = jnp.zeros((1, nv_p), jnp.int32).at[0, :nv].set(valid.astype(jnp.int32))
    nv_tiles = nv_p // tv
    kernel = functools.partial(_pq_scan_kernel, k=k, tv=tv, m=m, nv_tiles=nv_tiles)
    call = pl.pallas_call(
        kernel,
        grid=(nv_tiles,),
        in_specs=[
            pl.BlockSpec((m, 256), lambda j: (0, 0)),  # LUT resident
            pl.BlockSpec((tv, m), lambda j: (j, 0)),
            pl.BlockSpec((1, tv), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, k), lambda j: (0, 0)),
            pl.BlockSpec((1, k), lambda j: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, k), jnp.float32),
            jax.ShapeDtypeStruct((1, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.int32),
        ],
        interpret=default_interpret(interpret),
    )
    s, i = call(lut, codes_p, valid_p)
    return s[0], i[0]


# ---------------------------------------------------------------------------
# Batched work-unit ADC scan (the engine's compressed execution kernel)
# ---------------------------------------------------------------------------


def subspace_pad(m: int) -> int:
    """A table's subspace axis padded to the f32 sublane tile (8): the
    streamed kernel DMAs whole ``[Mp, 256]`` slabs, and Mosaic refuses a
    slice of the tiled axis that is not a multiple of 8 (M = 50 -> 56)."""
    return -(-int(m) // 8) * 8


def _pad_unit_tiles(codes, valid, nv_p):
    """Pad each unit's code rows to ``nv_p`` (uint8) and lay its valid mask
    out as [W, 1, nv_p] int32, so the mask's (1, 1, TV) block keeps the
    last two block dims at (full, TV) as the TPU lowering requires."""
    w, nv, m = codes.shape
    if nv_p != nv:
        codes = jnp.zeros((w, nv_p, m), jnp.uint8).at[:, :nv].set(codes.astype(jnp.uint8))
    valid_p = jnp.zeros((w, 1, nv_p), jnp.int32).at[:, 0, :nv].set(valid.astype(jnp.int32))
    return codes.astype(jnp.uint8), valid_p


def _lut_by_subspace(flat, lut_t, m: int) -> None:
    """Lay one unit's tables out subspace-major: ``flat`` [TQ, Mt·256]
    (Mt >= M, zero rows past M) -> ``lut_t`` [Mp, TQ, 256], so the
    subspace loop reads subspace j as one leading-axis slab. Once per unit."""
    for j in range(m):
        lut_t[j] = flat[:, j * 256 : (j + 1) * 256]


def _adc_unit_scores(lut_t, codes_ref, codes_t, tv: int, m: int):
    """[TQ, TV] ADC scores of one code tile: ``lut_t`` [Mp, TQ, 256] f32,
    ``codes_ref`` [1, TV, M] uint8, ``codes_t`` an i32 [Mp, TV] scratch.

    The tile's codes are widened and turned subspace-major once; then a loop
    over the M subspaces builds subspace j's one-hot [256, TV] and contracts
    it with j's tables on the MXU, which selects exactly one table entry per
    (query, row). The loop keeps the program (and VMEM) the same size for
    any M: the one-hot is one subspace wide, never [TV, M·256]. Terms are
    added one subspace at a time in order, as ``ref.adc_sum`` adds them, so
    the kernel matches the jnp reference bit for bit."""
    codes_t[0:m, :] = codes_ref[0].astype(jnp.int32).T  # [M, TV]
    iota = jax.lax.broadcasted_iota(jnp.int32, (256, tv), 0)

    def term(j):
        onehot = (iota == codes_t[pl.ds(j, 1), :]).astype(jnp.float32)  # [256, TV]
        return jax.lax.dot_general(
            lut_t[j], onehot, (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )

    return jax.lax.fori_loop(1, m, lambda j, acc: acc + term(j), term(0))


def _scan_tile(lut_t, codes_ref, codes_t, valid_ref, out_s_ref, out_i_ref,
               acc_s_ref, acc_i_ref, *, k: int, tv: int, m: int, nv_tiles: int):
    """Score one code tile and fold it into the unit's running top-k."""
    j = pl.program_id(1)
    scores = _adc_unit_scores(lut_t, codes_ref, codes_t, tv, m)  # [TQ, TV]
    valid = valid_ref[0, 0, :] != 0
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gidx = jnp.where(valid[None, :], col + j * tv, -1)

    new_s, new_i = _merge_topk(acc_s_ref[...], acc_i_ref[...], scores, gidx, k)
    acc_s_ref[...] = new_s
    acc_i_ref[...] = new_i

    @pl.when(j == nv_tiles - 1)
    def _flush():
        out_s_ref[...] = new_s[None]
        out_i_ref[...] = new_i[None]


def _init_acc(acc_s_ref, acc_i_ref) -> None:
    acc_s_ref[...] = jnp.full(acc_s_ref.shape, NEG_INF, jnp.float32)
    acc_i_ref[...] = jnp.full(acc_i_ref.shape, -1, jnp.int32)


def _workunit_pq_kernel(
    lut_ref,  # [1, TQ, Mt*256] f32 — this unit's per-query tables, flattened
    codes_ref,  # [1, TV, M] uint8
    valid_ref,  # [1, 1, TV] int32
    out_s_ref,  # [1, TQ, K]
    out_i_ref,  # [1, TQ, K]
    lut_t,  # scratch f32 [Mp, TQ, 256] — the unit's tables, subspace-major
    codes_t,  # scratch i32 [Mp, TV] — the tile's codes, subspace-major
    acc_s_ref,  # scratch f32 [TQ, K]
    acc_i_ref,  # scratch i32 [TQ, K]
    *,
    k: int,
    tv: int,
    m: int,
    nv_tiles: int,
):
    # code tile (inner) — w outer, so scratch is per-unit
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _init_acc(acc_s_ref, acc_i_ref)
        _lut_by_subspace(lut_ref[0], lut_t, m)

    _scan_tile(lut_t, codes_ref, codes_t, valid_ref, out_s_ref, out_i_ref,
               acc_s_ref, acc_i_ref, k=k, tv=tv, m=m, nv_tiles=nv_tiles)


# the device trace's op names of the two ADC grids ("%pq_adc_scan.1",
# "%pq_adc_scan_streamed.1"), which the benchmark's ADC roofline reads
ADC_SCAN_NAME = "pq_adc_scan"
ADC_SCAN_STREAMED_NAME = "pq_adc_scan_streamed"


@functools.partial(jax.jit, static_argnames=("k", "tv", "interpret"))
def workunit_pq_scan(
    luts: jax.Array,  # f32 [W, TQ, Mt, 256] — per-query ADC tables per unit (Mt >= M)
    codes: jax.Array,  # uint8 [W, NV, M] — gathered code rows per unit
    valid: jax.Array,  # bool [W, NV]
    *,
    k: int,
    tv: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Work-unit grid [W, TQ, TV] over compressed codes.

    Returns (scores f32 [W, TQ, k] best-first, idx i32 [W, TQ, k]; -1 = none).
    The LUT block of a unit stays VMEM-resident across its code sweep; code
    tiles ship as uint8 and widen in-register. M is the codes' width; a
    table's subspace rows past M (zero padding) are never read.
    """
    w, tq, mt, nbook = luts.shape
    assert nbook == 256, "PQ codebooks are 8-bit (256 entries)"
    nv, m = codes.shape[1], codes.shape[2]
    assert mt >= m, (mt, m)
    k = int(k)
    tv = fit_tile(tv, nv)
    nv_p = max(tv, ((nv + tv - 1) // tv) * tv)
    codes_p, valid_p = _pad_unit_tiles(codes, valid, nv_p)
    luts_f = luts.reshape(w, tq, mt * nbook)
    nv_tiles = nv_p // tv

    kernel = functools.partial(
        _workunit_pq_kernel, k=k, tv=tv, m=m, nv_tiles=nv_tiles
    )
    call = pl.pallas_call(
        kernel,
        grid=(w, nv_tiles),  # unit outer, code tile inner
        in_specs=[
            pl.BlockSpec((1, tq, mt * nbook), lambda w_, j: (w_, 0, 0)),
            pl.BlockSpec((1, tv, m), lambda w_, j: (w_, j, 0)),
            pl.BlockSpec((1, 1, tv), lambda w_, j: (w_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, k), lambda w_, j: (w_, 0, 0)),
            pl.BlockSpec((1, tq, k), lambda w_, j: (w_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w, tq, k), jnp.float32),
            jax.ShapeDtypeStruct((w, tq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((subspace_pad(m), tq, nbook), jnp.float32),
            pltpu.VMEM((subspace_pad(m), tv), jnp.int32),
            pltpu.VMEM((tq, k), jnp.float32),
            pltpu.VMEM((tq, k), jnp.int32),
        ],
        interpret=default_interpret(interpret),
        name=ADC_SCAN_NAME,
    )
    return call(luts_f, codes_p, valid_p)


# ---------------------------------------------------------------------------
# Streamed-LUT work-unit ADC scan: the resident table never expands
# ---------------------------------------------------------------------------


def _workunit_pq_streamed_kernel(
    idx_ref,  # SMEM i32 [1, 1, TQ] — this unit's LUT row per query slot
    table_ref,  # HBM f32 [U, Mp, 256] — the resident ADC table, NEVER expanded
    codes_ref,  # [1, TV, M] uint8
    valid_ref,  # [1, 1, TV] int32
    out_s_ref,  # [1, TQ, K]
    out_i_ref,  # [1, TQ, K]
    lut_vmem,  # scratch f32 [TQ, Mp, 256] — this unit's streamed LUT rows
    lut_t,  # scratch f32 [Mp, TQ, 256] — the same, subspace-major
    codes_t,  # scratch i32 [Mp, TV]
    acc_s_ref,  # scratch f32 [TQ, K]
    acc_i_ref,  # scratch i32 [TQ, K]
    sem,  # DMA completion semaphore
    *,
    k: int,
    tv: int,
    tq: int,
    m: int,
    mp: int,
    nv_tiles: int,
):
    # code tile (inner) — w outer, so scratch is per-unit
    @pl.when(pl.program_id(1) == 0)
    def _init():
        _init_acc(acc_s_ref, acc_i_ref)
        # gather this unit's TQ LUT rows HBM -> VMEM, addressed through the
        # unit's index block in SMEM: the per-unit [TQ, Mp, 256] block is
        # BUILT in VMEM by DMA, so no [W, TQ, M, 256] operand is ever
        # materialized. Each copy lands a whole [Mp, 256] slab at a
        # leading-dim offset (a single row of a 2-D [TQ, Mp*256] buffer, or
        # a slab of M rows not a multiple of 8, would cut the (8, 128) VMEM
        # tiling); all TQ copies are in flight before the first wait
        dmas = [
            pltpu.make_async_copy(table_ref.at[idx_ref[0, 0, t]], lut_vmem.at[t], sem)
            for t in range(tq)
        ]
        for dma in dmas:
            dma.start()
        for dma in dmas:
            dma.wait()
        _lut_by_subspace(lut_vmem[...].reshape(tq, mp * 256), lut_t, m)

    _scan_tile(lut_t, codes_ref, codes_t, valid_ref, out_s_ref, out_i_ref,
               acc_s_ref, acc_i_ref, k=k, tv=tv, m=m, nv_tiles=nv_tiles)


@functools.partial(jax.jit, static_argnames=("k", "tv", "interpret"))
def workunit_pq_scan_streamed(
    table: jax.Array,  # f32 [U, Mt, 256] — resident per-query ADC tables (Mt >= M)
    lut_idx: jax.Array,  # i32 [W, TQ] — LUT row per unit slot (0 for padding)
    codes: jax.Array,  # uint8 [W, NV, M] — gathered code rows per unit
    valid: jax.Array,  # bool [W, NV]
    *,
    k: int,
    tv: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Work-unit ADC grid that streams LUT rows straight out of the resident
    table.

    Same [W, TV] sweep and one-hot MXU contraction as ``workunit_pq_scan``,
    but the per-unit LUT block is assembled in VMEM by per-row DMA from the
    [U, Mp, 256] HBM table, addressed through the unit's [TQ] index block,
    which the grid pipelines into SMEM unit by unit (prefetching all W·TQ
    indices at once would outgrow SMEM at W in the tens of thousands). The
    [W, TQ, M, 256] expansion — W·TQ/U×
    redundant HBM traffic plus its allocation — is gone; each unit reads
    exactly the TQ rows it scans with.

    M is the codes' width. The table's subspace axis is DMAed in slabs of
    ``subspace_pad(M)`` rows: the engine builds it that wide, zero-padded
    (``core.pq.adc_tables_device``); a narrower one is padded here, a copy
    per call.

    Returns (scores f32 [W, TQ, k] best-first, idx i32 [W, TQ, k]; -1 = none).
    """
    _, mt, nbook = table.shape
    assert nbook == 256, "PQ codebooks are 8-bit (256 entries)"
    w, tq = lut_idx.shape
    nv, m = codes.shape[1], codes.shape[2]
    mp = subspace_pad(m)
    assert mt >= m, (mt, m)
    if mt != mp:
        table = jnp.pad(table[:, :m], ((0, 0), (0, mp - m), (0, 0)))
    k = int(k)
    tv = fit_tile(tv, nv)
    nv_p = max(tv, ((nv + tv - 1) // tv) * tv)
    codes_p, valid_p = _pad_unit_tiles(codes, valid, nv_p)
    nv_tiles = nv_p // tv

    kernel = functools.partial(
        _workunit_pq_streamed_kernel, k=k, tv=tv, tq=tq, m=m, mp=mp, nv_tiles=nv_tiles
    )
    call = pl.pallas_call(
        kernel,
        grid=(w, nv_tiles),  # unit outer, code tile inner
        in_specs=[
            pl.BlockSpec((1, 1, tq), lambda w_, j: (w_, 0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # table stays in HBM
            pl.BlockSpec((1, tv, m), lambda w_, j: (w_, j, 0)),
            pl.BlockSpec((1, 1, tv), lambda w_, j: (w_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, k), lambda w_, j: (w_, 0, 0)),
            pl.BlockSpec((1, tq, k), lambda w_, j: (w_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((w, tq, k), jnp.float32),
            jax.ShapeDtypeStruct((w, tq, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tq, mp, nbook), jnp.float32),
            pltpu.VMEM((mp, tq, nbook), jnp.float32),
            pltpu.VMEM((mp, tv), jnp.int32),
            pltpu.VMEM((tq, k), jnp.float32),
            pltpu.VMEM((tq, k), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
        ],
        interpret=default_interpret(interpret),
        name=ADC_SCAN_STREAMED_NAME,
    )
    idx = lut_idx.astype(jnp.int32).reshape(w, 1, tq)
    return call(idx, table, codes_p, valid_p)
