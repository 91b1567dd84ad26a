"""Pallas TPU kernel: fused masked-distance + top-k (the HQI hot loop).

This is Algorithm 3 line 10 + the Section 4.2 bitmap pushdown as one kernel:
for a tile of grouped query vectors and a tile of a posting list, compute
similarity scores on the MXU (one ``q_tile @ v_tileᵀ`` matmul), apply the
attribute-filter bitmap as a -inf mask *in VMEM*, and fold the tile into a
running per-query top-k carried in VMEM scratch across the vector-tile grid
dimension. HBM traffic is O(nq·k + nv·d) instead of O(nq·nv): the full
distance matrix is never materialized.

TPU adaptation notes (vs the paper's CPU/FAISS loop):
  * posting lists are padded to TV-aligned tiles; padding rows are masked via
    the same ``valid`` bitmap the attribute filter uses — zero extra cost;
  * the per-query result heap becomes an unrolled K-pass selection merge
    (K is small, ≤ 16 in all HQI configs), which lowers to pure
    max/compare/select ops — no sort network, MXU stays the bottleneck;
  * tiles are 128-aligned so the matmul maps onto the 128×128 MXU.

Grid: (nq_tiles, nv_tiles); the vector-tile dim is innermost so the running
top-k scratch for a query tile stays live in VMEM across its whole sweep.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import default_interpret

NEG_INF = float(-3.4e38)
# f32 contractions at full f32 precision on the MXU, in the kernels and in
# their jnp references alike, so both paths score identically on a TPU
HIGHEST = jax.lax.Precision.HIGHEST


def fit_tile(cap: int, n: int) -> int:
    """Tile length for an axis of ``n`` rows: the power of two covering
    ``n`` (at least 8, the f32 sublane count), never above ``cap`` — a
    32-row posting list scans as one 32-row tile, not a padded 512-row one."""
    return min(cap, max(8, 1 << max(0, n - 1).bit_length()))


def _merge_topk(run_s, run_i, tile_s, tile_i, k: int):
    """Select top-k of concat(running[k], tile[TV]) per row. Unrolled K-pass

    selection — only max/eq/where ops (Mosaic-safe).
    run_s f32 [TQ,K], run_i i32 [TQ,K], tile_s f32 [TQ,TV], tile_i i32 [TQ,TV].
    """
    cat_s = jnp.concatenate([run_s, tile_s], axis=1)  # [TQ, K+TV]
    cat_i = jnp.concatenate([run_i, tile_i], axis=1)
    width = cat_s.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, cat_s.shape, 1)
    out_s, out_i = [], []
    for _ in range(k):
        m = jnp.max(cat_s, axis=1, keepdims=True)  # [TQ,1]
        is_m = cat_s == m
        # first position attaining the max (stable tie-break)
        first = jnp.min(jnp.where(is_m, pos, width), axis=1, keepdims=True)
        sel = pos == first
        out_s.append(m[:, 0])
        out_i.append(jnp.sum(jnp.where(sel, cat_i, 0), axis=1))
        # a taken slot becomes an absent one: once only sentinels remain, a
        # later pass may pick it again, and must not return its id twice
        cat_s = jnp.where(sel, NEG_INF, cat_s)
        cat_i = jnp.where(sel, -1, cat_i)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1).astype(jnp.int32)


def _fused_knn_kernel(
    q_ref,  # [TQ, D]
    v_ref,  # [TV, D]
    valid_ref,  # [1, TV] int32 (0/1)
    out_s_ref,  # [TQ, K]
    out_i_ref,  # [TQ, K]
    acc_s_ref,  # scratch f32 [TQ, K]
    acc_i_ref,  # scratch i32 [TQ, K]
    *,
    k: int,
    tv: int,
    metric: str,
    nv_tiles: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_s_ref[...] = jnp.full(acc_s_ref.shape, NEG_INF, jnp.float32)
        acc_i_ref[...] = jnp.full(acc_i_ref.shape, -1, jnp.int32)

    q = q_ref[...].astype(jnp.float32)  # [TQ, D]
    v = v_ref[...].astype(jnp.float32)  # [TV, D]
    ip = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )  # [TQ, TV] on the MXU
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)  # [TQ,1]
        vn = jnp.sum(v * v, axis=1)[None, :]  # [1,TV]
        scores = 2.0 * ip - qn - vn
    else:
        scores = ip
    valid = valid_ref[0, :] != 0  # [TV]
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gidx = col + j * tv  # global vector index
    gidx = jnp.where(valid[None, :], gidx, -1)

    new_s, new_i = _merge_topk(acc_s_ref[...], acc_i_ref[...], scores, gidx, k)
    acc_s_ref[...] = new_s
    acc_i_ref[...] = new_i

    @pl.when(j == nv_tiles - 1)
    def _flush():
        out_s_ref[...] = acc_s_ref[...]
        out_i_ref[...] = acc_i_ref[...]


def _fused_knn_db_stationary_kernel(
    q_ref,  # [TQ, D]
    v_ref,  # [TV, D]
    valid_ref,  # [1, TV]
    out_s_ref,  # [TQ, K]
    out_i_ref,  # [TQ, K]
    acc_s_ref,  # scratch f32 [NQP, K] — ALL query tiles' running top-k
    acc_i_ref,  # scratch i32 [NQP, K]
    *,
    k: int,
    tq: int,
    tv: int,
    metric: str,
):
    """DB-stationary grid (v outer, q inner): each DB tile is read ONCE from

    HBM and every query tile's running top-k lives in VMEM scratch across the
    whole sweep. HBM traffic drops from O(nq_tiles · NV · d) to
    O(NV·d + NQ·d·nv_tiles) — the right order when NV ≫ NQ (batch search
    against a big posting-list/index shard, the HQI serving shape)."""
    j = pl.program_id(0)  # v tile (outer)
    i = pl.program_id(1)  # q tile (inner)

    @pl.when(j == 0)
    def _init():
        acc_s_ref[pl.ds(i * tq, tq), :] = jnp.full((tq, k), NEG_INF, jnp.float32)
        acc_i_ref[pl.ds(i * tq, tq), :] = jnp.full((tq, k), -1, jnp.int32)

    q = q_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    ip = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32,
    )
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)
        vn = jnp.sum(v * v, axis=1)[None, :]
        scores = 2.0 * ip - qn - vn
    else:
        scores = ip
    valid = valid_ref[0, :] != 0
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    gidx = jnp.where(valid[None, :], col + j * tv, -1)

    run_s = acc_s_ref[pl.ds(i * tq, tq), :]
    run_i = acc_i_ref[pl.ds(i * tq, tq), :]
    new_s, new_i = _merge_topk(run_s, run_i, scores, gidx, k)
    acc_s_ref[pl.ds(i * tq, tq), :] = new_s
    acc_i_ref[pl.ds(i * tq, tq), :] = new_i
    # the output block of query tile i is revisited (and written back) at
    # every v tile, so it always holds the running top-k — never an
    # unwritten buffer; the last v tile's write-back is the final answer
    out_s_ref[...] = new_s
    out_i_ref[...] = new_i


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "tq", "tv", "interpret"),
)
def fused_knn_db_stationary(
    q: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    *,
    k: int,
    metric: str = "ip",
    tq: int = 128,
    tv: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """DB-stationary variant — preferred when NV ≫ NQ (see kernel docstring).

    VMEM budget: scratch is (NQ_padded, k) floats+ints ≈ 12·NQ·k bytes; with
    k=10 a full 64k-query batch fits in ~8 MB of VMEM."""
    nq, d = q.shape
    nv = v.shape[0]
    k = int(k)
    tq, tv = fit_tile(tq, nq), fit_tile(tv, nv)
    nq_p = max(tq, ((nq + tq - 1) // tq) * tq)
    nv_p = max(tv, ((nv + tv - 1) // tv) * tv)
    q_p = jnp.zeros((nq_p, d), q.dtype).at[:nq].set(q)
    v_p = jnp.zeros((nv_p, d), v.dtype).at[:nv].set(v)
    valid_p = jnp.zeros((1, nv_p), jnp.int32).at[0, :nv].set(valid.astype(jnp.int32))
    nq_tiles, nv_tiles = nq_p // tq, nv_p // tv

    kernel = functools.partial(
        _fused_knn_db_stationary_kernel,
        k=k, tq=tq, tv=tv, metric=metric,
    )
    call = pl.pallas_call(
        kernel,
        grid=(nv_tiles, nq_tiles),  # v outer, q inner
        in_specs=[
            pl.BlockSpec((tq, d), lambda j, i: (i, 0)),
            pl.BlockSpec((tv, d), lambda j, i: (j, 0)),
            pl.BlockSpec((1, tv), lambda j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda j, i: (i, 0)),
            pl.BlockSpec((tq, k), lambda j, i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_p, k), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((nq_p, k), jnp.float32),
            pltpu.VMEM((nq_p, k), jnp.int32),
        ],
        interpret=default_interpret(interpret),
    )
    out_s, out_i = call(q_p, v_p, valid_p)
    return out_s[:nq], out_i[:nq]


@functools.partial(
    jax.jit,
    static_argnames=("k", "metric", "tq", "tv", "interpret"),
)
def fused_knn(
    q: jax.Array,  # [NQ, D]
    v: jax.Array,  # [NV, D]
    valid: jax.Array,  # bool [NV]
    *,
    k: int,
    metric: str = "ip",
    tq: int = 128,
    tv: int = 512,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Returns (scores f32 [NQ,k] best-first, idx i32 [NQ,k]; -1 = none).

    NQ, NV need not be tile-aligned — we pad here; D should be modest (the
    whole vector fits one block; HQI embeddings are 64–256 dims).
    """
    nq, d = q.shape
    nv = v.shape[0]
    k = int(k)
    tq, tv = fit_tile(tq, nq), fit_tile(tv, nv)
    nq_p = max(tq, ((nq + tq - 1) // tq) * tq)
    nv_p = max(tv, ((nv + tv - 1) // tv) * tv)
    q_p = jnp.zeros((nq_p, d), q.dtype).at[:nq].set(q)
    v_p = jnp.zeros((nv_p, d), v.dtype).at[:nv].set(v)
    valid_p = jnp.zeros((1, nv_p), jnp.int32).at[0, :nv].set(valid.astype(jnp.int32))
    nq_tiles, nv_tiles = nq_p // tq, nv_p // tv

    kernel = functools.partial(
        _fused_knn_kernel, k=k, tv=tv, metric=metric, nv_tiles=nv_tiles
    )
    call = pl.pallas_call(
        kernel,
        grid=(nq_tiles, nv_tiles),
        in_specs=[
            pl.BlockSpec((tq, d), lambda i, j: (i, 0)),
            pl.BlockSpec((tv, d), lambda i, j: (j, 0)),
            pl.BlockSpec((1, tv), lambda i, j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((tq, k), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq_p, k), jnp.float32),
            jax.ShapeDtypeStruct((nq_p, k), jnp.int32),
        ],
        # Running top-k per query tile, carried in VMEM across the inner grid dim.
        scratch_shapes=[
            pltpu.VMEM((tq, k), jnp.float32),
            pltpu.VMEM((tq, k), jnp.int32),
        ],
        interpret=default_interpret(interpret),
    )
    out_s, out_i = call(q_p, v_p, valid_p)
    return out_s[:nq], out_i[:nq]
