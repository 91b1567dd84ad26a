"""Distributed batch hybrid search: the plan/execute engine on a device mesh.

Mapping of HQI onto the (pod, data, model) mesh:

  * the ``PackedArena`` is sharded over the **model** axis as contiguous
    partition slices (``PackedArena.shard``): each rank owns a slice of the
    f32 rows, uint8 PQ codes, posting-list table, and bitmap slices;
  * the plan is replicated: ``build_plan_sharded`` routes every engine task
    to its partition's owner rank, so each rank executes exactly its shard's
    work units — bucket dispatches (``workunit_topk`` / ``workunit_pq_topk``)
    run inside ``shard_map`` with every rank's units stacked along the model
    axis, and bitmap pushdown / PQ compose unchanged;
  * the only cross-rank traffic is the per-query top-k candidate all-gather
    of ``ops.sharded_merge_topk`` — O(k · |model|) (score, id) pairs per
    query, independent of DB size, never distance rows;
  * the query stream splits over **data** (and **pod**) host-side — batch
    parallelism, queries never need to see each other, so the serving layer
    (or the pods themselves) partition the stream and every model group
    answers its slice independently.

``execute_sharded`` is the whole entry point: ``HQIIndex.search`` routes
through it when ``HQIConfig.mesh`` is set, and ``batch_search_ivf(mesh=...)``
uses it for standalone indexes. Results are bit-identical to the
single-device engine — tests/test_engine_sharded.py proves it across mesh
sizes on CPU host devices (``--xla_force_host_platform_device_count``).

``make_roofline_search_step`` survives for the dry-run: it models the
sharded engine's device program (per-rank tiled scans + the k·|model|
candidate gather) at 100M-vector scale where the host-side planner is
abstracted to a resident full scan — a roofline row, not a search API.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..kernels import ref as kref
from .arena import ShardedArena
from .ivf import ScanStats
from .plan import EngineTask, PlanConfig, build_plan_sharded
from .planner import ExtraCandidates, ShardStats, execute_plan_sharded


@dataclasses.dataclass(frozen=True)
class ShardSpec:
    """Which mesh axis the engine shards the arena over.

    Only ``model_axis`` is read: every other mesh axis (data, pod)
    replicates — the query stream splits over those host-side at the
    serving layer, where each group runs its slice of the workload through
    this engine independently.
    """

    model_axis: str = "model"

    def n_shards(self, mesh: Mesh) -> int:
        return int(mesh.shape[self.model_axis])


def execute_sharded(
    sharded: ShardedArena,
    tasks: List[EngineTask],
    q_vecs: np.ndarray,  # f32 [m, d]
    *,
    mesh: Mesh,
    spec: Optional[ShardSpec] = None,
    m: int,
    k: int,
    cfg: Optional[PlanConfig] = None,
    extra: Sequence[ExtraCandidates] = (),
    stats: Optional[ScanStats] = None,
) -> Tuple[np.ndarray, np.ndarray, ShardStats]:
    """Plan + execute one workload's vector work across the mesh.

    The thin mesh entry: replicate the plan (``build_plan_sharded`` routes
    tasks to arena-shard owners), execute with per-rank bucket dispatches and
    the all-gather top-k merge. Returns (scores f32 [m, k], ids i64 [m, k],
    per-rank ``ShardStats``) — scores/ids bit-identical to the single-device
    ``build_plan``/``execute_plan`` pair.
    """
    spec = ShardSpec() if spec is None else spec
    cfg = PlanConfig() if cfg is None else cfg
    assert sharded.n_shards == spec.n_shards(mesh), (
        f"arena sharded {sharded.n_shards} ways but mesh axis "
        f"{spec.model_axis!r} has {spec.n_shards(mesh)} ranks"
    )
    splan = build_plan_sharded(
        sharded, tasks, q_vecs, m=m, k=k, cfg=cfg, stats=stats
    )
    shard_stats = ShardStats.zeros(sharded.n_shards)
    scores, ids = execute_plan_sharded(
        splan, sharded, q_vecs,
        mesh=mesh, axis=spec.model_axis, cfg=cfg,
        extra=extra, stats=stats, shard_stats=shard_stats,
    )
    return scores, ids, shard_stats


# ----------------------------------------------------------- dry-run roofline


def make_roofline_search_step(mesh: Mesh, *, k: int, metric: str = "ip", db_tile: int = 16_384):
    """jit'd (db, bitmap, queries) -> (scores, ids): the dry-run's model of
    the sharded engine's device program at production scale.

    Each model rank scans its resident row shard as fixed-shape tiles with a
    running masked top-k (the work-unit schedule with host planning
    abstracted to a dense scan: the M×N score matrix never materializes,
    HBM traffic is one shard read + O(M·k)), then the k·|model| candidate
    all-gather and a static merge select the global top-k — the same
    communication structure ``ops.sharded_merge_topk`` gives the real
    engine. This is a roofline/HLO-cost row ("hqi-search"), not a search
    API: real searches go through ``execute_sharded``.
    """
    baxes = _batch_axes(mesh)

    def tiled_scan(queries, db, bitmap):
        n = db.shape[0]
        if n <= db_tile:
            return kref.masked_topk_ref(queries, db, bitmap, k, metric)
        nt = (n + db_tile - 1) // db_tile
        npad = nt * db_tile
        dbp = jnp.pad(db, ((0, npad - n), (0, 0)))
        bmp = jnp.pad(bitmap, (0, npad - n))
        mq = queries.shape[0]

        def step(carry, inp):
            rs, ri = carry
            dtile, btile, off = inp
            s, i = kref.masked_topk_ref(queries, dtile, btile, k, metric)
            gi = jnp.where(i >= 0, i + off, -1)
            cat_s = jnp.concatenate([rs, s], axis=1)
            cat_i = jnp.concatenate([ri, gi], axis=1)
            top, pos = jax.lax.top_k(cat_s, k)
            return (top, jnp.take_along_axis(cat_i, pos, axis=1)), None

        init = (
            jnp.full((mq, k), kref.NEG_INF, jnp.float32),
            jnp.full((mq, k), -1, jnp.int32),
        )
        tiles = dbp.reshape(nt, db_tile, -1)
        bts = bmp.reshape(nt, db_tile)
        offs = jnp.arange(nt, dtype=jnp.int32) * db_tile
        (rs, ri), _ = jax.lax.scan(step, init, (tiles, bts, offs))
        ri = jnp.where(jnp.isfinite(rs) & (rs > kref.NEG_INF / 2), ri, -1)
        return rs, ri

    def local(db, bitmap, queries):
        # per-device shapes: db [N/mp, d], bitmap [N/mp], queries [M/dp, d]
        n_local = db.shape[0]
        shard_idx = jax.lax.axis_index("model")
        scores, idx = tiled_scan(queries, db, bitmap)
        gids = jnp.where(idx >= 0, idx + shard_idx * n_local, -1)
        # THE cross-rank step: k·|model| candidates per query, never rows
        all_s = jax.lax.all_gather(scores, "model")
        all_i = jax.lax.all_gather(gids, "model")
        mshards = all_s.shape[0]
        cat_s = jnp.moveaxis(all_s, 0, 1).reshape(queries.shape[0], mshards * k)
        cat_i = jnp.moveaxis(all_i, 0, 1).reshape(queries.shape[0], mshards * k)
        top_s, pos = jax.lax.top_k(cat_s, k)
        top_i = jnp.take_along_axis(cat_i, pos, axis=1)
        return top_s, top_i

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model", None), P("model"), P(baxes, None)),
        out_specs=(P(baxes, None), P(baxes, None)),
        check_vma=False,
    )
    return jax.jit(fn)


def _batch_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def roofline_search_specs(mesh: Mesh, *, n: int, d: int, m: int):
    """ShapeDtypeStructs with shardings for the dry-run."""
    baxes = _batch_axes(mesh)
    sh = lambda spec: NamedSharding(mesh, spec)
    return (
        jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=sh(P("model", None))),
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=sh(P("model"))),
        jax.ShapeDtypeStruct((m, d), jnp.float32, sharding=sh(P(baxes, None))),
    )
