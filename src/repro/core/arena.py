"""Index-wide packed vector arena — the storage side of the execution engine.

Every partition's IVF stores its vectors re-ordered so each posting list is a
contiguous slice (see ivf.py). The arena concatenates those per-partition
``packed`` arrays into ONE index-wide array and exposes a *global* posting-list
table: posting list ``g`` of any partition lives at
``packed[list_start[g] : list_start[g] + list_len[g]]``.

This is what lets the planner bucket work units across partitions and
templates: a single ``packed[rows]`` gather (and a single device transfer)
serves every partition, so one kernel dispatch can mix posting lists from
anywhere in the index. ``gid`` maps packed rows straight back to the caller's
tuple ids (global database rows for HQI, local vector indices for a standalone
IVF), so executor output needs no per-partition id translation.

Compressed storage: when a ``PQCodebook`` is attached, the arena also carries
``codes`` — uint8 [N, M] PQ codes row-aligned with ``packed`` — so the
engine's ADC scan stage gathers M-byte code rows instead of d·4-byte vectors
and the exact re-rank stage gathers the (few) surviving f32 rows from the
same arena. Codes are encoded once per partition block and maintained
incrementally through ``updated()``.

Device residency: ``device_rows()`` uploads ``packed`` to the device once per
arena, on the first f32 scan that needs it, and the executor gathers each
bucket's rows there. In pq mode ``device_codes()`` does the same for the
uint8 codes instead, and the f32 rows stay in host memory, read only by the
exact re-rank: the layout of an index whose rows outgrow the chip. Either
copy is bit for bit, lives and dies with the arena (``updated()`` and
``from_partitions()`` build new arenas, which upload again), and is never
part of ``to_state()``.

Sharded storage: ``shard()`` splits the arena into contiguous *partition*
slices, one per model-axis rank of a device mesh. Because partitions are
contiguous blocks of the packed array, every per-rank structure — f32 rows,
uint8 PQ codes, posting-list table, id map — is a zero-copy view of the base
arena, re-based to rank-local coordinates. ``gid`` stays *global* in every
shard, so the sharded executor's outputs need no cross-rank id translation,
and ``packed_bitmap`` keeps working per shard because bitmap slices are
partition-local already.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..obs.trace import to_device
from . import kmeans as km
from .ivf import IVFIndex
from .pq import PQCodebook, encode_pq


def _nearest_cuts(boundary_rows: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Index of the boundary NEAREST each row target (not the next one up —
    snapping up degenerates badly under skew, e.g. partitions of 10 and 900
    rows split 2 ways must cut at 10, not at the end)."""
    hi = np.clip(
        np.searchsorted(boundary_rows, targets, side="left"),
        1, len(boundary_rows) - 1,
    )
    lo = hi - 1
    pick_lo = (targets - boundary_rows[lo]) <= (boundary_rows[hi] - targets)
    return np.where(pick_lo, lo, hi).astype(np.int64)


# one upload per arena even when the service's scheduler thread and a
# foreground search reach a new arena together
_UPLOAD_LOCK = threading.Lock()


@dataclasses.dataclass
class PackedArena:
    """Concatenated posting-list storage for one or more IVF partitions."""

    packed: np.ndarray  # f32 [N, d] — all partitions, posting-list order
    gid: np.ndarray  # i64 [N] — packed row -> caller tuple id
    local_of: np.ndarray  # i64 [N] — packed row -> partition-local vector idx
    list_start: np.ndarray  # i64 [G] — first packed row of global list g
    list_len: np.ndarray  # i64 [G]
    list_base: np.ndarray  # i64 [P + 1] — partition p owns lists [base[p], base[p+1])
    part_row: np.ndarray  # i64 [P + 1] — partition p owns packed rows [row[p], row[p+1])
    centroids: List[np.ndarray]  # per-partition coarse quantizer
    metric: str
    pq: Optional[PQCodebook] = None  # index-wide codebook (compressed mode)
    codes: Optional[np.ndarray] = None  # uint8 [N, M], row-aligned with packed
    # ``packed`` on the device (``device_rows``); not an init field, so
    # ``dataclasses.replace`` never carries a stale copy to a new arena
    _rows_dev: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )
    # ``codes`` on the device (``device_codes``), likewise
    _codes_dev: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    @property
    def n(self) -> int:
        return int(self.packed.shape[0])

    @property
    def d(self) -> int:
        return int(self.packed.shape[1])

    @property
    def n_parts(self) -> int:
        return len(self.centroids)

    @property
    def n_lists(self) -> int:
        return int(self.list_start.shape[0])

    def n_lists_of(self, part: int) -> int:
        return int(self.list_base[part + 1] - self.list_base[part])

    def probe(self, part: int, q_vecs: np.ndarray, nprobe: int) -> np.ndarray:
        """nprobe nearest posting lists of partition ``part`` as GLOBAL list ids.

        int32 [m, min(nprobe, n_lists_of(part))]. Identical ranking to
        ``IVFIndex.probe`` (same quantizer, same top-m kernel) so engine
        results match the per-query scan path exactly.
        """
        nprobe = int(min(nprobe, self.n_lists_of(part)))
        local = km.topm_centroids(q_vecs, self.centroids[part], nprobe, metric=self.metric)
        return local + np.int32(self.list_base[part])

    def device_rows(self) -> Any:
        """``packed`` resident on the device, f32 [N, d] bit for bit.

        Uploaded on the first call, through the ``arena.h2d`` copy span, and
        kept for the arena's lifetime: the f32 executor gathers every
        bucket's rows from it instead of shipping them per bucket."""
        # The device's default layout: on a TPU that puts N minor (it pads
        # less), so each gather first relays the rows out, ~3 ms for 800 MB
        # on a v5e. A row-major copy saves that but breaks executables
        # loaded from the persistent compile cache, which expect the default.
        if self._rows_dev is None:
            with _UPLOAD_LOCK:
                if self._rows_dev is None:
                    (self._rows_dev,) = to_device("arena.h2d", self.packed)
        return self._rows_dev

    def device_codes(self) -> Any:
        """``codes`` resident on the device, uint8 [N, M] bit for bit.

        Uploaded on the first pq execute, through the ``arena.h2d`` copy
        span (N·M bytes), and kept for the arena's lifetime: the ADC stage
        gathers every bucket's code tiles from it."""
        if self._codes_dev is None:
            with _UPLOAD_LOCK:
                if self._codes_dev is None:
                    (self._codes_dev,) = to_device("arena.h2d", self.codes)
        return self._codes_dev

    def packed_bitmap(self, part: int, local_bitmap: np.ndarray) -> np.ndarray:
        """Partition-local vector-order bitmap -> that partition's packed order."""
        s, e = int(self.part_row[part]), int(self.part_row[part + 1])
        return local_bitmap[self.local_of[s:e]]

    def attach_pq(self, pq: PQCodebook) -> None:
        """Encode the packed rows under ``pq`` (idempotent per codebook).

        Used by the single-index path (``batch_search_ivf``) where the arena
        is built before a codebook exists; ``HQIIndex`` instead passes ``pq``
        at construction so codes ride every (incremental) rebuild.
        """
        if self.pq is pq and self.codes is not None:
            return
        if pq.d != self.d:
            raise ValueError(
                f"PQ codebook shape mismatch: codebook encodes d={pq.d} "
                f"(m={pq.m} subspaces × dsub={pq.dsub}), arena rows have "
                f"d={self.d}"
            )
        self.pq = pq
        self.codes = encode_pq(pq, self.packed)
        self._codes_dev = None

    # ------------------------------------------------------------ persistence

    def to_state(self) -> dict:
        """Snapshot state (store/snapshot.py): arrays stay np.ndarray leaves.

        The arena is derivable from the partitions, but persisting it makes a
        loaded index *warm* — the first engine-backed search after a load
        skips the O(N·d) concatenation (and the O(N·M) re-encode in pq mode)
        and serves straight off the mmap'd blobs.
        """
        state = {
            "metric": self.metric,
            "packed": self.packed,
            "gid": self.gid,
            "local_of": self.local_of,
            "list_start": self.list_start,
            "list_len": self.list_len,
            "list_base": self.list_base,
            "part_row": self.part_row,
            "centroids": {str(p): c for p, c in enumerate(self.centroids)},
            "pq": None if self.pq is None else self.pq.to_state(),
            "codes": self.codes,
        }
        return state

    @staticmethod
    def from_state(state: dict) -> "PackedArena":
        cents = state["centroids"]
        return PackedArena(
            packed=np.asarray(state["packed"]),
            gid=np.asarray(state["gid"]),
            local_of=np.asarray(state["local_of"]),
            list_start=np.asarray(state["list_start"]),
            list_len=np.asarray(state["list_len"]),
            list_base=np.asarray(state["list_base"]),
            part_row=np.asarray(state["part_row"]),
            centroids=[np.asarray(cents[str(p)]) for p in range(len(cents))],
            metric=state["metric"],
            pq=None if state["pq"] is None else PQCodebook.from_state(state["pq"]),
            codes=None if state["codes"] is None else np.asarray(state["codes"]),
        )

    # ------------------------------------------------------------------ shard

    def shard(
        self, n_shards: int, bounds: Optional[Sequence[int]] = None
    ) -> "ShardedArena":
        """Split into contiguous slices, one per model-axis rank.

        The split is at *posting-list* granularity — the finest sharding
        that keeps every work unit's posting list whole on one rank, the
        invariant the sharded executor's bit-exact parity rests on — and
        prefers cuts on whole partition boundaries (the HQI case: each rank
        owns contiguous partition slices, so rows, codes, posting lists, and
        bitmap slices move together) unless partition skew would leave the
        mesh imbalanced, in which case the cut falls on posting-list
        boundaries inside a partition (e.g. the standalone-IVF case, one
        partition spread over every rank).

        ``bounds`` (optional, ``n_shards + 1`` monotone GLOBAL list ids with
        ``bounds[0] == 0`` and ``bounds[-1] == n_lists``) pins the split —
        tests use it to force skewed and empty shards. The default cuts at
        the boundary NEAREST each balanced-row target. Shards are index
        ranges, not copies: the base arena stays the single storage and
        ``gid`` stays global, so no result ever needs per-rank id
        translation.
        """
        n_shards = int(n_shards)
        assert n_shards >= 1, n_shards
        G = self.n_lists
        row_starts = np.append(self.list_start, self.n)  # i64 [G + 1]
        if bounds is None:
            targets = np.arange(1, n_shards) * (self.n / n_shards)
            # candidate splits at both granularities; keep the better-balanced
            # one (partition slices win ties — whole-slice shards are the
            # deployment-friendly layout)
            by_part = self.list_base[
                _nearest_cuts(self.part_row[: self.n_parts + 1], targets)
            ]
            by_list = _nearest_cuts(row_starts, targets)
            candidates = []
            for cuts in (by_part, by_list):
                b = np.concatenate([[0], np.clip(cuts, 0, G), [G]]).astype(np.int64)
                b = np.maximum.accumulate(b)
                candidates.append((int(np.diff(row_starts[b]).max()), b))
            list_bounds = min(candidates, key=lambda c: c[0])[1]
        else:
            list_bounds = np.asarray(bounds, dtype=np.int64)
            assert list_bounds.shape == (n_shards + 1,), list_bounds
            assert list_bounds[0] == 0 and list_bounds[-1] == G, list_bounds
            assert (np.diff(list_bounds) >= 0).all(), list_bounds
        return ShardedArena(
            base=self,
            list_bounds=list_bounds,
            row_bounds=row_starts[list_bounds],
        )

    # ------------------------------------------------------------ constructors

    @staticmethod
    def from_partitions(
        parts: Sequence[Tuple[np.ndarray, IVFIndex]],
        pq: Optional[PQCodebook] = None,
    ) -> "PackedArena":
        """parts: (rows, ivf) pairs; ``rows`` maps ivf-local idx -> caller id."""
        if not parts:
            raise ValueError("arena needs at least one partition")
        metric = parts[0][1].metric
        if len(parts) == 1:
            rows, ivf = parts[0]
            return PackedArena(
                packed=ivf.packed,
                gid=np.asarray(rows, dtype=np.int64)[ivf.order],
                local_of=ivf.order,
                list_start=ivf.offsets[:-1].astype(np.int64),
                list_len=np.diff(ivf.offsets).astype(np.int64),
                list_base=np.array([0, ivf.n_lists], dtype=np.int64),
                part_row=np.array([0, ivf.n], dtype=np.int64),
                centroids=[ivf.centroids],
                metric=metric,
                pq=pq,
                codes=None if pq is None else encode_pq(pq, ivf.packed),
            )
        packed, gid, local_of, starts, lens, cents = [], [], [], [], [], []
        list_base = np.zeros(len(parts) + 1, dtype=np.int64)
        part_row = np.zeros(len(parts) + 1, dtype=np.int64)
        for p, (rows, ivf) in enumerate(parts):
            assert ivf.metric == metric, "mixed-metric partitions"
            packed.append(ivf.packed)
            gid.append(np.asarray(rows, dtype=np.int64)[ivf.order])
            local_of.append(ivf.order)
            starts.append(ivf.offsets[:-1].astype(np.int64) + part_row[p])
            lens.append(np.diff(ivf.offsets).astype(np.int64))
            cents.append(ivf.centroids)
            list_base[p + 1] = list_base[p] + ivf.n_lists
            part_row[p + 1] = part_row[p] + ivf.n
        packed_all = np.concatenate(packed, axis=0)
        return PackedArena(
            packed=packed_all,
            gid=np.concatenate(gid),
            local_of=np.concatenate(local_of),
            list_start=np.concatenate(starts),
            list_len=np.concatenate(lens),
            list_base=list_base,
            part_row=part_row,
            centroids=cents,
            metric=metric,
            pq=pq,
            codes=None if pq is None else encode_pq(pq, packed_all),
        )

    @staticmethod
    def updated(
        old: "PackedArena",
        parts: Sequence[Tuple[np.ndarray, IVFIndex]],
        changed: Sequence[int],
    ) -> "PackedArena":
        """Incremental rebuild after the serving layer extends some partitions.

        ``parts`` is the full current partition list; only partitions in
        ``changed`` are re-derived from their (rows, ivf) pair — every other
        partition's packed block, id map, posting-list table, and PQ code
        block are reused from ``old`` as views (no per-partition recompute or
        re-encode), and only the final concatenation is paid. Partition count
        and order must match.
        """
        assert len(parts) == old.n_parts, "partition count changed; rebuild instead"
        changed_set = set(int(c) for c in changed)
        packed, gid, local_of, starts, lens, cents = [], [], [], [], [], []
        codes: List[np.ndarray] = []
        list_base = np.zeros(len(parts) + 1, dtype=np.int64)
        part_row = np.zeros(len(parts) + 1, dtype=np.int64)
        for p, (rows, ivf) in enumerate(parts):
            assert ivf.metric == old.metric, "mixed-metric partitions"
            if p in changed_set:
                packed.append(ivf.packed)
                gid.append(np.asarray(rows, dtype=np.int64)[ivf.order])
                local_of.append(ivf.order)
                starts.append(ivf.offsets[:-1].astype(np.int64) + part_row[p])
                lens.append(np.diff(ivf.offsets).astype(np.int64))
                if old.pq is not None:
                    codes.append(encode_pq(old.pq, ivf.packed))
                n_p, nl_p = ivf.n, ivf.n_lists
            else:
                r0, r1 = int(old.part_row[p]), int(old.part_row[p + 1])
                l0, l1 = int(old.list_base[p]), int(old.list_base[p + 1])
                packed.append(old.packed[r0:r1])
                gid.append(old.gid[r0:r1])
                local_of.append(old.local_of[r0:r1])
                starts.append(old.list_start[l0:l1] - r0 + part_row[p])
                lens.append(old.list_len[l0:l1])
                if old.pq is not None:
                    codes.append(old.codes[r0:r1])
                n_p, nl_p = r1 - r0, l1 - l0
            cents.append(ivf.centroids)
            list_base[p + 1] = list_base[p] + nl_p
            part_row[p + 1] = part_row[p] + n_p
        return PackedArena(
            packed=np.concatenate(packed, axis=0),
            gid=np.concatenate(gid),
            local_of=np.concatenate(local_of),
            list_start=np.concatenate(starts),
            list_len=np.concatenate(lens),
            list_base=list_base,
            part_row=part_row,
            centroids=cents,
            metric=old.metric,
            pq=old.pq,
            codes=np.concatenate(codes, axis=0) if old.pq is not None else None,
        )

    @staticmethod
    def sharded_from_ivf(ivf: IVFIndex, n_shards: int) -> "ShardedArena":
        """Sharded single-index arena, memoized per shard count.

        The shard is just index bounds over the (memoized) ``from_ivf``
        arena, but still worth caching: repeated sharded ``batch_search_ivf``
        calls over one IVF reuse the split instead of re-deriving boundaries
        per call. Codebook changes need no invalidation — the bounds are
        pq-independent and ``attach_pq``'s code swap is visible through the
        shared ``base`` reference.
        """
        arena = PackedArena.from_ivf(ivf)
        cache = getattr(ivf, "_sharded_cache", None)
        if cache is None:
            cache = ivf._sharded_cache = {}
        key = int(n_shards)
        if key not in cache:
            cache[key] = arena.shard(n_shards)
        return cache[key]

    @staticmethod
    def from_ivf(ivf: IVFIndex) -> "PackedArena":
        """Single-index arena; ``gid`` is the ivf-local vector index.

        Memoized on the (immutable) index instance — repeated
        ``batch_search_ivf`` calls over one IVF pay the O(n) id mapping once.
        """
        arena = getattr(ivf, "_arena_cache", None)
        if arena is None:
            arena = PackedArena.from_partitions([(np.arange(ivf.n, dtype=np.int64), ivf)])
            ivf._arena_cache = arena
        return arena


@dataclasses.dataclass
class ShardedArena:
    """The arena split into per-rank contiguous posting-list ranges.

    Built by ``PackedArena.shard``. Rank r owns global posting lists
    ``[list_bounds[r], list_bounds[r+1])`` and therefore global packed rows
    ``[row_bounds[r], row_bounds[r+1])`` — the sharded planner routes each
    work unit to ``owner_of_list(unit.glist)`` and the compressed path's
    re-rank uses ``owner_of_row`` to hand every rank exactly the candidate
    rows it stores. An empty range is a rank with no data (all rows on other
    ranks), which executes as fully-masked padding.
    """

    base: PackedArena
    list_bounds: np.ndarray  # i64 [R + 1] — global posting-list split
    row_bounds: np.ndarray  # i64 [R + 1] — global packed-row split

    @property
    def n_shards(self) -> int:
        return len(self.list_bounds) - 1

    @property
    def rows_per_shard(self) -> np.ndarray:
        return np.diff(self.row_bounds)

    def owner_of_list(self, glists: np.ndarray) -> np.ndarray:
        """Owning rank per global list id (duplicate bounds = empty shards)."""
        return np.searchsorted(self.list_bounds, glists, side="right") - 1

    def owner_of_row(self, rows: np.ndarray) -> np.ndarray:
        """Owning rank per global packed row."""
        return np.searchsorted(self.row_bounds, rows, side="right") - 1
