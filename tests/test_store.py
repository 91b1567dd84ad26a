"""Persistence & recovery: snapshot round-trip parity, WAL crash recovery,
compaction, and delta-store compression.

The load-bearing guarantees:

  * a saved-then-loaded index answers **bit-identically** (ids AND scores)
    to the in-memory original, across metrics, scan modes, and mesh on/off;
  * after a crash, ``open_service`` recovers every ACKNOWLEDGED insert and
    delete (committed to the WAL before the ack) with the same external ids,
    and cleanly drops the unacknowledged torn tail;
  * compaction folds + re-snapshots without changing any answer, and prunes
    generations/WAL segments no recovery path needs;
  * once the live delta outgrows ``ServiceConfig.delta_pq_threshold`` (and
    the index has a codebook), flush scans run compressed (ADC + exact
    re-rank) — under the threshold they stay exact f32.
"""
import os

import numpy as np
import pytest

from repro.core import HQIConfig, HQIIndex, PackedArena, train_pq
from repro.core.types import Workload
from repro.kernels import ops as kops
from repro.service import HQIService, ServiceConfig
from repro.store import (
    Compactor,
    WriteAheadLog,
    init_store,
    list_generations,
    load_snapshot,
    open_service,
    pin_generation,
    pinned_generations,
    prune_generations,
    save_snapshot,
    unpin_generation,
)
from repro.store.wal import _HEADER, _MAGIC

from conftest import small_db, small_workload

EXACT = 10_000  # nprobe past every list count: search becomes exact


def _build(metric="ip", scan_mode=None, n=1500, seed=0, n_queries=40):
    db = small_db(n=n, d=16, seed=seed, metric=metric)
    wl = small_workload(db, n_queries=n_queries, seed=seed + 1)
    cfg = HQIConfig(min_partition_size=128, max_leaves=8)
    if scan_mode == "pq":
        cfg = HQIConfig(
            min_partition_size=128, max_leaves=8, scan_mode="pq", pq_m=4,
            refine_factor=4,
        )
    return db, wl, HQIIndex.build(db, wl, cfg)


def _one_dev_mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("model",))


# ---------------------------------------------------------------------------
# Snapshot round-trip parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("metric", ["ip", "l2"])
@pytest.mark.parametrize("scan_mode", [None, "pq"])
@pytest.mark.parametrize("mesh", [False, True])
def test_roundtrip_parity(tmp_path, metric, scan_mode, mesh):
    """build → save → load → bit-identical ids+scores, every configuration."""
    _, wl, hqi = _build(metric=metric, scan_mode=scan_mode)
    if mesh:
        hqi.cfg.mesh = _one_dev_mesh()
    r0 = hqi.search(wl, nprobe=4)
    save_snapshot(tmp_path, hqi)
    loaded = load_snapshot(str(tmp_path)).index
    if mesh:
        loaded.cfg.mesh = _one_dev_mesh()
    r1 = loaded.search(wl, nprobe=4)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.scores, r1.scores)
    # the adaptive/per-query path must agree too (routing + bitmap cache)
    o0 = hqi.search_online(wl, nprobe=4)
    o1 = loaded.search_online(wl, nprobe=4)
    np.testing.assert_array_equal(o0.ids, o1.ids)
    np.testing.assert_array_equal(o0.scores, o1.scores)


def test_loaded_snapshot_is_warm(tmp_path):
    """Load restores the arena (rows + codes) and the Router bitmap cache —
    no O(N) recompute before the first engine search."""
    _, wl, hqi = _build(scan_mode="pq")
    hqi.search(wl, nprobe=4)  # materialize arena + populate bitmap cache
    assert hqi.router._bitmap_cache
    save_snapshot(tmp_path, hqi)
    loaded = load_snapshot(str(tmp_path)).index
    assert loaded._arena is not None
    assert loaded._arena.codes is not None and loaded._arena.pq is not None
    assert set(loaded.router._bitmap_cache) == set(hqi.router._bitmap_cache)
    for filt, bm in hqi.router._bitmap_cache.items():
        np.testing.assert_array_equal(bm, loaded.router._bitmap_cache[filt])


def test_snapshot_carries_no_device_rows(tmp_path):
    """The arena's resident device rows are a cache: never in ``to_state``,
    absent after a load, and uploaded again by the loaded index's first
    f32 search, which answers as the original does."""
    import jax

    _, wl, hqi = _build()
    r0 = hqi.search(wl, nprobe=4)
    assert hqi.arena._rows_dev is not None  # the f32 search made them resident
    leaves = jax.tree_util.tree_leaves(hqi.arena.to_state())
    assert not any(isinstance(x, jax.Array) for x in leaves)
    save_snapshot(tmp_path, hqi)
    loaded = load_snapshot(str(tmp_path)).index
    assert loaded._arena is not None and loaded._arena._rows_dev is None
    r1 = loaded.search(wl, nprobe=4)
    assert loaded._arena._rows_dev is not None
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.scores, r1.scores)


def test_roundtrip_after_extend(tmp_path):
    """A snapshot taken after live folds round-trips the grown index."""
    db, wl, hqi = _build()
    hqi.search(wl, nprobe=4)
    from repro.core.types import VectorDatabase

    new = db.take(np.arange(7))
    new = VectorDatabase(
        vectors=new.vectors + 0.01, columns=new.columns, metric=db.metric,
        ids=db.n + np.arange(7, dtype=np.int64),
    )
    hqi.extend(new)
    r0 = hqi.search(wl, nprobe=EXACT)
    save_snapshot(tmp_path, hqi)
    loaded = load_snapshot(str(tmp_path)).index
    r1 = loaded.search(wl, nprobe=EXACT)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.scores, r1.scores)


def test_roundtrip_property():
    """Hypothesis sweep: save→load parity holds on random configurations."""
    pytest.importorskip("hypothesis", reason="property tests need the [test] extra")
    import tempfile

    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.integers(0, 50),
        metric=st.sampled_from(["ip", "l2"]),
        pq=st.booleans(),
        k=st.integers(1, 8),
    )
    def check(seed, metric, pq, k):
        db = small_db(n=900, d=16, seed=seed, metric=metric)
        wl = small_workload(db, n_queries=20, seed=seed + 1, k=k)
        cfg = HQIConfig(
            min_partition_size=128, max_leaves=8,
            scan_mode="pq" if pq else None, pq_m=4,
        )
        hqi = HQIIndex.build(db, wl, cfg)
        r0 = hqi.search(wl, nprobe=3)
        with tempfile.TemporaryDirectory() as tmp:
            save_snapshot(tmp, hqi)
            loaded = load_snapshot(tmp).index
        r1 = loaded.search(wl, nprobe=3)
        np.testing.assert_array_equal(r0.ids, r1.ids)
        np.testing.assert_array_equal(r0.scores, r1.scores)

    check()


def test_generation_fallback_and_prune(tmp_path):
    """A torn newest generation is skipped; pruning keeps CURRENT loadable."""
    _, wl, hqi = _build(n=900, n_queries=16)
    r0 = hqi.search(wl, nprobe=3)
    save_snapshot(tmp_path, hqi)
    save_snapshot(tmp_path, hqi)
    # simulate a crash that tore generation 2: blob missing entirely
    gen2 = tmp_path / "gen-000002"
    os.remove(gen2 / "arrays" / "index.db.vectors.npy")
    snap = load_snapshot(str(tmp_path))
    assert snap.generation == 1
    r1 = snap.index.search(wl, nprobe=3)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    # a truncated blob (partial write) is also detected
    save_snapshot(tmp_path, hqi)  # gen 3, complete
    blob = tmp_path / "gen-000003" / "arrays" / "index.db.vectors.npy"
    with open(blob, "r+b") as f:
        f.truncate(64)
    assert load_snapshot(str(tmp_path)).generation == 1
    # prune keeps the newest `keep` (and never the CURRENT target)
    save_snapshot(tmp_path, hqi)  # gen 4
    prune_generations(str(tmp_path), keep=1)
    assert list_generations(str(tmp_path)) == ["gen-000004"]
    assert load_snapshot(str(tmp_path)).generation == 4


def test_prune_keep_zero_and_pins(tmp_path):
    """Regression: ``keep=0`` silently deleted NOTHING despite the "all but
    the newest keep" contract. It now prunes everything except CURRENT and
    pinned generations; negative keep raises."""
    import pytest

    _, wl, hqi = _build(n=600, n_queries=8)
    for _ in range(4):
        save_snapshot(tmp_path, hqi)  # gen 1..4; CURRENT -> gen-000004
    root = str(tmp_path)
    with pytest.raises(ValueError):
        prune_generations(root, keep=-1)
    # pinned generations survive any keep (the tuner's rollback target)
    pin_generation(root, "gen-000002")
    assert pinned_generations(root) == {"gen-000002"}
    doomed = prune_generations(root, keep=0)
    assert sorted(doomed) == ["gen-000001", "gen-000003"]
    assert list_generations(root) == ["gen-000002", "gen-000004"]
    assert load_snapshot(root).generation == 4  # CURRENT untouched
    # explicit pinned= argument works too; unpinning re-exposes to pruning
    unpin_generation(root, "gen-000002")
    assert prune_generations(root, keep=0, pinned=("gen-000002",)) == []
    assert prune_generations(root, keep=0) == ["gen-000002"]
    assert list_generations(root) == ["gen-000004"]


# ---------------------------------------------------------------------------
# WAL + crash recovery
# ---------------------------------------------------------------------------


def _svc_pair(tmp_path, wl, hqi, **cfg_kw):
    kw = dict(k=wl.k, nprobe=EXACT, max_batch=16, deadline_s=0.0)
    kw.update(cfg_kw)
    return init_store(str(tmp_path), hqi, cfg=ServiceConfig(**kw))


def _answers(svc, wl):
    handles = [
        svc.submit(wl.vectors[i], wl.templates[wl.template_of[i]])
        for i in range(wl.m)
    ]
    svc.drain()
    return np.stack([h.ids for h in handles]), np.stack([h.scores for h in handles])


def test_recovery_restores_acknowledged_writes(tmp_path):
    """Acknowledged inserts/deletes survive a crash with identical answers."""
    db, wl, hqi = _build(metric="l2")
    svc = _svc_pair(tmp_path, wl, hqi)
    rng = np.random.default_rng(7)
    ids_a = svc.insert(db.vectors[:5] + 0.01)
    svc.delete([int(ids_a[1]), 3, 3])  # delta + indexed + repeat (no-op)
    ids_b = svc.insert(rng.normal(size=(4, db.d)).astype(np.float32))
    a_ids, a_scores = _answers(svc, wl)

    # "crash": drop the in-memory service, reopen from disk
    svc.wal.close()
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    assert svc2.n_live == svc.n_live
    np.testing.assert_array_equal(np.sort(svc2.live_ids()), np.sort(svc.live_ids()))
    b_ids, b_scores = _answers(svc2, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_scores, b_scores)
    # id assignment continues exactly where the crashed process would have
    nxt = svc2.insert(db.vectors[:1])
    assert int(nxt[0]) == int(ids_b[-1]) + 1


def test_crash_mid_wal_append_drops_only_the_tail(tmp_path):
    """A record torn mid-append (crash during write) is dropped; every
    earlier (acknowledged) record survives."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    acked = svc.insert(db.vectors[:3] + 0.05)
    svc.delete([int(acked[2])])
    svc.wal.close()

    seg = os.path.join(str(tmp_path), "wal", svc.wal.segments()[-1])
    with open(seg, "ab") as f:
        # a torn insert: intact header claiming 500 payload bytes, only 20
        # made it to disk before the "crash"
        f.write(_HEADER.pack(_MAGIC, 99, 1, 500, 0) + b"x" * 20)

    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    live = set(svc2.live_ids().tolist())
    assert int(acked[0]) in live and int(acked[1]) in live
    assert int(acked[2]) not in live  # the acknowledged delete survived
    # the torn record contributed nothing and the log is appendable again
    nxt = svc2.insert(db.vectors[:1])
    assert int(nxt[0]) == int(acked[-1]) + 1
    svc3 = open_service(str(tmp_path), cfg=svc.cfg)
    assert int(nxt[0]) in set(svc3.live_ids().tolist())


def test_corrupt_payload_detected_by_crc(tmp_path):
    """Bit rot inside a sealed segment's committed payload raises loudly —
    acknowledged records sit behind the damage, silent drop is data loss."""
    from repro.store.wal import WalCorruptionError

    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.insert(db.vectors[:2])
    svc.insert(db.vectors[2:4])
    svc.wal.close()  # seals the segment (close == rotate)
    seg = os.path.join(str(tmp_path), "wal", svc.wal.segments()[-1])
    size = os.path.getsize(seg)
    with open(seg, "r+b") as f:
        f.seek(size - 24)  # inside record 2's payload, before the seal frame
        f.write(b"\xff\xff\xff")
    with pytest.raises(WalCorruptionError, match="sealed segment"):
        open_service(str(tmp_path))


def test_refresh_rotates_and_compaction_prunes(tmp_path):
    """refresh() seals the WAL segment; compaction snapshots at the fold
    point and prunes generations + covered segments."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.insert(db.vectors[:4] + 0.01)
    assert len(svc.wal.segments()) == 1
    svc.refresh()
    svc.insert(db.vectors[4:6] + 0.01)
    assert len(svc.wal.segments()) == 2  # rotation at the fold boundary

    comp = Compactor(svc, str(tmp_path), keep_generations=1, min_delta_rows=1)
    assert comp.compact_once() == "gen-000002"
    assert list_generations(str(tmp_path)) == ["gen-000002"]
    # gen-2 covers every record: every sealed segment is prunable
    assert svc.wal.segments() == []
    # ... and the log stays appendable, continuing the sequence
    svc.insert(db.vectors[6:7] + 0.01)
    assert len(svc.wal.segments()) == 1
    # post-compaction recovery needs no replayed pre-fold inserts
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    a_ids, a_s = _answers(svc, wl)
    b_ids, b_s = _answers(svc2, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_s, b_s)


def test_background_compactor_thread(tmp_path):
    """start()/stop() drives fold→snapshot cycles without answer drift."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    comp = Compactor(svc, str(tmp_path), interval_s=0.01, min_delta_rows=1)
    comp.start()
    import time

    rng = np.random.default_rng(11)
    for _ in range(4):
        svc.insert(rng.normal(size=(3, db.d)).astype(np.float32))
        time.sleep(0.03)
    comp.stop()
    assert comp.generations_written >= 1
    a_ids, a_s = _answers(svc, wl)
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    b_ids, b_s = _answers(svc2, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_s, b_s)


def test_seq_continues_after_full_wal_prune(tmp_path):
    """Compaction may prune EVERY segment; recovered services must keep
    committing ABOVE the snapshot's seq or the next recovery would skip
    acknowledged writes as already-covered."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.insert(db.vectors[:4] + 0.01)
    comp = Compactor(svc, str(tmp_path), keep_generations=1)
    comp.compact_once(force=True)
    comp.compact_once(force=True)  # no new writes: same wal_seq, prunes all
    assert svc.wal.segments() == []
    svc.wal.close()

    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    acked = svc2.insert(db.vectors[4:6] + 0.01)  # seqs must resume > covered
    svc3 = open_service(str(tmp_path), cfg=svc.cfg)
    live = set(svc3.live_ids().tolist())
    assert int(acked[0]) in live and int(acked[1]) in live


def test_sealed_segment_corruption_is_not_truncated(tmp_path):
    """Mid-log bit rot in a SEALED segment stops replay conservatively but
    must not destroy the bytes (only the open segment's torn tail is
    repaired)."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.insert(db.vectors[:2])
    svc.refresh()  # seals segment 1
    svc.insert(db.vectors[2:4])  # opens segment 2
    svc.wal.close()
    segs = svc.wal.segments()
    assert len(segs) == 2
    sealed = os.path.join(str(tmp_path), "wal", segs[0])
    size = os.path.getsize(sealed)
    with open(sealed, "r+b") as f:
        f.seek(size - 3)
        f.write(b"\xff\xff\xff")
    wal = WriteAheadLog(os.path.join(str(tmp_path), "wal"))
    assert os.path.getsize(sealed) == size  # bytes kept for forensics
    wal.close()
    # ... and recovery refuses to serve with acknowledged records
    # unreachable behind the rot, instead of silently dropping them
    from repro.store.wal import WalCorruptionError

    with pytest.raises(WalCorruptionError, match="sealed segment"):
        open_service(str(tmp_path), cfg=svc.cfg)


def test_delete_only_interval_still_seals_and_prunes(tmp_path):
    """Tombstones of indexed rows never touch the delta, but their WAL
    records must still be sealed + pruned by compaction (they are covered
    by the snapshot's live mask)."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.delete(np.arange(0, 30, 3))
    comp = Compactor(svc, str(tmp_path), keep_generations=1)
    assert comp.compact_once(force=True) is not None
    assert svc.wal.segments() == []  # delete-only segment sealed + covered
    svc.wal.close()
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    a_ids, a_s = _answers(svc, wl)
    b_ids, b_s = _answers(svc2, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_s, b_s)
    assert svc2.n_live == svc.n_live


def test_fallback_when_blob_torn_inside_header_margin(tmp_path):
    """A blob truncated by less than the npy header passes the cheap size
    check but fails at load — the loader must fall back, not crash."""
    _, wl, hqi = _build(n=900, n_queries=16)
    r0 = hqi.search(wl, nprobe=3)
    save_snapshot(tmp_path, hqi)
    save_snapshot(tmp_path, hqi)
    blob = tmp_path / "gen-000002" / "arrays" / "index.db.vectors.npy"
    size = os.path.getsize(blob)
    with open(blob, "r+b") as f:
        f.truncate(size - 40)  # within the ~128 B header margin
    snap = load_snapshot(str(tmp_path))
    assert snap.generation == 1
    r1 = snap.index.search(wl, nprobe=3)
    np.testing.assert_array_equal(r0.ids, r1.ids)


def test_rejected_insert_is_never_logged(tmp_path):
    """Validation failures happen BEFORE the WAL commit: a rejected insert
    leaves neither a log record nor visible rows."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    seq_before = svc.wal.last_seq
    n_before = svc.n_live
    with pytest.raises(AssertionError, match="unknown columns"):
        svc.insert(db.vectors[:1], columns={"no_such_column": np.zeros(1)})
    assert svc.wal.last_seq == seq_before
    assert svc.n_live == n_before
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)  # replay stays clean
    assert svc2.n_live == n_before


def test_snapshot_handles_pathological_column_names(tmp_path):
    """Column names flow into blob filenames; separators must not escape."""
    from repro.core.types import Column, VectorDatabase

    rng = np.random.default_rng(0)
    db = VectorDatabase(
        vectors=rng.normal(size=(600, 16)).astype(np.float32),
        columns={"a/b c": Column.numeric("a/b c", rng.random(600))},
        metric="ip",
    )
    from repro.core.predicates import NotNull, make_filter

    wl = Workload(
        vectors=rng.normal(size=(8, 16)).astype(np.float32),
        templates=[make_filter(NotNull("a/b c"))],
        template_of=np.zeros(8, dtype=np.int32),
        k=5,
    )
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=128, max_leaves=4))
    r0 = hqi.search(wl, nprobe=EXACT)
    save_snapshot(tmp_path, hqi)
    loaded = load_snapshot(str(tmp_path)).index
    r1 = loaded.search(wl, nprobe=EXACT)
    np.testing.assert_array_equal(r0.ids, r1.ids)
    np.testing.assert_array_equal(r0.scores, r1.scores)


def test_init_store_over_reused_root_covers_stale_wal(tmp_path):
    """Re-bootstrapping over a previously used root must not leave the old
    incarnation's WAL records replayable into the new index."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    stale = svc.insert(db.vectors[:2] + 0.5)  # incarnation 1's records
    svc.wal.close()

    _, _, hqi2 = _build(seed=3)  # operator rebuilds from scratch
    svc2 = _svc_pair(tmp_path, wl, hqi2)
    fresh = svc2.insert(db.vectors[2:4] + 0.5)
    svc2.wal.close()

    svc3 = open_service(str(tmp_path), cfg=svc2.cfg)  # must not resurrect
    live = set(svc3.live_ids().tolist())
    assert int(fresh[0]) in live and int(fresh[1]) in live
    assert svc3.n_live == svc2.n_live
    a_ids, _ = _answers(svc2, wl)
    b_ids, _ = _answers(svc3, wl)
    np.testing.assert_array_equal(a_ids, b_ids)


def test_corruption_in_covered_segment_does_not_block_recovery(tmp_path):
    """Bit rot in a retained-but-snapshot-covered segment is skipped: the
    newest snapshot + WAL tail can fully serve the restart."""
    db, wl, hqi = _build()
    svc = _svc_pair(tmp_path, wl, hqi)
    svc.insert(db.vectors[:3] + 0.01)
    comp = Compactor(svc, str(tmp_path), keep_generations=2)
    comp.compact_once()  # gen-2 covers seg-1; seg-1 retained for gen-1
    covered = svc.wal.segments()
    assert len(covered) == 1
    acked = svc.insert(db.vectors[3:5] + 0.01)  # opens segment 2
    svc.wal.close()
    seg1 = os.path.join(str(tmp_path), "wal", covered[0])
    with open(seg1, "r+b") as f:
        f.seek(30)
        f.write(b"\xff\xff\xff")  # interior rot in the covered segment
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    live = set(svc2.live_ids().tolist())
    assert int(acked[0]) in live and int(acked[1]) in live
    assert svc2.n_live == svc.n_live


def test_wal_reopen_resumes_seq(tmp_path):
    """Reopening a WAL continues the sequence; replay(after_seq) filters."""
    wal = WriteAheadLog(str(tmp_path / "wal"))
    s1 = wal.log_insert(np.zeros((2, 4), np.float32), np.array([10, 11]))
    s2 = wal.log_delete([10])
    wal.close()
    wal2 = WriteAheadLog(str(tmp_path / "wal"))
    assert wal2.last_seq == s2 == 2
    s3 = wal2.log_delete([11])
    recs = list(wal2.replay(after_seq=s1))
    assert [r.seq for r in recs] == [s2, s3]
    wal2.close()


# ---------------------------------------------------------------------------
# Delta-store compression (ROADMAP satellite)
# ---------------------------------------------------------------------------


def _pq_service(tmp_path, threshold):
    db, wl, hqi = _build(metric="l2", scan_mode="pq")
    svc = HQIService(
        hqi,
        ServiceConfig(
            k=wl.k, nprobe=EXACT, max_batch=16, deadline_s=0.0,
            delta_pq_threshold=threshold,
        ),
    )
    return db, wl, svc


def test_delta_pq_scan_over_threshold(tmp_path):
    """Past the threshold the delta scans compressed (pq-tagged dispatch);
    with full refine the answers stay exactly equal to the f32 scan."""
    db, wl, svc = _pq_service(tmp_path, threshold=8)
    rng = np.random.default_rng(5)
    n_new = 40
    svc.index.cfg.plan.refine_factor = (n_new // wl.k) + 1  # full refine: exact
    svc.insert(rng.normal(size=(n_new, db.d)).astype(np.float32))

    kops.reset_dispatch_stats()
    a_ids, a_s = _answers(svc, wl)
    shapes = kops.dispatch_stats().snapshot().shapes
    assert any(s[0] == "pq" for s in shapes), shapes  # compressed delta scan

    # identical workload through the exact path (threshold disabled)
    svc.cfg.delta_pq_threshold = None
    b_ids, b_s = _answers(svc, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_s, b_s)


def test_delta_pq_under_threshold_stays_exact(tmp_path):
    """At or under the threshold no ADC dispatch happens on the delta."""
    db, wl, svc = _pq_service(tmp_path, threshold=4096)
    svc.insert(db.vectors[:6] + 0.01)
    kops.reset_dispatch_stats()
    _answers(svc, wl)
    shapes = kops.dispatch_stats().snapshot().shapes
    assert not any(s[0] == "pq" for s in shapes), shapes


def test_delta_pq_respects_tombstones_and_filters(tmp_path):
    """Compressed delta scans still honor deletes and template bitmaps."""
    db, wl, svc = _pq_service(tmp_path, threshold=4)
    rng = np.random.default_rng(9)
    svc.index.cfg.plan.refine_factor = 64
    ids = svc.insert(rng.normal(size=(20, db.d)).astype(np.float32))
    svc.delete(ids[:10])
    a_ids, _ = _answers(svc, wl)
    dead = set(int(i) for i in ids[:10])
    assert not (set(a_ids[a_ids >= 0].tolist()) & dead)


# ---------------------------------------------------------------------------
# Codebook-shape validation (satellite fix)
# ---------------------------------------------------------------------------


def test_attach_pq_rejects_mismatched_codebook():
    rng = np.random.default_rng(0)
    from repro.core import IVFIndex

    vecs = rng.normal(size=(256, 16)).astype(np.float32)
    ivf = IVFIndex.build(vecs, metric="l2", n_centroids=4)
    arena = PackedArena.from_ivf(ivf)
    bad = train_pq(rng.normal(size=(256, 24)).astype(np.float32), 4, metric="l2")
    with pytest.raises(ValueError, match=r"d=24.*d=16"):
        arena.attach_pq(bad)
    assert arena.pq is None and arena.codes is None  # attach left no residue


def test_encode_pq_rejects_mismatched_vectors():
    from repro.core import encode_pq

    rng = np.random.default_rng(0)
    cb = train_pq(rng.normal(size=(512, 16)).astype(np.float32), 4, metric="l2")
    with pytest.raises(ValueError, match=r"m=4.*dsub=4.*d=20"):
        encode_pq(cb, rng.normal(size=(8, 20)).astype(np.float32))


# ---------------------------------------------------------------------------
# WAL group commit
# ---------------------------------------------------------------------------


def _count_fsync(monkeypatch, delay_s=0.002):
    """Replace os.fsync with a counting (optionally slowed) stand-in; the
    delay widens the group-commit window so followers actually pile up."""
    import time

    import repro.store.wal as wal_mod

    calls = []
    real = os.fsync

    def counting(fd):
        calls.append(fd)
        if delay_s:
            time.sleep(delay_s)
        return real(fd)

    monkeypatch.setattr(wal_mod.os, "fsync", counting)
    return calls


def test_group_commit_batches_fsyncs(tmp_path, monkeypatch):
    """Concurrent writers share durability barriers: T threads x B commits
    with a slowed fsync must issue FEWER fsyncs than commits (leader syncs
    the whole staged tail; followers just wait for the high-water mark),
    while every insert still acks unique, gap-free ids."""
    import threading

    db, wl, hqi = _build(n=600)
    svc = _svc_pair(tmp_path, wl, hqi)
    calls = _count_fsync(monkeypatch)
    base = len(calls)
    T, B = 8, 6
    acked = [[] for _ in range(T)]

    def writer(t):
        rng = np.random.default_rng(100 + t)
        for _ in range(B):
            ids = svc.insert(rng.normal(size=(1, db.d)).astype(np.float32))
            acked[t].append(int(ids[0]))

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(T)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    n_commits = T * B
    n_fsyncs = len(calls) - base
    assert n_fsyncs < n_commits, (n_fsyncs, n_commits)  # batching happened
    assert n_fsyncs >= 1  # but durability was never skipped
    flat = sorted(x for lane in acked for x in lane)
    assert len(set(flat)) == n_commits  # unique ids, no double-assignment
    assert flat == list(range(flat[0], flat[0] + n_commits))  # gap-free
    # each thread's acks arrive in its own submission order
    assert all(lane == sorted(lane) for lane in acked)

    # crash + reopen: every acknowledged row replays bit-identically
    svc.wal.close()
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    assert svc2.n_live == svc.n_live
    np.testing.assert_array_equal(np.sort(svc2.live_ids()), np.sort(svc.live_ids()))


def test_group_commit_mixed_inserts_deletes(tmp_path, monkeypatch):
    """Interleaved concurrent inserts and deletes keep the WAL replay order
    consistent with the in-memory state: recovery lands on the same live set
    and the same answers as the uncrashed process."""
    import threading

    db, wl, hqi = _build(n=600, metric="l2")
    svc = _svc_pair(tmp_path, wl, hqi)
    _count_fsync(monkeypatch, delay_s=0.001)
    seed_ids = svc.insert(db.vectors[:12] + 0.01)

    def inserter(t):
        rng = np.random.default_rng(t)
        for _ in range(5):
            svc.insert(rng.normal(size=(2, db.d)).astype(np.float32))

    def deleter(t):
        for j in range(3):
            svc.delete([int(seed_ids[(t * 3 + j) % len(seed_ids)])])

    threads = [threading.Thread(target=inserter, args=(t,)) for t in range(4)]
    threads += [threading.Thread(target=deleter, args=(t,)) for t in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    a_ids, a_scores = _answers(svc, wl)
    svc.wal.close()
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    assert svc2.n_live == svc.n_live
    np.testing.assert_array_equal(np.sort(svc2.live_ids()), np.sort(svc.live_ids()))
    b_ids, b_scores = _answers(svc2, wl)
    np.testing.assert_array_equal(a_ids, b_ids)
    np.testing.assert_array_equal(a_scores, b_scores)


def test_group_commit_fsync_failure_is_not_acknowledged(tmp_path, monkeypatch):
    """A failing durability barrier must propagate to every commit waiting on
    it (no silent ack), and the log must keep working once fsync recovers —
    later commits land above the failed ones with correct ids."""
    import repro.store.wal as wal_mod

    db, wl, hqi = _build(n=600)
    svc = _svc_pair(tmp_path, wl, hqi)
    ok_ids = svc.insert(db.vectors[:2] + 0.01)

    real = os.fsync
    fail = {"on": True}

    def flaky(fd):
        if fail["on"]:
            raise OSError("injected fsync failure")
        return real(fd)

    monkeypatch.setattr(wal_mod.os, "fsync", flaky)
    with pytest.raises(OSError, match="injected"):
        svc.insert(db.vectors[2:4] + 0.01)

    # failing past the retry budget poisons the log: writes fail fast until
    # the operator heals it (repro.fault quarantine — reads keep serving)
    assert svc.wal.poisoned is not None
    from repro.service import ServiceReadOnly

    with pytest.raises(ServiceReadOnly):
        svc.insert(db.vectors[4:6] + 0.01)

    fail["on"] = False
    svc.wal.clear_poison()
    later = svc.insert(db.vectors[4:6] + 0.01)
    # the failed batch still consumed its id range (its frame is in the log;
    # replay applies it), so the next ack continues above it
    assert int(later[0]) == int(ok_ids[-1]) + 3
    svc.wal.close()
    svc2 = open_service(str(tmp_path), cfg=svc.cfg)
    assert svc2.n_live == svc.n_live


def test_wal_stage_sync_api_direct(tmp_path, monkeypatch):
    """stage() orders frames (seq = file order) without waiting; sync_upto()
    is idempotent and monotone; replay sees every staged record exactly once,
    in order, across concurrent writers."""
    import threading

    wal = WriteAheadLog(str(tmp_path / "wal"))
    calls = _count_fsync(monkeypatch, delay_s=0.001)
    T, B = 6, 10

    def writer(t):
        for j in range(B):
            seq = wal.stage_delete(np.array([t * B + j], dtype=np.int64))
            wal.sync_upto(seq)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(T)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    assert len(calls) < T * B  # group commit collapsed barriers
    recs = list(wal.replay(0))
    assert [r.seq for r in recs] == list(range(1, T * B + 1))
    seen = sorted(int(r.arrays["ids"][0]) for r in recs)
    assert seen == list(range(T * B))
    # syncing an already-durable seq is a no-op (no new fsync)
    n = len(calls)
    wal.sync_upto(1)
    assert len(calls) == n
    wal.close()
