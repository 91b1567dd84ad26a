"""The plan's and the executor's host stages as tracer spans.

A traced search names each piece of host work under ``plan.build`` and
``plan.execute``: the quantizer probes and the grouping of the plan, and per
bucket the assembly, the gather, the host->device copy and the step that
keeps its results on the device as merge candidates (``merge.scatter``,
with the rows it placed; one more lays the buffer out for the merge). The
copies carry the exact bytes they ship, each readback is one host sync, a
single-device search reads nothing back per bucket (the id remap and
readback spans are the sharded path's alone), and an untraced search
builds nothing for the host stages.
"""
import collections
import os
import textwrap
import tracemalloc

import jax
import numpy as np
import pytest

from repro.core import HQIConfig, HQIIndex
from repro.core.arena import PackedArena
from repro.core.ivf import IVFIndex
from repro.core.plan import EngineTask, PlanConfig, build_plan, _next_pow2
from repro.core.planner import execute_plan
from repro.obs import trace

from conftest import small_db, small_workload
from test_distributed import REPO, run_with_devices

# the spans of the f32 batch path and the span each one nests in
F32_PARENT = {
    "plan.probe": "plan.build",
    "plan.group": "plan.build",
    "probe.h2d": "plan.probe",
    "probe.d2h": "plan.probe",
    "query.h2d": "plan.execute",
    "scan.assemble": "plan.execute",
    "scan.gather": "plan.execute",
    "scan.h2d": "plan.execute",
    "dispatch.scan": "plan.execute",
    "merge.scatter": "plan.execute",
    "merge.h2d": "plan.execute",
    "merge.d2h": "plan.execute",
}
# the host stages that are spans of their own (copies, readbacks and device
# dispatches aside)
HOST_STAGES = {"plan.probe", "plan.group", "scan.assemble", "scan.gather"}
# spans of the sharded path alone: the host id remap and its readback
SHARDED_ONLY = ("scan.remap", "scan.d2h")
# ids shipped to the device: int64 on the host, canonical width on the device
ID_BYTES = jax.dtypes.canonicalize_dtype(np.int64).itemsize


@pytest.fixture(autouse=True)
def _no_tracer():
    trace.disable()
    yield
    trace.disable()


@pytest.fixture(scope="module")
def hqi_and_workload():
    db = small_db(n=3000, seed=3)
    wl = small_workload(db, n_queries=200)
    return HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8)), wl


def traced(fn):
    """Events of one call of ``fn`` with a recording tracer."""
    t = trace.enable(capacity=100_000)
    try:
        fn()
    finally:
        trace.disable()
    return [e for e in t.events() if e["ph"] == "X"]


def parent_of(events):
    """{span name: the names of the spans it was opened in}."""
    out = collections.defaultdict(set)
    for e in events:
        out[e["name"]].add(e.get("args", {}).get("parent"))
    return out


@pytest.mark.parametrize("layout", ["segmented", "dense"])
def test_traced_f32_search_emits_every_span(hqi_and_workload, layout):
    hqi, wl = hqi_and_workload
    hqi.cfg.plan = PlanConfig(merge_layout=layout)
    try:
        hqi.search(wl, nprobe=8, batch_vec=True)  # compile outside the trace
        events = traced(lambda: hqi.search(wl, nprobe=8, batch_vec=True))
    finally:
        hqi.cfg.plan = PlanConfig()
    parents = parent_of(events)
    for name, parent in F32_PARENT.items():
        assert parents[name] == {parent}, (name, parents[name])
    merge = "merge.segmented" if layout == "segmented" else "merge.final"
    assert parents[merge] == {"plan.execute"}
    assert parents["plan.build"] == parents["plan.execute"] == {None}
    # at most one span per bucket and stage: never one per work unit
    n = collections.Counter(e["name"] for e in events)
    assert n["scan.assemble"] == n["scan.gather"] == n["scan.h2d"] == n["dispatch.scan"]
    # each bucket's results stay on the device: one step a bucket and one
    # gather of the buffer, no readback, no host remap, one small copy
    assert n["merge.scatter"] == n["dispatch.scan"] + 1
    assert all(e["args"]["rows"] > 0 for e in events if e["name"] == "merge.scatter")
    assert not any(n[name] for name in SHARDED_ONLY) and n["merge.h2d"] == 1
    for e in events:
        if e["name"].endswith((".h2d", ".d2h")):
            assert e["args"]["bytes"] > 0


def _tiny(layout="segmented", n=600, d=8, m=37, k=4, seed=0):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    ivf = IVFIndex.build(vecs, metric="l2", n_centroids=12, kmeans_iters=5, seed=0)
    arena = PackedArena.from_ivf(ivf)
    q = rng.normal(size=(m, d)).astype(np.float32)
    tasks = [
        EngineTask(part=0, qrows=np.arange(0, 20, dtype=np.int64), nprobe=5, packed_bitmap=None),
        EngineTask(part=0, qrows=np.arange(15, m, dtype=np.int64), nprobe=3, packed_bitmap=None),
    ]
    cfg = PlanConfig(tq_unit=8, min_list_pad=8, max_bucket_shapes=4, merge_layout=layout)
    return arena, tasks, q, cfg, m, k


def _run_tiny(layout):
    arena, tasks, q, cfg, m, k = _tiny(layout)
    out = {}

    def run():
        out["plan"] = build_plan(arena, tasks, q, m=m, k=k, cfg=cfg)
        execute_plan(out["plan"], arena, q, cfg=cfg)

    run()  # compile outside the trace
    return traced(run), out["plan"], arena, tasks, q, m, k


@pytest.mark.parametrize("layout", ["segmented", "dense"])
def test_h2d_bytes_equal_the_plan_shapes(layout):
    events, plan, arena, tasks, q, m, k = _run_tiny(layout)
    d = q.shape[1]
    got = collections.Counter()
    for e in events:
        if e["name"].endswith(".h2d"):
            got[e["name"]] += e["args"]["bytes"]
    # probe: the pow2-padded queries and centroids (>= 8 rows each)
    n_cent = arena.centroids[0].shape[0]
    want_probe = sum(
        _next_pow2(len(t.qrows), 8) * d * 4 + _next_pow2(n_cent, 8) * d * 4 for t in tasks
    )
    # queries once, pow2-padded (>= 8 rows); the arena's rows are resident
    # already (uploaded by the untraced first run)
    want_query = _next_pow2(m, 8) * d * 4
    # scan: per bucket the list starts [W] i32, query rows [W, tq] i32 and
    # valid [W, lp] bool; the Q and V tiles are gathered on the device
    want_scan = 0
    for lp, units in plan.buckets.items():
        W = _next_pow2(len(units), 1)
        want_scan += W * 4 + W * plan.tq * 4 + W * lp
    # the merge buffer is laid out on the device: the host ships only the
    # block row of each buffer row [C] i32 and, segmented, the segment ends
    # [m] i32, never the candidates
    if layout == "segmented":
        want_merge = _next_pow2(int(plan.seg_counts.sum()), 1) * 4 + m * 4
    else:
        want_merge = m * plan.n_slots * 4
    assert got == {"probe.h2d": want_probe, "query.h2d": want_query, "scan.h2d": want_scan,
                   "merge.h2d": want_merge}


@pytest.mark.parametrize("layout", ["segmented", "dense"])
def test_d2h_count_is_probes_buckets_and_merges(layout):
    events, plan, arena, tasks, q, m, k = _run_tiny(layout)
    n = collections.Counter(e["name"] for e in events if e["name"].endswith(".d2h"))
    # one readback per probe and the merged top-k: none per bucket
    assert n == {"probe.d2h": len(tasks), "merge.d2h": 1}
    # the final top-k read back: scores f32 and ids [m, k]
    (merge,) = [e for e in events if e["name"] == "merge.d2h"]
    assert merge["args"]["bytes"] == m * k * (4 + ID_BYTES)


@pytest.mark.parametrize("layout", ["segmented", "dense"])
def test_scan_copies_ship_no_rows(hqi_and_workload, layout):
    hqi, wl = hqi_and_workload
    hqi.cfg.plan = PlanConfig(merge_layout=layout)
    try:
        hqi.search(wl, nprobe=8, batch_vec=True)  # uploads the rows, compiles
        events = traced(lambda: hqi.search(wl, nprobe=8, batch_vec=True))
    finally:
        hqi.cfg.plan = PlanConfig()
    d = wl.vectors.shape[1]
    buckets = [e["args"] for e in events if e["name"] == "dispatch.scan"]
    assert buckets
    # what the per-bucket copies would be if they carried the V tiles
    rows_bytes = sum(_next_pow2(b["units"], 1) * b["lp"] * d * 4 for b in buckets)
    scan_bytes = sum(e["args"]["bytes"] for e in events if e["name"] == "scan.h2d")
    assert 0 < scan_bytes < rows_bytes
    # the device gather keeps its span, one per bucket, and the resident
    # rows are not copied again
    n = collections.Counter(e["name"] for e in events)
    assert n["scan.gather"] == len(buckets)
    assert n["query.h2d"] == 1 and n["arena.h2d"] == 0


def test_arena_rows_upload_once_per_arena():
    from repro.core.types import VectorDatabase

    db = small_db(n=2000, seed=5)
    wl = small_workload(db, n_queries=80)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8))

    def uploads():
        events = traced(lambda: hqi.search(wl, nprobe=8, batch_vec=True))
        return [e["args"]["bytes"] for e in events if e["name"] == "arena.h2d"]

    t = trace.enable(capacity=100_000)
    try:
        hqi.search(wl, nprobe=8, batch_vec=True)
        hqi.search(wl, nprobe=8, batch_vec=True)
    finally:
        trace.disable()
    # the rows, then the ids (``device_gid``): once each
    up = [e for e in t.events() if e["name"] == "arena.h2d"]
    assert [e["args"]["bytes"] for e in up] == [hqi.arena.packed.nbytes, hqi.arena.n * 4]
    assert {e["args"]["parent"] for e in up} == {"plan.execute"}
    # an insert folds into a new arena (PackedArena.updated): it uploads anew
    old = hqi.arena
    new = db.take(np.arange(9))
    hqi.extend(VectorDatabase(
        vectors=new.vectors + 0.01, columns=new.columns, metric=db.metric,
        ids=db.n + np.arange(9, dtype=np.int64),
    ))
    assert hqi.arena is not old and hqi.arena.n == old.n + 9
    assert uploads() == [hqi.arena.packed.nbytes, hqi.arena.n * 4]
    assert uploads() == []


def test_arena_gid_uploads_once_per_arena():
    """``device_gid`` puts the arena's ids on the device once, as int32
    through one ``arena.h2d`` of N·4 bytes; a second traced search uploads
    no ids, and the pq path, whose stage A keeps packed rows, none at all."""
    db = small_db(n=2000, seed=6)
    wl = small_workload(db, n_queries=80)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8))
    arena = hqi.arena
    assert arena._gid_dev is None
    t = trace.enable(capacity=100_000)
    try:
        gid = arena.device_gid()
        assert arena.device_gid() is gid
    finally:
        trace.disable()
    (up,) = [e for e in t.events() if e["name"] == "arena.h2d"]
    assert up["args"]["bytes"] == arena.n * 4
    assert gid.dtype == np.int32 and np.array_equal(np.asarray(gid), arena.gid)
    hqi.search(wl, nprobe=8, batch_vec=True)  # uploads the rows
    events = traced(lambda: hqi.search(wl, nprobe=8, batch_vec=True))
    assert not [e for e in events if e["name"] == "arena.h2d"]
    assert arena.device_gid() is gid

    pq = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8, scan_mode="pq"))
    pq.search(wl, nprobe=8, batch_vec=True)
    assert pq.arena._codes_dev is not None and pq.arena._gid_dev is None


def test_pq_search_emits_the_shared_spans(hqi_and_workload):
    _, wl = hqi_and_workload
    db = small_db(n=3000, seed=3)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8, scan_mode="pq"))
    hqi.search(wl, nprobe=8, batch_vec=True)
    events = traced(lambda: hqi.search(wl, nprobe=8, batch_vec=True))
    parents = parent_of(events)
    # the final fold after the re-rank still copies its small host buffer
    # (``merge.h2d``); stage A scatters on the device and reads back once
    for name in ("scan.assemble", "scan.gather", "scan.h2d", "merge.scatter", "merge.h2d",
                 "merge.d2h", "rerank.gather", "rerank.h2d", "rerank.d2h", "rerank.remap"):
        assert parents[name] == {"plan.execute"}, (name, parents[name])
    assert parents["probe.d2h"] == {"plan.probe"}
    assert not any(name in parents for name in SHARDED_ONLY)
    n = collections.Counter(e["name"] for e in events)
    scatters = [e["args"]["rows"] for e in events
                if e["name"] == "merge.scatter" and "rows" in e.get("args", {})]
    assert len(scatters) == n["dispatch.scan"] + 1 and all(r > 0 for r in scatters)
    # stage A's top-k' rows, then the final top-k
    assert n["merge.d2h"] == 2


def test_sharded_search_emits_the_shared_spans():
    tests = os.path.join(REPO, "tests")
    out = run_with_devices(textwrap.dedent(f"""
        import collections, sys
        sys.path.insert(0, {tests!r})
        import numpy as np, jax
        from jax.sharding import Mesh
        from conftest import small_db, small_workload
        from repro.core import HQIConfig, HQIIndex
        from repro.obs import trace

        db = small_db(n=3000, seed=3)
        wl = small_workload(db, n_queries=200)
        hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8))
        hqi.cfg.mesh = Mesh(np.asarray(jax.devices()[:4]), ("model",))
        hqi.search(wl, nprobe=8, batch_vec=True)
        t = trace.enable()
        hqi.search(wl, nprobe=8, batch_vec=True)
        trace.disable()
        parents = collections.defaultdict(set)
        n = collections.Counter()
        for e in t.events():
            parents[e["name"]].add(e.get("args", {{}}).get("parent"))
            n[e["name"]] += 1
        for name in ("scan.assemble", "scan.gather", "scan.h2d", "scan.d2h", "scan.remap",
                     "merge.scatter", "merge.h2d", "merge.d2h"):
            assert parents[name] == {{"plan.execute"}}, (name, parents[name])
        # assembly runs once per rank that holds units of a bucket
        assert n["scan.assemble"] >= n["dispatch.sharded"] > 0
        assert n["scan.h2d"] == n["scan.d2h"] == n["dispatch.sharded"]
        print("OK")
    """), n=4)
    assert "OK" in out


def test_untraced_search_builds_nothing_for_the_spans(hqi_and_workload, monkeypatch):
    hqi, wl = hqi_and_workload
    hqi.search(wl, nprobe=8, batch_vec=True)
    opened = []
    null_span = trace.NullTracer.span

    def spy(self, name, **args):
        opened.append((name, args))
        return null_span(self, name, **args)

    monkeypatch.setattr(trace.NullTracer, "span", spy)
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for _ in range(3):
        hqi.search(wl, nprobe=8, batch_vec=True)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    names = {n for n, _ in opened}
    # the host stages open the shared no-op span, with no arguments built;
    # copies and readbacks open none at all (the device scatter's span
    # carries its row count, as the scan dispatch's carries its shape)
    assert HOST_STAGES | {"merge.scatter", "dispatch.scan"} <= names
    assert all(not args for n, args in opened if n in HOST_STAGES)
    assert not any(n.endswith((".h2d", ".d2h")) for n in names)
    keep = [tracemalloc.Filter(True, trace.__file__)]
    grown = after.filter_traces(keep).compare_to(base.filter_traces(keep), "lineno")
    assert sum(s.size_diff for s in grown) <= 0
    assert trace.get_tracer().events() == []
