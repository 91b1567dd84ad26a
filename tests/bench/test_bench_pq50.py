"""The ``kg-batch-pq50`` cell on the CPU: its per-layer readers on synthetic
readings and on a program without what they read, the useful-work count of
its configuration against a brute-force count, and whole tiny runs of the
cell, sound (traced) and with the timed path broken underneath."""
import json

import numpy as np
import pytest

from tinycheckout import BENCH, make_tiny_root, run_tiny

from harness import program
from harness.dataset import plain_mask
from harness.session import Readings
from harness.spec import load_module
from harness.trace import DeviceTrace, Op
from harness.work import scan_work
from test_bench_work import brute_force_work

CELL, CONFIG = "kg-batch-pq50", "kg-pbg-wikidata-pq50-1m"
# the tiny size's own limit for recall_miss, set from CPU readings at this
# size (PERF.md), as tinycheckout sets the f32 cells'
TINY_RECALL_MISS = 0.42


@pytest.fixture(scope="module")
def pq_root(tmp_path_factory):
    root = make_tiny_root(tmp_path_factory.mktemp("checkout"))
    path = root / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(path.read_text())
    cfg.update(n=6000, queries_per_split=200)
    path.write_text(json.dumps(cfg))
    path = root / "bench" / "limits" / f"{CELL}.json"
    lim = json.loads(path.read_text())
    lim["limits"]["recall_miss"] = TINY_RECALL_MISS
    path.write_text(json.dumps(lim))
    return root


def span(name, dur_us, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us), "pid": 1, "tid": 1, "args": args}


# two passes of the pq path's spans, durations in us
SPANS = [
    ev
    for _ in range(2)
    for ev in (
        span("plan.execute", 90_000.0),
        span("query.h2d", 300.0, bytes=1_638_400),
        span("scan.adc", 2_500.0, queries=2000, m=50),
        span("scan.h2d", 900.0, bytes=40_000),
        span("scan.gather", 1_100.0),
        span("rerank.gather", 8_000.0),
        span("rerank.h2d", 6_000.0, bytes=67_190_784),
        span("rerank.exact", 1_000.0),
        span("rerank.d2h", 200.0, bytes=160_000),
        span("rerank.remap", 400.0),
    )
]

EXPECTED = {
    "adc_ms.pq": 2.5,
    "rerank_ms.pq": 8.0 + 6.0 + 1.0 + 0.2 + 0.4,
    "rerank_bytes.pq": 67_190_784.0,
}


def readings(spans, passes=2, device=None, work=None, cell=CELL):
    return Readings(cell=cell, d=200, peaks=None, passes=passes, spans=spans, device=device, work=work)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_pq_reader_on_synthetic_readings(name):
    read = load_module(BENCH / "metrics" / f"{name}.py").read
    assert read(readings(SPANS)) == pytest.approx(EXPECTED[name], rel=1e-12)
    # the f32 path's spans (or a parent without the pq spans): nothing to read
    without = [ev for ev in SPANS if ev["name"] in ("plan.execute", "scan.h2d", "scan.gather")]
    assert read(readings(without)) is None
    assert read(readings(SPANS, passes=0)) is None


def adc_trace(names_and_seconds):
    ops = [Op(f"{name} = (f32[4,64,40], s32[4,64,40]) custom-call()", "jit_x(1)", 1e9 * i, 1e9 * s, 0)
           for i, (name, s) in enumerate(names_and_seconds)]
    return DeviceTrace(window=(0.0, 1e12), ops=ops, offset_ns=0.0)


def test_adc_roofline_on_a_synthetic_trace():
    from harness.peaks import TPU_PEAKS

    roof = load_module(BENCH / "metrics" / "pq_scan_roofline.pq.py")
    peaks = TPU_PEAKS["TPU v5 lite"]
    # one pass: 1e6 useful pairs over 2e5 useful rows for 100 queries (d = 200)
    work = {"pairs": 1_000_000, "rows": 200_000, "flops": 4e8, "bytes": 4.0 * 200 * (200_000 + 100)}
    trace = adc_trace([("%pq_adc_scan_streamed.1", 0.002), ("%pq_adc_scan.3", 0.001),
                       ("%fused_knn.1", 0.5), ("%fusion.2", 0.5)])
    r = readings([], passes=2, device=trace, work=work)
    r.peaks = peaks
    # M = 50 adds a pair; 50 code bytes a row plus one [50, 256] f32 table a query
    flops, nbytes = 50 * 1_000_000, 50 * 200_000 + 100 * 50 * 256 * 4
    assert roof.adc_work(work, 200, 50) == (flops, nbytes)
    least = max(2 * flops / peaks.peak_flops, 2 * nbytes / peaks.hbm_bw)
    assert roof.read(r) == pytest.approx(100 * least / 0.003, rel=1e-9)
    # the re-rank's %fused_knn is not an ADC op; a trace without ADC ops
    # (a program without the kernels' names), or a cell without pq_m, reads
    # nothing
    r.device = adc_trace([("%fused_knn.1", 0.5)])
    assert roof.read(r) is None
    r.device, r.cell = trace, "kg-batch-t0"
    assert roof.read(r) is None


def test_pq_work_count_matches_brute_force():
    cfg = json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())
    cfg.update(n=4000, queries_per_split=100)
    ds = load_module(BENCH / "gen" / f"{cfg['generator']}.py").generate(cfg, 3, logs=("t0",))
    ds.freeze()
    index = program.build_index(ds, cfg, "t0")
    assert index.cfg.plan.scan_mode == "pq" and index.pq.m == 50
    masks = np.stack([plain_mask(t, ds.columns) for t in ds.templates])
    log = ds.logs["t0"]
    got = scan_work(index, masks, log.vectors, log.template_of, cfg["nprobe"], ds.metric)
    pairs, rows = brute_force_work(index, masks, log, cfg["nprobe"], ds.metric)
    assert got["pairs"] == pairs and got["rows"] == rows
    # the program's own count of the pairs its ADC stage scores agrees
    from repro.core.ivf import ScanStats
    from repro.core.plan import build_plan

    wl = program.program_workload(ds, log)
    stats = ScanStats()
    tasks, _, _ = index._engine_tasks(wl, nprobe=cfg["nprobe"], batch_vec=True, stats=stats)
    build_plan(index.arena, tasks, wl.vectors, m=wl.m, k=wl.k, stats=stats)
    assert stats.dists_computed == pairs


def test_traced_run_reports_the_cell_metrics(pq_root):
    rc, line = run_tiny(pq_root, CELL, seed=2**34 + 5, trace=1)
    assert rc == 0 and line["correct"], line and line["compared"]
    bench = json.loads((pq_root / "BENCHMARK.json").read_text())
    cell_metrics = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    # the ADC kernels' device time exists only in a chip's trace
    assert set(line["metrics"]) == cell_metrics - {"pq_scan_roofline.pq"}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the queries, the re-rank's rows and the buckets' starts, LUT rows and
    # bitmaps: the re-rank's f32 rows are most of what crosses
    assert 0 < m["rerank_bytes.pq"] < m["h2d_bytes.batch"] < 2 * m["rerank_bytes.pq"]
    assert m["adc_ms.pq"] + m["rerank_ms.pq"] + m["gather_ms.batch"] < m["execute_ms.batch"]


def failing(line) -> set:
    return {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("fault", ["merge_shifted", "scan_skips_rows"])
def test_pq_fault_is_not_correct(pq_root, monkeypatch, fault):
    from harness.faults import FAULTS

    FAULTS[fault](monkeypatch.setattr)
    rc, line = run_tiny(pq_root, CELL, seed=7)
    assert rc == 0
    assert "recall_miss" in failing(line), line["compared"]
