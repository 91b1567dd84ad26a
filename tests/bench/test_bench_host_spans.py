"""The per-layer readers of the program's host spans, and the spans on the
profiler's clock.

Each reader is checked on synthetic readings whose answer is worked out by
hand, and on the readings of a program without those spans, where it has
nothing to read. A traced tiny search under ``jax.profiler`` shows every
tracer span as an annotation on the host plane, where the harness's one
offset puts it; a traced tiny run of each cell reports every reader.
"""
import pytest

from tinycheckout import BENCH, run_tiny, tiny_root  # noqa: F401  (fixture)

from harness.session import Readings
from harness.spec import load_module
from harness.trace import Recorder, WINDOW


def span(name, dur_us, **args):
    ev = {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us), "pid": 1, "tid": 1}
    if args:
        ev["args"] = args
    return ev


# two passes: every stage twice, once a pass, with the span durations in us
SPANS = [
    ev
    for _ in range(2)
    for ev in (
        span("plan.build", 9_000.0),
        span("plan.probe", 3_000.0),
        span("probe.h2d", 250.0, bytes=1_000, parent="plan.probe"),
        span("probe.d2h", 50.0, bytes=64, parent="plan.probe"),
        span("plan.group", 5_000.0),
        span("plan.execute", 40_000.0),
        span("scan.assemble", 2_000.0),
        span("scan.gather", 7_000.0),
        span("scan.h2d", 6_000.0, bytes=50_000),
        span("dispatch.scan", 1_500.0),
        span("scan.d2h", 400.0, bytes=800),
        span("scan.remap", 1_200.0),
        span("merge.scatter", 300.0),
        span("merge.h2d", 750.0, bytes=9_000),
        span("merge.d2h", 100.0, bytes=160),
    )
] + [{"name": "profile.mark", "ph": "i", "ts": 0.0, "pid": 1, "tid": 1}]

EXPECTED = {
    "probe_ms.batch": 3.0,
    "assemble_ms.batch": 2.0,
    "gather_ms.batch": 7.0,
    "h2d_ms.batch": 0.25 + 6.0 + 0.75,
    "h2d_bytes.batch": 1_000 + 50_000 + 9_000,
    "d2h_ms.batch": 0.05 + 0.4 + 0.1,
    "host_syncs.batch": 3.0,
    "scatter_ms.batch": 1.2 + 0.3,
}


def readings(spans, passes=2):
    return Readings(cell="kg-batch-t0", d=200, peaks=None, passes=passes, spans=spans)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_synthetic_readings(name):
    read = load_module(BENCH / "metrics" / f"{name}.py").read
    assert read(readings(SPANS)) == pytest.approx(EXPECTED[name], rel=1e-12)
    # a program without the spans (or a window without a pass): nothing to read
    without = [ev for ev in SPANS if ev["name"] in ("plan.build", "plan.execute", "dispatch.scan")]
    assert read(readings(without)) is None
    assert read(readings(SPANS, passes=0)) is None


def test_spans_sit_on_their_annotations(tmp_path):
    """The recording tracer annotates the profiler's host plane with each
    span, and the harness's one offset (taken as the window opens) maps each
    tracer span onto its annotation within a millisecond."""
    from jax.profiler import ProfileData

    from repro.core import HQIConfig, HQIIndex
    from repro.obs import trace

    from conftest import small_db, small_workload

    db = small_db(n=3000, seed=3)
    wl = small_workload(db, n_queries=200)
    hqi = HQIIndex.build(db, wl, HQIConfig(min_partition_size=256, max_leaves=8))
    hqi.search(wl, nprobe=8, batch_vec=True)
    rec = Recorder(str(tmp_path / "profile"))
    tracer = trace.enable()
    try:
        with rec.window():
            hqi.search(wl, nprobe=8, batch_vec=True)
    finally:
        trace.disable()
    spans = [ev for ev in tracer.events() if ev["ph"] == "X"]
    assert {"plan.probe", "scan.gather", "scan.h2d", "merge.d2h"} <= {ev["name"] for ev in spans}
    offset = rec.reduce().offset_ns
    host = {}
    for plane in ProfileData.from_file(rec.xplane()).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    assert WINDOW in host
    for ev in spans:
        start = tracer._t0_ns + ev["ts"] * 1e3 + offset
        end = start + ev["dur"] * 1e3
        near = [abs(s - start) + abs(e - end) for s, e in host.get(ev["name"], [])]
        assert near and min(near) < 1e6, (ev["name"], min(near, default=None))


@pytest.mark.parametrize("cell", ["kg-batch-t0", "turing-batch-range"])
def test_traced_run_reports_the_host_readers(tiny_root, cell):  # noqa: F811
    rc, line = run_tiny(tiny_root, cell, seed=2**33 + 3, trace=1)
    assert rc == 0 and line["correct"]
    assert set(EXPECTED) <= set(line["metrics"])
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["host_syncs.batch"] >= 3 and m["h2d_bytes.batch"] > 0
    # the host stages fit inside the spans that hold them
    assert m["assemble_ms.batch"] + m["gather_ms.batch"] + m["scatter_ms.batch"] < m["execute_ms.batch"]
    assert m["probe_ms.batch"] < m["plan_ms.batch"]
