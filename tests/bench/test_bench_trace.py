"""The trace reduction on a small trace recorded on a TPU v5e: three f32
scan kernels (the db-stationary grid, 4 work units of 64 queries x 256
rows) and three final merges inside the harness's window annotation. The
numbers below were read from the same file on the chip when it was
recorded, and the kernel and merge events were counted by hand."""
import pytest

from tinycheckout import BENCH

from harness.spec import load_module
from harness.trace import reduce_xplane

TRACE = BENCH.parent / "tests" / "bench" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return reduce_xplane(str(TRACE), None)


def test_window_and_busy_time(trace):
    assert trace.devices() == [0]
    assert len(trace.ops) == 63
    assert trace.window_s == pytest.approx(0.01442574, rel=1e-9)
    assert trace.busy_s() == pytest.approx(0.000135615, rel=1e-9)
    # 1 - busy / window: the idle share the readers report
    idle = load_module(BENCH / "metrics" / "idle_share.batch.py")
    r = type("R", (), {"device": trace})()
    assert idle.read(r) == pytest.approx(100 * (1 - 0.000135615 / 0.01442574))


def test_kernel_and_merge_ops(trace):
    scan = load_module(BENCH / "metrics" / "scan_roofline.batch.py")
    merge = load_module(BENCH / "metrics" / "merge_device_ms.batch.py")
    assert sum(1 for op in trace.ops if scan.is_scan(op)) == 3
    assert trace.op_seconds(scan.is_scan) == pytest.approx(3.7417e-05, rel=1e-9)
    assert sum(1 for op in trace.ops if merge.is_merge(op)) == 33
    assert trace.op_seconds(merge.is_merge) == pytest.approx(8.371e-05, rel=1e-9)
    top = trace.top_ops(2)
    assert top[0][0] == "jit__merge_topk_jnp/%fusion"
    assert top[1] == ["jit_fused_knn_db_stationary/%fused_knn_db_stationary.1", pytest.approx(3.7417e-05)]


def test_idle_gaps_are_named_by_the_covering_span(trace):
    w0, w1 = trace.window
    # one program span covering the whole window (tracer epoch 0, offset 0)
    spans = [{"ph": "X", "name": "plan.execute", "ts": w0 / 1e3, "dur": (w1 - w0) / 1e3}]
    gaps = trace.idle_gaps(spans, 0)
    assert gaps[0][0] == "plan.execute"
    assert sum(s for _, s in gaps) == pytest.approx(0.01442574 - 0.000135615, rel=1e-6)
