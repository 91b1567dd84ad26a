"""Kernels (kernels/fused_knn.py): the least time the chip could take for
the window's useful scan work (``harness.work``: bytes at the HBM peak or
FLOPs at the bf16 peak, whichever is larger) over the device time of the
f32 scan kernels in the trace."""

from harness.peaks import least_time

# the Pallas f32 scan kernels (both grids) as the device trace names their
# custom calls: "%fused_knn.1", "%fused_knn_db_stationary.1"
SCAN_OP = "%fused_knn"


def is_scan(op) -> bool:
    return op.short.startswith(SCAN_OP)


def read(r):
    if r.device is None or not r.work or not r.passes:
        return None
    busy = r.device.op_seconds(is_scan)
    if busy <= 0:
        return None
    t, _bound = least_time(r.work["flops"] * r.passes, r.work["bytes"] * r.passes, r.peaks)
    return 100.0 * t / busy
