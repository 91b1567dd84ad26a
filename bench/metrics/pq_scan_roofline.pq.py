"""Kernels (kernels/pq_scan.py): the least time the chip could take for the
window's useful ADC work (bytes at the HBM peak or FLOPs at the bf16 peak,
whichever is larger) over the device time of the ADC kernels' ops in the
trace, both grids.

The useful work is counted from the harness's ``scan_work`` of one pass,
whatever implements the scan: M table adds per useful (query, row) pair;
M code bytes per useful row, read once, plus one [M, 256] f32 table per
query. M is the ``index.pq_m`` of the cell's configuration; a cell without
one has nothing to read."""
from pathlib import Path

from harness import spec
from harness.peaks import least_time

# the ADC kernels' custom calls as the device trace names them:
# "%pq_adc_scan.1", "%pq_adc_scan_streamed.1"
ADC_OP = "%pq_adc_scan"


def is_adc(op) -> bool:
    return op.short.startswith(ADC_OP)


def pq_m(cell: str):
    """The cell configuration's PQ width, read from this checkout."""
    root = Path(__file__).resolve().parents[2]
    try:
        config = spec.resolve(cell, root).config
    except (KeyError, FileNotFoundError):
        return None
    return config.get("index", {}).get("pq_m")


def adc_work(work: dict, d: int, m: int):
    """(FLOPs, bytes) of one pass's useful ADC work. ``scan_work`` counts
    bytes = 4 d (rows + queries), which gives the number of queries."""
    queries = work["bytes"] / (4.0 * d) - work["rows"]
    return float(m) * work["pairs"], float(m) * work["rows"] + queries * m * 256 * 4


def read(r):
    if r.device is None or not r.work or not r.passes:
        return None
    busy = r.device.op_seconds(is_adc)
    m = pq_m(r.cell)
    if busy <= 0 or not m:
        return None
    flops, nbytes = adc_work(r.work, r.d, m)
    t, _bound = least_time(flops * r.passes, nbytes * r.passes, r.peaks)
    return 100.0 * t / busy
