"""Plan (core/plan.py): host milliseconds per pass in the program's
``plan.probe`` spans: each task's quantizer top-m call, its host->device
copy, device work and blocking readback."""


def read(r):
    spans = r.span_seconds("plan.probe")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
