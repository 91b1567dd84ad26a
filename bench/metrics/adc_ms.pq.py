"""Plan (core/planner.py, core/pq.py): host milliseconds per pass in the
program's ``scan.adc`` spans: the jitted program that builds every scanned
query's ADC tables on the device from the resident queries and codebook
(fenced while tracing, so it includes the device work)."""


def read(r):
    spans = r.span_seconds("scan.adc")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
