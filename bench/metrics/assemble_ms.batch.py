"""Executor (core/planner.py): host milliseconds per pass in the program's
``scan.assemble`` spans: the per-unit loop that builds each bucket's row
indices, validity mask and query and slot maps."""


def read(r):
    spans = r.span_seconds("scan.assemble")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
