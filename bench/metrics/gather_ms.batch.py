"""Executor (core/planner.py): host milliseconds per pass in the program's
``scan.gather`` spans: each bucket's query-tile fill and its gather of the
arena's rows (or codes) on the host."""


def read(r):
    spans = r.span_seconds("scan.gather")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
