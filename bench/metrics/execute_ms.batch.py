"""Executor (core/planner.py): milliseconds per pass in the program's
``plan.execute`` span (host gathers and copies, dispatches and merges; the
span is fenced while tracing, so it includes the device work)."""


def read(r):
    spans = r.span_seconds("plan.execute")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
