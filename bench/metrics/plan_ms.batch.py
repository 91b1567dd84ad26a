"""Plan (core/plan.py): host milliseconds per pass in the program's
``plan.build`` span."""


def read(r):
    spans = r.span_seconds("plan.build")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
