"""Routing (core/hqi.py Router): host milliseconds per pass in the
program's ``engine.route`` span."""


def read(r):
    spans = r.span_seconds("engine.route")
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
