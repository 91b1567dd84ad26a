"""Executor (core/planner.py): milliseconds per pass in the program's
``rerank.*`` spans: the exact re-rank of the ADC candidates, from the host
gather of their f32 rows (``rerank.gather``) and its copy (``rerank.h2d``)
through the dispatch (``rerank.exact``), readback and id remap."""


def read(r):
    spans = [ev["dur"] / 1e6 for ev in r.spans
             if ev.get("ph") == "X" and ev["name"].startswith("rerank.")]
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
