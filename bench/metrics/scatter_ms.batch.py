"""Executor (core/planner.py): host milliseconds per pass that put scan
results where the merge reads them: the program's ``scan.remap`` spans
(local index -> id) and ``merge.scatter`` spans (the candidate buffer's
scatter)."""

NAMES = ("scan.remap", "merge.scatter")


def read(r):
    spans = [s for name in NAMES for s in r.span_seconds(name)]
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
