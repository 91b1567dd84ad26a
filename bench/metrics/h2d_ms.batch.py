"""Executor: host milliseconds per pass in the program's host->device copy
spans (every ``<stage>.h2d``: scan operands, merge inputs, probe queries and
centroids), each fenced while tracing so it times the copy itself."""


def read(r):
    spans = [ev["dur"] / 1e6 for ev in r.spans
             if ev.get("ph") == "X" and ev["name"].endswith(".h2d")]
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
