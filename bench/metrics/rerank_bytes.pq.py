"""Executor: bytes per pass the exact re-rank copies from the host to the
device, summed from the ``bytes`` argument of the program's ``rerank.h2d``
spans: the candidates' f32 rows, which live in host memory in this layout,
with the queries and the candidates' mask."""


def read(r):
    sizes = [ev["args"]["bytes"] for ev in r.spans
             if ev.get("ph") == "X" and ev["name"] == "rerank.h2d" and "bytes" in ev.get("args", {})]
    if not sizes or not r.passes:
        return None
    return float(sum(sizes)) / r.passes
