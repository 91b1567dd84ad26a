"""Executor: blocking device->host readbacks per pass, one per
``<stage>.d2h`` span of the program (probes, scan buckets, merges)."""


def read(r):
    n = sum(1 for ev in r.spans if ev.get("ph") == "X" and ev["name"].endswith(".d2h"))
    if not n or not r.passes:
        return None
    return float(n) / r.passes
