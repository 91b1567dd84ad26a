"""Executor: bytes the program copies from the host to the device per pass,
summed from the ``bytes`` argument of its ``<stage>.h2d`` spans."""


def read(r):
    sizes = [ev["args"]["bytes"] for ev in r.spans
             if ev.get("ph") == "X" and ev["name"].endswith(".h2d") and "bytes" in ev.get("args", {})]
    if not sizes or not r.passes:
        return None
    return float(sum(sizes)) / r.passes
