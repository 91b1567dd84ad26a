"""Executor: host milliseconds per pass in the program's blocking
device->host readback spans (every ``<stage>.d2h``), each opened after its
inputs are ready so it times the readback alone."""


def read(r):
    spans = [ev["dur"] / 1e6 for ev in r.spans
             if ev.get("ph") == "X" and ev["name"].endswith(".d2h")]
    if not spans or not r.passes:
        return None
    return 1e3 * sum(spans) / r.passes
