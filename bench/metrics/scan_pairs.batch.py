"""Plan: the useful (query, row) pairs of one pass (``harness.work``): rows
in the posting lists each query probes that pass its template. It is the
work the roofline of ``scan_roofline.batch`` is counted from, read from the
index's own partitions and lists, so a change to how the lists are built or
probed shows here first; the two rooflines of PRs that differ here do not
measure the same work."""


def read(r):
    if not r.work:
        return None
    return float(r.work["pairs"])
