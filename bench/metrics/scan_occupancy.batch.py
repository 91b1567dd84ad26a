"""Plan: useful (query, row) pairs of the window (counted by the benchmark,
``harness.work``) as a share of the padded pairs the dispatched f32 scan
buckets held (the program's kernel profiler: padded FLOPs / 2d, read as a
count)."""


def read(r):
    scan = (r.profile or {}).get("scan") or {}
    if not r.work or not scan.get("flops_padded") or not r.passes:
        return None
    padded_pairs = scan["flops_padded"] / (2.0 * r.d)
    return 100.0 * r.work["pairs"] * r.passes / padded_pairs
