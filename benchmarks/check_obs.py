"""CI guard for the observability layer (rides the bench-smoke job).

    PYTHONPATH=src python -m benchmarks.check_obs [BENCH_obs.json] [trace.json]

Fails the build when
  * the tracing-enabled/disabled QPS overhead ratio from the obs bench
    exceeds ``REPRO_OBS_MAX_OVERHEAD`` (default 1.05 — the "tracing costs
    < 5%" contract), or
  * the exported ``trace.json`` fails Chrome-trace schema validation, or
  * the trace is missing the span names the serving pipeline must emit
    (queue wait, dispatch, merge, flush, WAL fsync) — a silent
    instrumentation regression would otherwise pass the ratio gate by
    tracing nothing.

The overhead gate is a ratio of two medians measured interleaved on the
same machine in the same process, so it is far more stable than an absolute
QPS floor; still, noisy shared runners can exceed 1.05 on a fair build —
bump ``REPRO_OBS_MAX_OVERHEAD`` explicitly in the workflow rather than
deleting the gate.
"""
from __future__ import annotations

import json
import os
import sys

from repro.obs.trace import validate_chrome_trace

# every serving trace must show these stages end-to-end; dispatch/merge span
# names carry stage suffixes (dispatch.scan, merge.segmented, merge.final,
# merge.gather) so those two are prefix-matched. scan.* spans are the
# executor's host stages (assemble, gather, h2d, d2h, remap) — their absence
# means the executor lost its host-side instrumentation.
REQUIRED_SPANS = ["queue.wait", "flush", "wal.fsync"]
REQUIRED_PREFIXES = ["dispatch.", "merge.", "scan."]


def check(bench_path: str, trace_path: str, max_ratio: float) -> list:
    errors = []

    with open(bench_path) as f:
        bench = json.load(f)
    rows = {r["name"]: r for r in bench.get("rows", [])}
    row = rows.get("obs/overhead_ratio")
    if row is None:
        errors.append(f"{bench_path}: no obs/overhead_ratio row")
    else:
        # derived leads with the full-precision ratio ("0.987x ...");
        # us_per_call goes through emit's %.1f and is only a fallback
        try:
            ratio = float(row["derived"].split("x", 1)[0])
        except (ValueError, IndexError):
            ratio = float(row["us_per_call"])
        if ratio > max_ratio:
            errors.append(
                f"tracing overhead {ratio:.3f}x exceeds gate {max_ratio:.2f}x"
                f" ({row['derived']})"
            )
        else:
            print(f"overhead ratio {ratio:.3f}x <= {max_ratio:.2f}x  OK")

    try:
        with open(trace_path) as f:
            doc = json.load(f)
        n = validate_chrome_trace(doc)
        print(f"{trace_path}: {n} events, schema OK")
    except (OSError, ValueError, json.JSONDecodeError) as e:
        errors.append(f"{trace_path}: {e}")
        return errors  # no events to check names against

    names = {e["name"] for e in (doc["traceEvents"] if isinstance(doc, dict) else doc)}
    for want in REQUIRED_SPANS:
        if want not in names:
            errors.append(f"trace missing required span {want!r}")
    for pre in REQUIRED_PREFIXES:
        if not any(n.startswith(pre) for n in names):
            errors.append(f"trace has no span named {pre}*")
    if not errors:
        print(f"required spans present ({len(names)} distinct names)")
    return errors


def main() -> int:
    bench_path = sys.argv[1] if len(sys.argv) > 1 else "BENCH_obs.json"
    trace_path = sys.argv[2] if len(sys.argv) > 2 else "trace.json"
    max_ratio = float(os.environ.get("REPRO_OBS_MAX_OVERHEAD", "1.05"))
    errors = check(bench_path, trace_path, max_ratio)
    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
