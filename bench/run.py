#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's configuration, traffic mix, metric readers and limits are found
by name (``bench/harness/spec.py``). The run makes its data from the seed,
builds and warms the system (``setup_s``), measures for ``--seconds``, then
checks every answer of the window against the plain reference. The last
line of standard output is one JSON object; the numbers compared, each with
its limit, are the last lines of standard error.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` profiles
the window and reports its per-layer metrics, the device's busy seconds and
a breakdown. Without a TPU, or with fewer chips than the cell needs, it
exits with 3 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 4


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` where
    set, else ``<checkout>/.jax_cache``; every program is kept."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def result_line(cell, session, outcome, correct: bool, compared: dict, dev: dict) -> dict:
    from harness.session import log
    from harness.spec import load_module

    metrics = {}
    if session.trace:
        for m in cell.per_layer:
            value = load_module(cell.root / "bench" / "metrics" / f"{m['name']}.py").read(outcome.readings)
            if value is None:
                log(f"per-layer metric {m['name']}: nothing to read")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(outcome.end_to_end, setup_s=session.setup_s)
        for m in cell.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=int(outcome.memory_peak_bytes))
    line = {
        "correct": bool(correct),
        "attempted": int(outcome.attempted),
        "failed": int(outcome.failed),
        "metrics": metrics,
        "device": device,
    }
    if session.trace:
        tr = outcome.readings.device
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = {
            "device_ops": tr.top_ops(10),
            "idle_gaps": tr.idle_gaps(outcome.readings.spans, outcome.readings.tracer_t0_ns, 10),
        }
    line["compared"] = compared
    return line


def main(argv=None, *, root: Path = ROOT, require_tpu: bool = True, peaks=None) -> int:
    """``require_tpu=False`` (the CPU tests only) skips the look for a chip
    and the compile cache, and takes ``peaks`` as given; ``root`` is the
    checkout whose ``BENCHMARK.json`` and ``src`` are used."""
    args = parse(argv)
    from harness import spec
    from harness.session import Session, eprint, log

    src = root / "src"
    if not (src / "repro").is_dir():
        eprint(f"no program under {src}: nothing to measure")
        return EXIT_NO_PROGRAM
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    cell = spec.resolve(args.workload, root)

    import jax

    from harness import check, device
    from harness.peaks import peaks_for

    if require_tpu:
        try:
            devices = device.require_chips(cell.chips)
        except device.NoChip as e:
            eprint(f"{e}: this benchmark runs only on the chip")
            return EXIT_NO_CHIP
        peaks = peaks_for(devices[0].device_kind)
        log(f"compile cache: {enable_compile_cache(root)}")
    else:
        devices = jax.devices()[: cell.chips]
    dev = device.describe(devices)
    log(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    log(f"cell {cell.name}: config {cell.config_name}, traffic {cell.traffic_name}, "
        f"seed {args.seed}, {args.seconds} s, trace {args.trace}")

    session = Session(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace), devices=devices,
        peaks=peaks, t_start=T_START, out_dir=root / "bench" / "out" / cell.name,
    )
    outcome = cell.driver().run(session)
    correct, compared = check.verdict(outcome.numbers, cell.limits)
    if session.window_compiles:
        log(f"WARNING: {session.window_compiles} compiles inside the window")
    line = result_line(cell, session, outcome, correct, compared, dev)
    check.print_compared(compared)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
