#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, in one process.

    python3 bench/control.py --workload kg-batch-t0 --seeds 11,12,13 --seconds 5
    python3 bench/control.py --workload kg-batch-t0 --seeds 11,12,13 --fault merge_shifted

For each seed: the cell's set-up and a short window at its own load, as
``bench/run.py`` runs them, then the numbers compared for the program's
answers and for the control's: the plain reference computed in bf16 and put
in the program's place, answering the same queries. With ``--fault`` the
program runs with that fault planted under its timed path
(``harness/faults.py``). Prints one JSON line per seed. The limits in
``bench/limits/<cell>.json`` are set from these readings: above the largest
the sound program gives, below the smallest the control or a fault gives
(``PERF.md``). The benchmark's own runs never compute the control or plant
a fault. Needs the chip.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", default=None, help="a fault of harness/faults.py to plant")
    args = ap.parse_args(argv)

    import run
    from harness import device, spec
    from harness.peaks import peaks_for
    from harness.session import Session

    cell = spec.resolve(args.workload, ROOT)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"{e}: the control runs only on the chip", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.enable_compile_cache(ROOT)
    if args.fault:
        from harness.faults import FAULTS

        FAULTS[args.fault](setattr)
    for seed in (int(s) for s in args.seeds.split(",")):
        session = Session(cell, seed=seed, seconds=args.seconds, trace=False, devices=devices,
                          peaks=peaks_for(devices[0].device_kind), t_start=time.perf_counter(),
                          out_dir=ROOT / "bench" / "out" / cell.name, control=True)
        out = cell.driver().run(session)
        print(json.dumps({
            "seed": seed,
            "fault": args.fault,
            "program": out.numbers,
            "control": out.control_numbers,
            "end_to_end": out.end_to_end,
            "setup_s": session.setup_s,
            "window_compiles": session.window_compiles,
        }), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
