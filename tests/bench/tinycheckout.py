"""Helpers of the benchmark's CPU tests: the harness on ``sys.path`` and a
tiny checkout whose cells run in seconds.

``tiny_root`` copies ``BENCHMARK.json`` and ``bench/`` into a temporary
checkout with the configurations cut to a few thousand rows and the limits
that depend on size set for them, and links the program's ``src``. ``run_tiny``
drives ``bench/run.py``'s ``main`` there without looking for a chip.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIGS = {
    "kg-pbg-wikidata-1m": {"n": 6000, "queries_per_split": 200},
    "msturing-range-1m": {"n": 6000, "n_query_vectors": 10, "assumed": {
        "n_clusters": 50, "centre_scale": 1.0}},
}
# the tiny sizes' own limit for recall_miss, which the committed limit (set
# at the cells' sizes on the chip) does not fit: set from CPU readings at
# these sizes (PERF.md)
TINY_LIMITS = {"kg-batch-t0": {"recall_miss": 0.42}, "turing-batch-range": {"recall_miss": 0.32}}


def make_tiny_root(dst: Path) -> Path:
    dst.mkdir(parents=True, exist_ok=True)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(BENCH, dst / "bench", ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(REPO / "src", dst / "src")
    for name, over in TINY_CONFIGS.items():
        p = dst / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg.update(over)
        p.write_text(json.dumps(cfg))
    for name, over in TINY_LIMITS.items():
        p = dst / "bench" / "limits" / f"{name}.json"
        lim = json.loads(p.read_text())
        lim["limits"].update(over)
        p.write_text(json.dumps(lim))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))


def run_tiny(root: Path, workload: str, seed: int, seconds: float = 1.0, trace: int = 0):
    """(exit code, result line) of ``bench/run.py`` on the CPU in ``root``."""
    import run
    from harness.peaks import TPU_PEAKS

    from io import StringIO
    from contextlib import redirect_stdout

    buf = StringIO()
    with redirect_stdout(buf):
        rc = run.main(
            ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)],
            root=root, require_tpu=False, peaks=TPU_PEAKS["TPU v5 lite"],
        )
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    return rc, (json.loads(lines[-1]) if rc == 0 else None)
