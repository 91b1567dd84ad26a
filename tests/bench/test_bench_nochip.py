"""``bench/run.py`` refuses to measure off the chip and in a checkout that
holds only the benchmark: a non-zero exit and no result line."""
import shutil
from contextlib import redirect_stdout
from io import StringIO

from tinycheckout import BENCH, REPO

import run


def main_quietly(argv, **kw):
    buf = StringIO()
    with redirect_stdout(buf):
        rc = run.main(argv, **kw)
    return rc, buf.getvalue()


def test_no_tpu_no_result():
    """The tests run with JAX on the CPU, where the look for a chip fails."""
    rc, out = main_quietly(["--workload", "kg-batch-t0", "--seed", "1", "--seconds", "1"])
    assert rc == run.EXIT_NO_CHIP
    assert not any(line.startswith("{") for line in out.splitlines())


def test_benchmark_files_alone_no_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("out", "__pycache__"))
    rc, out = main_quietly(["--workload", "kg-batch-t0", "--seed", "1", "--seconds", "1"], root=tmp_path)
    assert rc == run.EXIT_NO_PROGRAM
    assert not any(line.startswith("{") for line in out.splitlines())
