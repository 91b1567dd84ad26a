"""The control: the plain reference in bf16, put in the program's place, must
come out not correct under each cell's committed limits, while the program
on the same run comes out correct (a tiny size on the CPU; the chip readings
the limits were set from are in PERF.md)."""
import time

import jax
import pytest

from tinycheckout import tiny_root  # noqa: F401  (fixture)

from harness import check, spec
from harness.peaks import TPU_PEAKS
from harness.session import Session


@pytest.mark.parametrize("cell", ["kg-batch-t0", "turing-batch-range"])
def test_control_fails_the_committed_limits(tiny_root, cell):  # noqa: F811
    c = spec.resolve(cell, tiny_root)
    session = Session(c, seed=2**35 + 3, seconds=1.0, trace=False, devices=jax.devices()[:1],
                      peaks=TPU_PEAKS["TPU v5 lite"], t_start=time.perf_counter(),
                      out_dir=tiny_root / "bench" / "out" / cell, control=True)
    out = c.driver().run(session)
    ok, compared = check.verdict(out.numbers, c.limits)
    assert ok, compared
    ok, compared = check.verdict(out.control_numbers, c.limits)
    assert not ok, compared
    assert out.control_numbers["score_gap"] > c.limits["score_gap"]
