"""A whole run of each cell at a tiny size on the CPU, sound and with the
timed path broken underneath (``bench/harness/faults.py``): ``correct``
must come out false for every fault the cell can have, and true without
one."""
import pytest

from tinycheckout import run_tiny, tiny_root  # noqa: F401  (fixture)

from harness.faults import FAULTS

BATCH = ["kg-batch-t0", "turing-batch-range"]


@pytest.mark.parametrize("cell", BATCH)
def test_sound_run_is_correct(tiny_root, cell):  # noqa: F811
    rc, line = run_tiny(tiny_root, cell, seed=2**33 + 7)
    assert rc == 0
    assert line["correct"], line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) >= {"setup_s", "recall_at_10"}


def test_traced_run_reports_per_layer_metrics(tiny_root):  # noqa: F811
    rc, line = run_tiny(tiny_root, "kg-batch-t0", seed=11, trace=1)
    assert rc == 0 and line["correct"]
    assert {"route_ms.batch", "plan_ms.batch", "execute_ms.batch", "scan_pairs.batch"} <= set(
        line["metrics"])
    assert line["device"]["window_s"] > 0 and "breakdown" in line


def failing(line) -> set:
    return {k for k, c in line["compared"].items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("cell", BATCH)
@pytest.mark.parametrize("fault,caught_by", [
    ("half_the_batch", {"unanswered"}),
    ("altered_answer", {"bad_answers", "score_gap"}),
    ("merge_shifted", {"recall_miss"}),
    ("scan_skips_rows", {"recall_miss"}),
])
def test_batch_fault_is_not_correct(tiny_root, monkeypatch, cell, fault, caught_by):  # noqa: F811
    FAULTS[fault](monkeypatch.setattr)
    rc, line = run_tiny(tiny_root, cell, seed=5)
    assert rc == 0
    assert not line["correct"], line["compared"]
    assert failing(line) & caught_by, line["compared"]
