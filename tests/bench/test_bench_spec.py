"""Every cell of ``BENCHMARK.json`` resolves by name to its files, and a new
configuration, traffic mix or metric needs only new files and entries."""
import json
import re
import shutil

import pytest

from tinycheckout import BENCH, REPO

from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCHMARK = json.loads((REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCHMARK["workloads"]]


def test_benchmark_keys_and_names():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs", "workloads",
                              "end_to_end", "per_layer"}
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    for p in BENCHMARK["paths"]:
        assert (REPO / p).is_dir()
    assert (REPO / BENCHMARK["command"][1]).is_file()
    names = [x["name"] for key in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCHMARK[key]]
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCHMARK["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for c in BENCHMARK["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    c = spec.resolve(cell, REPO)
    assert c.generator().generate
    assert c.driver().run
    assert set(c.limits) >= {"unanswered", "bad_answers", "score_gap"}
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert spec.load_module(BENCH / "metrics" / f"{m['name']}.py").read
        # the end-to-end metric it moves is reported in this cell
        assert any(e["name"] == m["moves"] for e in c.end_to_end)


def test_new_cell_is_new_files_only(tmp_path):
    """A later PR adds a configuration, a mix, a metric and a cell: files and
    entries only; nothing already there changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = json.loads((BENCH / "configs" / "kg-pbg-wikidata-1m.json").read_text())
    cfg.update(name="kg-other", n=2_000_000)
    (root / "bench" / "configs" / "kg-other.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "replay-t3.json").write_text(json.dumps(
        {"driver": "batch", "log": "t3", "build_log": "t0"}))
    (root / "bench" / "metrics" / "passes.batch.py").write_text("def read(r):\n    return r.passes\n")
    (root / "bench" / "limits" / "kg-other-drift.json").write_text(json.dumps(
        {"limits": {"unanswered": 0, "bad_answers": 0, "score_gap": 1e-4}}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "kg-other", "source": "x", "file": "bench/configs/kg-other.json",
                             "reduced": ["n"], "why": "x"})
    bench["workloads"].append({"name": "kg-other-drift", "config": "kg-other", "traffic": "replay-t3",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "passes.batch", "unit": "passes", "better": "higher",
                               "source": "host_clock", "layer": "plan", "moves": "batch_qps",
                               "workloads": ["kg-other-drift"]})
    for m in bench["end_to_end"]:
        if "workloads" in m and m["name"] == "batch_qps":
            m["workloads"].append("kg-other-drift")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    c = spec.resolve("kg-other-drift", root)
    assert c.config["n"] == 2_000_000 and c.traffic["log"] == "t3"
    assert c.driver().__file__.endswith("batch.py")
    assert [m["name"] for m in c.per_layer] == ["passes.batch"]
    assert spec.load_module(root / "bench" / "metrics" / "passes.batch.py").read(
        type("R", (), {"passes": 3})()) == 3
    after = {p: p.read_bytes() for p in before}
    assert after == before
