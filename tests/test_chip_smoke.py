"""``chip_smoke.py`` rehearsed on the CPU at a small size.

The phases run as the chip runs them — Pallas side in interpret mode here —
at n = 5,000, d = 200 and 200 queries, and the sharded phase on four virtual
CPU devices; the platform check is bypassed by calling the phases directly,
and ``main`` itself must refuse the CPU.
"""
import importlib.util
import json
from pathlib import Path

import pytest

from repro.kernels import ops

from test_distributed import run_with_devices

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_on_cpu(smoke, tmp_path, monkeypatch):
    smoke.kernel_phase(200)
    kg, index = smoke.build_deployment(5_000, 200, 200)
    batch = smoke.batch_phase(kg, index)
    assert 0.0 < batch["recall_pq"] <= 1.0 and 0.0 < batch["recall_f32"] <= 1.0
    # the service dispatches on the platform's default backend: Pallas, as
    # on the chip; enough inserts that the delta scan spans several tiles
    monkeypatch.setattr(ops, "_CPU_PALLAS", True)
    out = smoke.service_phase(
        kg, index, tmp_path / "store", n_queries=64, n_insert=1_500, n_delete=64
    )
    assert out["health"] == "ok"
    assert not (tmp_path / "store").exists()


def test_chip_smoke_sharded_phase_on_four_cpu_devices():
    out = run_with_devices(f"""
        import importlib.util, jax
        spec = importlib.util.spec_from_file_location("chip_smoke", {str(ROOT / "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(smoke)
        kg, index = smoke.build_deployment(12_000, 200, 200)
        assert len(index.partitions) > 1
        print(smoke.sharded_phase(kg, index, jax.devices()[:4]))
    """, n=4)
    assert out.strip().splitlines()[-1] == "{'f32': True, 'pq': True}"


def test_chip_smoke_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) == 1
    lines = capsys.readouterr().out.strip().splitlines()
    assert "platform=cpu" in lines[0]
    assert not any(l.startswith("{") and json.loads(l).get("ok") for l in lines)
