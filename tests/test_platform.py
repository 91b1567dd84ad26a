"""Backend, peak-table and compile-cache choices follow the platform, and
the index build compiles few programs whatever its partitions' sizes."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compile_cache
from repro.core import exhaustive_search
from repro.core import kmeans as km
from repro.kernels import fused_knn as fk
from repro.kernels import ops, platform
from repro.launch import roofline

from conftest import assert_same_results, small_db, small_workload

ROOT = Path(__file__).resolve().parents[1]


def test_off_tpu_ops_default_to_jnp_and_pallas_interprets(monkeypatch):
    assert not platform.on_tpu()
    monkeypatch.setattr(ops, "_CPU_PALLAS", False)
    assert ops.resolve_backend(None, None) == (False, True)
    assert ops.resolve_backend(True, None) == (True, True)
    monkeypatch.setattr(ops, "_CPU_PALLAS", True)  # the CPU Pallas test jobs
    assert ops.resolve_backend(None, None) == (True, True)
    # a Pallas dispatch that names no interpret mode still runs off a TPU
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 64, 16)), jnp.float32)
    valid = jnp.asarray(rng.random((2, 64)) < 0.7)
    s_p, i_p = ops.workunit_topk(q, v, valid, 4, use_pallas=True)
    s_j, i_j = ops.workunit_topk(q, v, valid, 4, use_pallas=False)
    for w in range(2):
        assert_same_results(np.asarray(s_p[w]), np.asarray(i_p[w]),
                            np.asarray(s_j[w]), np.asarray(i_j[w]))


def test_on_tpu_ops_default_to_compiled_pallas(monkeypatch):
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    assert ops.resolve_backend(None, None) == (True, False)
    assert ops.resolve_backend(False, None) == (False, False)
    assert ops.resolve_backend(None, True) == (True, True)  # asked explicitly
    assert fk.default_interpret(None) is False


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


@pytest.mark.parametrize(
    "platform, kind, expect",
    [
        ("tpu", "TPU v5 lite", roofline.TPU_PEAKS["TPU v5 lite"]),
        ("cpu", "cpu", roofline.CPU_TERMS),
        ("tpu", "TPU v99 imaginary", ValueError),
    ],
)
def test_current_hardware_by_device_kind(monkeypatch, platform, kind, expect):
    monkeypatch.setattr(jax, "devices", lambda *a, **kw: [_Device(platform, kind)])
    if expect is ValueError:
        with pytest.raises(ValueError, match="TPU v99 imaginary"):
            roofline.current_hardware()
    else:
        assert roofline.current_hardware() == expect


def test_v5e_peaks_match_published_figures():
    hw = roofline.TPU_PEAKS["TPU v5 lite"]
    assert (hw.peak_flops, hw.hbm_bw) == (197e12, 819e9)


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
print(enable_compile_cache())
salt = float(sys.argv[1])
jax.jit(lambda x: jnp.sin(x) * salt)(jnp.arange(11.0)).block_until_ready()
"""


def _files(d: Path) -> set:
    return {p.name for p in d.rglob("*") if p.is_file()} if d.exists() else set()


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_lands_in_its_directory(tmp_path, env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    want = tmp_path / "cc" if env_dir else compile_cache.DEFAULT_DIR
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    before = _files(want)
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_PROBE, str(np.random.default_rng().random())],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    )
    assert out.stdout.strip().splitlines()[-1] == str(want)
    assert _files(want) - before  # a new compiled program landed there
    if env_dir:
        assert not (tmp_path / ".jax_cache").exists()


def test_exhaustive_search_chunking_keeps_results():
    db = small_db(n=1500, d=16, seed=3)
    wl = small_workload(db, n_queries=40)
    ref = exhaustive_search(db, wl)
    # 7 queries per chunk: every template spans several padded chunks
    small = exhaustive_search(db, wl, score_budget_bytes=4 * db.n * 7)
    np.testing.assert_array_equal(ref.ids, small.ids)
    np.testing.assert_array_equal(ref.scores, small.scores)


def test_exhaustive_search_pads_each_template_to_its_own_size(monkeypatch):
    """Many templates of few queries each: the padded rows scored stay within
    twice the real queries (a template does not pay for the whole chunk)."""
    db = small_db(n=800, d=16, seed=4)
    wl = small_workload(db, n_queries=60)
    ref = exhaustive_search(db, wl)
    widths = []
    real_topk = ops.masked_topk

    def counting(q, *a, **kw):
        widths.append(q.shape[0])
        return real_topk(q, *a, **kw)

    monkeypatch.setattr(ops, "masked_topk", counting)
    got = exhaustive_search(db, wl, score_budget_bytes=4 * db.n * 32)
    sizes = [len(wl.queries_for_template(t)) for t in range(len(wl.templates))]
    assert len([s for s in sizes if s]) >= 4
    assert sum(widths) <= 2 * wl.m
    assert max(widths) <= 32
    assert len(set(widths)) <= 6  # powers of two up to the 32-query chunk
    np.testing.assert_array_equal(ref.ids, got.ids)
    np.testing.assert_array_equal(ref.scores, got.scores)


def test_kmeans_and_probe_programs_shared_across_centroid_counts():
    """Centroid counts that round to one power of two run one k-means and
    one probe program (an index build used to compile both once per
    partition), and the padding centroids never win."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(600, 16)).astype(np.float32)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    fns = (km._assign, km._update, km._topm)
    before = [f._cache_size() for f in fns]
    for k in (17, 23, 31):
        cents = km.train_kmeans(x, k, iters=2, metric="l2", seed=1)
        assert cents.shape == (k, 16) and np.isfinite(cents).all()
        best = np.asarray(ops.pairwise_scores(jnp.asarray(x), jnp.asarray(cents), "l2"))
        got = km.assign_kmeans(x, cents, metric="l2")
        assert got.max() < k
        np.testing.assert_allclose(best[np.arange(len(x)), got], best.max(axis=1), rtol=1e-5)
        probes = km.topm_centroids(q, cents, 4, metric="l2")
        assert probes.shape == (5, 4) and probes.max() < k
    assert all(f._cache_size() - b <= 1 for f, b in zip(fns, before))
