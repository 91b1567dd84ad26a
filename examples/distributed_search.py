"""Distributed batch hybrid search on a multi-device mesh (sharded engine).

Demonstrates the production topology at laptop scale: the packed arena is
sharded over the "model" axis (contiguous posting-list slices per rank), the
workload is planned ONCE and its work units route to the rank storing their
posting list, every bucket executes as one shard_map dispatch with bitmap
pushdown intact, and the only cross-rank traffic is the k·|model| per-query
candidate all-gather. Results are bit-identical to the single-device engine.

Run with 8 simulated devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_search.py
"""
import os
import sys

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402

from repro.core import Column, VectorDatabase  # noqa: E402
from repro.core.ivf import IVFIndex  # noqa: E402
from repro.core.planner import batch_search_ivf  # noqa: E402
from repro.core.predicates import Contains, evaluate_filter, make_filter  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

rng = np.random.default_rng(0)
n, d, m, k = 64_000, 32, 512, 10
mesh = make_test_mesh((2, 4), ("data", "model"))
print(f"devices: {len(jax.devices())}, mesh: {dict(mesh.shape)}")

membership = rng.random((n, 4)) < 0.3
membership[np.arange(n), rng.integers(0, 4, n)] = True
db = VectorDatabase(
    vectors=rng.normal(size=(n, d)).astype(np.float32),
    columns={"type": Column.setcat("type", membership)},
    metric="ip",
)
bitmap = evaluate_filter(make_filter(Contains("type", 2)), db)
queries = rng.normal(size=(m, d)).astype(np.float32)

ivf = IVFIndex.build(db.vectors, metric="ip", n_centroids=64, seed=0)
scores, ids = batch_search_ivf(
    ivf, queries, nprobe=16, k=k, bitmap=bitmap, mesh=mesh
)

# verify against the single-device engine: results must be bit-identical
s_ref, i_ref = batch_search_ivf(ivf, queries, nprobe=16, k=k, bitmap=bitmap)
assert np.array_equal(scores, s_ref) and np.array_equal(ids, i_ref)
print(f"searched {m} hybrid queries against {n} vectors across {mesh.shape['model']} model ranks")
print("top-3 of query 0:", ids[0][:3].tolist(), "scores", np.round(scores[0][:3], 3).tolist())
print("OK — sharded == single-device, bit-exact")
