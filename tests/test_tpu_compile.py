"""The main path's kernels compile for a TPU v5e, without one attached.

Each test lowers and compiles one kernel for a described ``v5e:2x2`` chip at
``chip_smoke.py``'s widths (d = 200, TQ = 64, TV in {32, 512}, k = 10; PQ at
M = 8 with k' = 40 over a 2,000-row LUT table), so the TPU compiler's
refusals (block tiling, VMEM, unaligned slices) show up here instead of on
the chip. The sharded engine's programs compile over a mesh of the four
described chips, as ``chip_smoke.py --chips 4`` runs them. Nothing runs:
shapes only. The compiled scan kernels and merge programs also keep the
names the benchmark's device-trace readers match.
"""
import functools
import os
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.fused_knn import fused_knn, fused_knn_db_stationary
from repro.kernels.pq_scan import workunit_pq_scan, workunit_pq_scan_streamed

W, TQ, D, K, M, K_PQ, U = 4, 64, 200, 10, 8, 40, 2_000
R = 4  # mesh ranks


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices[:R]), ("model",))


@pytest.fixture(scope="module")
def readers():
    """The benchmark's device-trace readers of the scan kernels and the
    merge programs, and the trace's op record they read."""
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from harness.spec import load_module
    from harness.trace import Op

    scan = load_module(bench / "metrics" / "scan_roofline.batch.py")
    merge = load_module(bench / "metrics" / "merge_device_ms.batch.py")
    return scan.is_scan, merge.is_merge, Op


def _compile(fn, sharding, *shapes):
    """``shapes`` are (shape, dtype) or (shape, dtype, sharding) for an
    operand placed other than ``sharding``."""
    args = [jax.ShapeDtypeStruct(s[0], s[1], sharding=(s[2:] or (sharding,))[0]) for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("tv", [32, 512])
@pytest.mark.parametrize("kernel", [fused_knn, fused_knn_db_stationary])
def test_f32_scan_kernels_compile(one_chip, kernel, tv):
    scan = jax.vmap(functools.partial(kernel, k=K, interpret=False))
    hlo = _compile(
        scan, one_chip,
        ((W, TQ, D), jnp.float32), ((W, tv, D), jnp.float32), ((W, tv), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("tv", [32, 512])
def test_workunit_pq_scan_compiles(one_chip, tv):
    hlo = _compile(
        functools.partial(workunit_pq_scan, k=K_PQ, interpret=False), one_chip,
        ((W, TQ, M, 256), jnp.float32), ((W, tv, M), jnp.uint8), ((W, tv), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("tv", [32, 512])
def test_workunit_pq_scan_streamed_compiles(one_chip, tv):
    hlo = _compile(
        functools.partial(workunit_pq_scan_streamed, k=K_PQ, interpret=False), one_chip,
        ((U, M, 256), jnp.float32), ((W, TQ), jnp.int32),
        ((W, tv, M), jnp.uint8), ((W, tv), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_segmented_merge_compiles(one_chip):
    rows, n_queries = 16_000, 2_000
    merge = functools.partial(
        ops._segmented_merge_topk_jnp, n_segments=n_queries, k=K
    )
    hlo = _compile(
        merge, one_chip,
        ((rows, K), jnp.float32), ((rows, K), jnp.int32), ((rows,), jnp.int32),
    )
    assert "while" in hlo  # k rounds of segment reductions


@pytest.mark.parametrize("tv", [32, 512])
def test_sharded_f32_scan_compiles_on_four_chips(four_chips, tv):
    scan = ops._sharded_scan_fn(four_chips, "model", K, "ip", True, False)
    hlo = _compile(
        scan, NamedSharding(four_chips, P("model")),
        ((R, W, TQ, D), jnp.float32), ((R, W, tv, D), jnp.float32), ((R, W, tv), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_sharded_pq_scan_compiles_on_four_chips(four_chips):
    scan = ops._sharded_pq_fn(four_chips, "model", K_PQ, True, False, True)
    hlo = _compile(
        scan, NamedSharding(four_chips, P("model")),
        ((U, M, 256), jnp.float32, NamedSharding(four_chips, P())),
        ((R, W, TQ), jnp.int32), ((R, W, 512, M), jnp.uint8), ((R, W, 512), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_sharded_gather_merge_compiles_on_four_chips(four_chips):
    merge = ops._sharded_merge_fn(four_chips, "model", K)
    hlo = _compile(
        merge, NamedSharding(four_chips, P("model")),
        ((R, 256, K), jnp.float32), ((R, 256, K), jnp.int32),
    )
    assert "all-gather" in hlo


@pytest.mark.parametrize("tv", [32, 512])  # both grids of the engine's scan
def test_scan_kernel_ops_keep_the_roofline_readers_name(one_chip, readers, tv):
    is_scan, _, Op = readers
    hlo = _compile(
        ops._unit_scan_fn(K, "ip", True, False), one_chip,
        ((W, TQ, D), jnp.float32), ((W, tv, D), jnp.float32), ((W, tv), jnp.bool_),
    )
    kernels = [ln.strip() for ln in hlo.splitlines() if "tpu_custom_call" in ln and " = " in ln]
    assert kernels
    assert all(is_scan(Op(k, "", 0.0, 0.0, 0)) for k in kernels), kernels


@pytest.mark.parametrize("merge", ["segmented", "final"])
def test_merge_programs_keep_the_merge_readers_name(one_chip, readers, merge):
    _, is_merge, Op = readers
    rows, n_queries = 16_000, 2_000
    if merge == "segmented":
        shapes = ((rows, K), jnp.float32), ((rows, K), jnp.int32), ((rows,), jnp.int32)
        fn, static = ops._segmented_merge_topk_jnp, dict(n_segments=n_queries, k=K)
    else:
        shapes = ((n_queries, 128), jnp.float32), ((n_queries, 128), jnp.int32)
        fn, static = ops._merge_topk_jnp, dict(k=K)
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip) for sh, dt in shapes]
    hlo = fn.lower(*args, **static).compile().as_text()
    module = hlo.split(None, 2)[1].rstrip(",")  # "HloModule <name>, ..."
    # the trace names a program's ops by its module and a fingerprint
    assert is_merge(Op("%fusion = f32[] fusion()", f"{module}(2233347581744820355)", 0.0, 0.0, 0))


# the benchmark cells' f32 scan buckets (W, lp) over their arenas (N rows of
# width D), as their warm-up logs them on the chip; queries padded to 2,048
GATHER_CELLS = {
    "kg": (1_000_000, 200, [(4, 4096), (64, 2048), (1024, 512), (8192, 32)]),
    "turing": (1_000_000, 100, [(2, 4096), (256, 2048), (8192, 256), (4096, 32)]),
}


@pytest.mark.parametrize("cell", sorted(GATHER_CELLS))
def test_resident_gather_compiles_apart_from_the_scan_kernels(one_chip, readers, cell):
    is_scan, is_merge, Op = readers
    n, d, buckets = GATHER_CELLS[cell]
    for w, lp in buckets:
        args = [
            jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in (((n, d), jnp.float32), ((2048, d), jnp.float32),
                           ((w,), jnp.int32), ((w, TQ), jnp.int32))
        ]
        lowered = ops.gather_unit_operands.lower(*args, lp=lp)
        q, v = lowered.out_info
        assert q.shape == (w, TQ, d) and v.shape == (w, lp, d)
        assert q.dtype == v.dtype == jnp.float32
        hlo = lowered.compile().as_text()
        # no kernel of its own, so the roofline reader times the scan alone,
        # and a module name the merge reader does not take
        assert "tpu_custom_call" not in hlo
        ops_ = [ln.strip() for ln in hlo.splitlines() if " = " in ln and ln.strip().startswith("%")]
        assert ops_ and not any(is_scan(Op(o, "", 0.0, 0.0, 0)) for o in ops_)
        module = hlo.split(None, 2)[1].rstrip(",")
        assert not is_merge(Op("%fusion = f32[] fusion()", f"{module}(1)", 0.0, 0.0, 0))
