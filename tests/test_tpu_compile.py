"""The main path's kernels compile for a TPU v5e, without one attached.

Each test lowers and compiles one kernel for a described ``v5e:2x2`` chip at
``chip_smoke.py``'s widths (d = 200, TQ = 64, TV in {32, 512}, k = 10; PQ at
M = 8 with k' = 40 over a 2,000-row LUT table, and at the published M = 50 of
the ``kg-batch-pq50`` cell, whose table is zero-padded to 56 subspace rows),
so the TPU compiler's
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
from repro.kernels.pq_scan import subspace_pad, workunit_pq_scan, workunit_pq_scan_streamed

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


@pytest.fixture(scope="module")
def is_adc():
    """The ADC roofline reader's match of the two ADC kernels' ops, and the
    trace's op record it reads."""
    bench = Path(__file__).resolve().parents[1] / "bench"
    if str(bench) not in sys.path:
        sys.path.insert(0, str(bench))
    from harness.spec import load_module
    from harness.trace import Op

    return load_module(bench / "metrics" / "pq_scan_roofline.pq.py").is_adc, Op


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


# (M, TV): both tile sizes at M = 8, the larger one at the published M = 50
PQ_SHAPES = pytest.mark.parametrize("m,tv", [(8, 32), (8, 512), (50, 512)],
                                    ids=["32", "512", "m50-512"])


def _adc_kernels(hlo):
    return [ln.strip() for ln in hlo.splitlines() if "tpu_custom_call" in ln and " = " in ln]


@PQ_SHAPES
def test_workunit_pq_scan_compiles(one_chip, is_adc, m, tv):
    hlo = _compile(
        functools.partial(workunit_pq_scan, k=K_PQ, interpret=False), one_chip,
        ((W, TQ, subspace_pad(m), 256), jnp.float32), ((W, tv, m), jnp.uint8), ((W, tv), jnp.bool_),
    )
    match, Op = is_adc
    kernels = _adc_kernels(hlo)
    assert kernels and all(match(Op(k, "", 0.0, 0.0, 0)) for k in kernels), kernels


@PQ_SHAPES
def test_workunit_pq_scan_streamed_compiles(one_chip, is_adc, m, tv):
    hlo = _compile(
        functools.partial(workunit_pq_scan_streamed, k=K_PQ, interpret=False), one_chip,
        ((U, subspace_pad(m), 256), jnp.float32), ((W, TQ), jnp.int32),
        ((W, tv, m), jnp.uint8), ((W, tv), jnp.bool_),
    )
    match, Op = is_adc
    kernels = _adc_kernels(hlo)
    assert kernels and all(match(Op(k, "", 0.0, 0.0, 0)) for k in kernels), kernels


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


@pytest.mark.parametrize("w,lp", GATHER_CELLS["kg"][2])
def test_resident_code_gather_compiles(one_chip, w, lp):
    """The kg-batch-pq50 cell's code tiles: the same buckets as kg-batch-t0
    (one corpus, one index build), gathered from 1M resident M = 50 codes."""
    n, m = GATHER_CELLS["kg"][0], 50
    args = [jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)
            for sh, dt in (((n, m), jnp.uint8), ((w,), jnp.int32))]
    lowered = ops.gather_unit_codes.lower(*args, lp=lp)
    (codes,) = jax.tree_util.tree_leaves(lowered.out_info)
    assert codes.shape == (w, lp, m) and codes.dtype == jnp.uint8
    assert "tpu_custom_call" not in lowered.compile().as_text()


def test_adc_tables_compile(one_chip):
    """The tables of 2,048 padded queries at d = 200, M = 50, padded to 56
    subspace rows, in one program."""
    from repro.core.pq import adc_tables_device

    args = [jax.ShapeDtypeStruct(sh, jnp.float32, sharding=one_chip)
            for sh in ((50, 256, 4), (2048, D))]
    lowered = adc_tables_device.lower(*args, metric="ip", m_pad=subspace_pad(50))
    (luts,) = jax.tree_util.tree_leaves(lowered.out_info)
    assert luts.shape == (2048, 56, 256) and luts.dtype == jnp.float32
    lowered.compile()


# the merge buffer of a kg pass (2^19 candidate rows) laid out from the kg
# cell's buckets (W units of TQ slots), and the widest step: the (8192, 32)
# bucket, k = 10 in f32, k' = 40 columns of which 32 filled in pq
@pytest.mark.parametrize("mode", ["f32", "pq"])
def test_merge_candidates_compile_without_a_loop(one_chip, readers, mode):
    """A TPU lowers a scatter of rows narrower than its buffer to a loop
    over the rows (a pq pass took seconds so): the candidate steps must
    compile to no loop, and keep names the merge reader does not take."""
    _, is_merge, Op = readers
    rows, n, kk = 1 << 19, 1_000_000, (10 if mode == "f32" else 32)
    width = K if mode == "f32" else K_PQ

    def arg(sh, dt):
        return jax.ShapeDtypeStruct(sh, dt, sharding=one_chip)

    lowered = ops.unit_topk_rows.lower(
        arg((8192, TQ, kk), jnp.float32), arg((8192, TQ, kk), jnp.int32),
        arg((8192,), jnp.int32), n, width=width,
    )
    assert [o.shape for o in lowered.out_info] == [(8192 * TQ, width)] * 2
    hlos = [lowered.compile().as_text()]
    blocks = [(w * TQ, width) for w, _ in GATHER_CELLS["kg"][2]]
    lowered = ops.gather_candidates.lower(
        [arg(b, jnp.float32) for b in blocks], [arg(b, jnp.int32) for b in blocks],
        arg((rows,), jnp.int32), arg((2_000,), jnp.int32),
    )
    s, v, seg = lowered.out_info
    assert s.shape == v.shape == (rows, width) and seg.shape == (rows,)
    hlos.append(lowered.compile().as_text())
    hlos.append(ops.candidate_ids.lower(
        arg((2_048, K), jnp.int32), n, arg((n,), jnp.int32)
    ).compile().as_text())
    for hlo in hlos:
        assert "while(" not in hlo and "tpu_custom_call" not in hlo
        module = hlo.split(None, 2)[1].rstrip(",")
        assert not is_merge(Op("%fusion = f32[] fusion()", f"{module}(1)", 0.0, 0.0, 0))
