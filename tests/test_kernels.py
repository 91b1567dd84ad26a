"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_knn import fused_knn

RNG = np.random.default_rng(0)


@pytest.mark.parametrize("nq,nv,d,k", [(5, 300, 32, 4), (130, 1000, 64, 10), (1, 7, 8, 3), (257, 129, 16, 5)])
@pytest.mark.parametrize("metric", ["ip", "l2"])
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_fused_knn_matches_ref(nq, nv, d, k, metric, dtype):
    q = jnp.asarray(RNG.normal(size=(nq, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(nv, d)), dtype)
    valid = jnp.asarray(RNG.random(nv) > 0.3)
    s1, i1 = fused_knn(q, v, valid, k=k, metric=metric, interpret=True)
    s2, i2 = ref.masked_topk_ref(q, v, valid, k, metric)
    tol = 1e-4 if dtype == np.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=tol, atol=tol)
    # ids: same candidate sets modulo exact-tie ordering
    for r in range(nq):
        a = set(np.asarray(i1)[r][np.asarray(i1)[r] >= 0].tolist())
        b = set(np.asarray(i2)[r][np.asarray(i2)[r] >= 0].tolist())
        if len(a) == len(b) and np.unique(np.asarray(s2)[r]).size == k:
            assert a == b


def test_fused_knn_all_invalid():
    q = jnp.ones((4, 8), jnp.float32)
    v = jnp.ones((64, 8), jnp.float32)
    s, i = fused_knn(q, v, jnp.zeros(64, bool), k=3, interpret=True)
    assert (np.asarray(i) == -1).all()


@pytest.mark.parametrize("kernel", ["fused_knn", "fused_knn_db_stationary"])
def test_fused_knn_fewer_valid_than_k_across_tiles(kernel):
    """A query with fewer valid rows than k, spread over several v tiles:
    the slots past the last valid row stay absent (idx -1), never a repeat
    of a row already returned."""
    from repro.kernels import fused_knn as fk

    q = jnp.asarray(RNG.normal(size=(4, 8)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2000, 8)), jnp.float32)
    valid = jnp.zeros(2000, bool).at[jnp.array([5, 700, 1900])].set(True)
    s1, i1 = getattr(fk, kernel)(q, v, valid, k=5, tv=512, interpret=True)
    s2, i2 = ref.masked_topk_ref(q, v, valid, 5, "ip")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))
    assert (np.asarray(i1)[:, 3:] == -1).all()
    np.testing.assert_allclose(np.asarray(s1)[:, :3], np.asarray(s2)[:, :3], rtol=1e-5)


@pytest.mark.parametrize(
    "b,s,hq,hkv,dh,causal,window",
    [
        (2, 64, 4, 2, 32, True, 0),
        (1, 100, 4, 4, 16, True, 32),
        (2, 33, 8, 2, 64, False, 0),
        (1, 256, 2, 1, 32, True, 64),
    ],
)
def test_flash_attention_matches_ref(b, s, hq, hkv, dh, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, s, hq, dh)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, dh)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, dh)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window, bq=32, bk=32)
    refo = ref.flash_attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
        causal=causal, window=window or None,
    )
    np.testing.assert_allclose(np.asarray(out), np.moveaxis(np.asarray(refo), 1, 2), rtol=2e-3, atol=2e-3)


def test_chunked_jax_attention_matches_ref():
    from repro.models.attention import flash_attention as chunked

    q = jnp.asarray(RNG.normal(size=(2, 75, 8, 16)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(2, 75, 4, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(2, 75, 4, 16)), jnp.float32)
    out = chunked(q, k, v, causal=True, q_chunk=16, kv_chunk=32)
    refo = ref.flash_attention_ref(
        jnp.moveaxis(q, 1, 2), jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2), causal=True
    )
    np.testing.assert_allclose(np.asarray(out), np.moveaxis(np.asarray(refo), 1, 2), rtol=2e-3, atol=2e-3)


def test_ops_dispatch_pallas_equals_jnp():
    q = jnp.asarray(RNG.normal(size=(10, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(120, 16)), jnp.float32)
    valid = jnp.asarray(RNG.random(120) > 0.5)
    s1, _ = ops.masked_topk(q, v, valid, 4, metric="l2", use_pallas=True, interpret=True)
    s2, _ = ops.masked_topk(q, v, valid, 4, metric="l2", use_pallas=False)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_batched_masked_topk():
    q = jnp.asarray(RNG.normal(size=(3, 8, 16)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(3, 50, 16)), jnp.float32)
    valid = jnp.asarray(RNG.random((3, 50)) > 0.4)
    s, i = ops.batched_masked_topk(q, v, valid, 4, metric="ip", use_pallas=False)
    for w in range(3):
        s2, i2 = ref.masked_topk_ref(q[w], v[w], valid[w], 4, "ip")
        np.testing.assert_allclose(np.asarray(s[w]), np.asarray(s2), rtol=1e-5)


@pytest.mark.parametrize("nq,nv,d,k,metric", [(5, 300, 32, 4, "ip"), (100, 700, 16, 7, "l2"), (130, 64, 8, 3, "ip")])
def test_fused_knn_db_stationary_matches_ref(nq, nv, d, k, metric):
    from repro.kernels.fused_knn import fused_knn_db_stationary

    q = jnp.asarray(RNG.normal(size=(nq, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(nv, d)), jnp.float32)
    valid = jnp.asarray(RNG.random(nv) > 0.3)
    s1, i1 = fused_knn_db_stationary(q, v, valid, k=k, metric=metric, tq=32, tv=64, interpret=True)
    s2, i2 = ref.masked_topk_ref(q, v, valid, k, metric)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-4, atol=1e-4)


def test_pq_scan_kernel_matches_oracle():
    from repro.core.pq import PQIndex, adc_scan_ref, adc_tables
    from repro.kernels.pq_scan import pq_scan

    rng = np.random.default_rng(0)
    vecs = rng.normal(size=(500, 32)).astype(np.float32)
    idx = PQIndex.build(vecs, m=4)
    q = rng.normal(size=(3, 32)).astype(np.float32)
    luts = jnp.asarray(adc_tables(idx.cb, q))
    valid = jnp.asarray(rng.random(500) > 0.3)
    for r in range(3):
        s1, i1 = pq_scan(luts[r], jnp.asarray(idx.codes), valid, k=5, tv=128, interpret=True)
        s2, i2 = adc_scan_ref(luts[r : r + 1], jnp.asarray(idx.codes), valid, 5)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2)[0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("w,tq,nv,m,k", [(3, 5, 100, 4, 6), (1, 8, 700, 8, 10), (4, 2, 30, 4, 3)])
def test_workunit_pq_topk_matches_ref(w, tq, nv, m, k):
    """Batched work-unit ADC kernel: pallas (one-hot MXU contraction) == jnp
    reference == the single-query oracle, with uint8 code tiles."""
    from repro.core.pq import PQIndex, adc_scan_ref, adc_tables

    rng = np.random.default_rng(w * 100 + m)
    d = m * 8
    vecs = rng.normal(size=(max(nv, 300), d)).astype(np.float32)
    idx = PQIndex.build(vecs, m=m)
    luts = np.stack(
        [adc_tables(idx.cb, rng.normal(size=(tq, d)).astype(np.float32)) for _ in range(w)]
    )
    codes = np.stack([idx.codes[rng.integers(0, len(vecs), nv)] for _ in range(w)])
    assert codes.dtype == np.uint8  # ships uint8 across the dispatch boundary
    valid = rng.random((w, nv)) > 0.3
    s_ref, i_ref = ref.workunit_pq_topk_ref(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(valid), k
    )
    s_pl, i_pl = ops.workunit_pq_topk(
        jnp.asarray(luts), jnp.asarray(codes), jnp.asarray(valid), k,
        use_pallas=True, interpret=True,
    )
    np.testing.assert_allclose(np.asarray(s_pl), np.asarray(s_ref), rtol=1e-4, atol=1e-4)
    for w_ in range(w):
        # unit w_ equals the one-query oracle run on its own tables
        s1, _ = adc_scan_ref(
            jnp.asarray(luts[w_]), jnp.asarray(codes[w_]), jnp.asarray(valid[w_]), k
        )
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s_ref)[w_], rtol=1e-4, atol=1e-4)
        for r in range(tq):
            a = np.asarray(i_ref)[w_, r]
            b = np.asarray(i_pl)[w_, r]
            assert set(a[a >= 0].tolist()) == set(b[b >= 0].tolist())
