"""Product quantization (Jégou et al. 2011) — the compressed-index companion

to IVF used by the paper's FAISS baseline family (IVF-PQ).

Vectors are split into M subvectors, each quantized against a 256-entry
codebook → codes are [n, M] uint8 (d·4 / M bytes ⇒ e.g. 32× compression at
d=64, M=8). Asymmetric distance computation (ADC): per query, precompute a
[M, 256] lookup table of partial distances; a database vector's score is a
sum of M table lookups — no float vector ever read at scan time.

TPU adaptation: a query's LUT (M·256·4 B: 50 KB at the published M = 50)
lives in VMEM; the scan is a gather+accumulate over uint8 code tiles fused
with the same running top-k as fused_knn (kernels/pq_scan.py). The tables
are one jitted device program (``adc_tables_device``) over the resident
queries and the resident codebook (``PQCodebook.device_centroids``). Bitmap
pushdown composes unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_knn import HIGHEST
from ..obs.trace import to_device
from . import kmeans as km

# one upload per codebook when the service's scheduler thread and a
# foreground search reach it together
_UPLOAD_LOCK = threading.Lock()


@dataclasses.dataclass
class PQCodebook:
    centroids: np.ndarray  # f32 [M, 256, dsub]
    metric: str
    # ``centroids`` on the device (``device_centroids``); not an init field,
    # so it is never part of the state or of a replaced codebook
    _centroids_dev: Optional[Any] = dataclasses.field(
        default=None, init=False, repr=False, compare=False
    )

    @property
    def m(self) -> int:
        return int(self.centroids.shape[0])

    @property
    def dsub(self) -> int:
        return int(self.centroids.shape[2])

    @property
    def d(self) -> int:
        """Vector dimensionality this codebook encodes (m · dsub)."""
        return self.m * self.dsub

    def device_centroids(self) -> Any:
        """``centroids`` resident on the device, f32 [M, 256, dsub]: uploaded
        once per codebook (``codebook.h2d``), read by every execute's
        ``adc_tables_device``."""
        if self._centroids_dev is None:
            with _UPLOAD_LOCK:
                if self._centroids_dev is None:
                    (self._centroids_dev,) = to_device(
                        "codebook.h2d", np.asarray(self.centroids, np.float32)
                    )
        return self._centroids_dev

    def to_state(self) -> dict:
        """Snapshot state (store/snapshot.py): arrays stay np.ndarray leaves."""
        return {"metric": self.metric, "centroids": self.centroids}

    @staticmethod
    def from_state(state: dict) -> "PQCodebook":
        return PQCodebook(
            centroids=np.asarray(state["centroids"]), metric=state["metric"]
        )


def train_pq(
    vectors: np.ndarray,
    m: int = 8,
    *,
    nbits: int = 8,
    iters: int = 8,
    metric: str = "l2",
    seed: int = 0,
    sample_cap: int = 65_536,
) -> PQCodebook:
    n, d = vectors.shape
    assert d % m == 0, f"d={d} not divisible by M={m}"
    k = 1 << nbits
    dsub = d // m
    rng = np.random.default_rng(seed)
    if n > sample_cap:
        vectors = vectors[rng.choice(n, sample_cap, replace=False)]
    cents = np.empty((m, k, dsub), np.float32)
    for j in range(m):
        sub = np.ascontiguousarray(vectors[:, j * dsub : (j + 1) * dsub])
        cents[j] = km.train_kmeans(sub, k, iters=iters, metric="l2", seed=seed + j)
    return PQCodebook(centroids=cents, metric=metric)


def encode_pq(cb: PQCodebook, vectors: np.ndarray) -> np.ndarray:
    """uint8 codes [n, M]."""
    n, d = vectors.shape
    if d != cb.d:
        raise ValueError(
            f"PQ codebook shape mismatch: codebook encodes d={cb.d} "
            f"(m={cb.m} subspaces × dsub={cb.dsub}), vectors have d={d}"
        )
    dsub = cb.dsub
    codes = np.empty((n, cb.m), np.uint8)
    for j in range(cb.m):
        sub = np.ascontiguousarray(vectors[:, j * dsub : (j + 1) * dsub])
        codes[:, j] = km.assign_kmeans(sub, cb.centroids[j], metric="l2").astype(np.uint8)
    return codes


def decode_pq(cb: PQCodebook, codes: np.ndarray) -> np.ndarray:
    """Reconstruction (for re-ranking / tests)."""
    n = codes.shape[0]
    out = np.empty((n, cb.m * cb.dsub), np.float32)
    for j in range(cb.m):
        out[:, j * cb.dsub : (j + 1) * cb.dsub] = cb.centroids[j][codes[:, j]]
    return out


@functools.partial(jax.jit, static_argnames=("metric", "m_pad"))
def adc_tables_device(
    centroids: jax.Array,  # f32 [M, 256, dsub] — the codebook
    queries: jax.Array,  # f32 [U, M·dsub]
    *,
    metric: str,
    m_pad: int,
) -> jax.Array:
    """Per-query partial-score LUTs, f32 [U, m_pad, 256], higher = better,
    in one device program; subspace rows past M are zero (the kernels'
    aligned table layout, ``kernels.pq_scan.subspace_pad``).

    l2: -‖q_sub − c‖² summed over subspaces == -‖q − decode(code)‖².
    ip: q_sub · c summed == q · decode(code).
    """
    m, nbook, dsub = centroids.shape
    qs = queries.reshape(queries.shape[0], m, dsub)
    ip = jnp.einsum("umd,mcd->umc", qs, centroids, precision=HIGHEST)
    if metric == "l2":
        luts = (
            2 * ip
            - jnp.sum(qs * qs, axis=2)[:, :, None]
            - jnp.sum(centroids * centroids, axis=2)[None, :, :]
        )
    else:
        luts = ip
    return jnp.pad(luts, ((0, 0), (0, m_pad - m), (0, 0)))


def adc_tables(cb: PQCodebook, queries: np.ndarray) -> np.ndarray:
    """``adc_tables_device`` for host callers: f32 [nq, M, 256]."""
    luts = adc_tables_device(
        jnp.asarray(cb.centroids, jnp.float32),
        jnp.asarray(queries, jnp.float32),
        metric=cb.metric,
        m_pad=cb.m,
    )
    return np.asarray(luts)


def adc_scan_ref(
    luts: jax.Array,  # f32 [nq, M, 256]
    codes: jax.Array,  # uint8/int32 [nv, M]
    valid: jax.Array,  # bool [nv]
    k: int,
) -> Tuple[jax.Array, jax.Array]:
    """Oracle ADC scan: scores [nq, nv] = Σ_m lut[q, m, code[v, m]] → top-k."""
    from ..kernels.ref import NEG_INF

    c = codes.astype(jnp.int32)  # [nv, M]
    # gather per subspace then sum: [nq, nv]
    scores = jnp.zeros((luts.shape[0], codes.shape[0]), jnp.float32)
    for j in range(luts.shape[1]):
        scores = scores + luts[:, j, :][:, c[:, j]]
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    top, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(top <= NEG_INF / 2, -1, idx).astype(jnp.int32)
    return top, idx


@dataclasses.dataclass
class PQIndex:
    """Flat PQ index with ADC scan + optional exact re-ranking."""

    cb: PQCodebook
    codes: np.ndarray  # [n, M] uint8
    vectors: Optional[np.ndarray] = None  # kept for re-ranking if provided

    @staticmethod
    def build(vectors: np.ndarray, m: int = 8, *, metric: str = "l2", keep_vectors: bool = True, seed: int = 0) -> "PQIndex":
        cb = train_pq(vectors, m, metric=metric, seed=seed)
        codes = encode_pq(cb, vectors)
        return PQIndex(cb=cb, codes=codes, vectors=vectors if keep_vectors else None)

    def search(
        self,
        queries: np.ndarray,
        k: int,
        *,
        bitmap: Optional[np.ndarray] = None,
        rerank: int = 0,  # fetch rerank·k ADC candidates, re-score exactly
    ) -> Tuple[np.ndarray, np.ndarray]:
        n = self.codes.shape[0]
        valid = jnp.asarray(bitmap if bitmap is not None else np.ones(n, bool))
        luts = jnp.asarray(adc_tables(self.cb, queries))
        kk = k * max(1, rerank)
        s, i = adc_scan_ref(luts, jnp.asarray(self.codes), valid, min(kk, n))
        s, i = np.asarray(s), np.asarray(i)
        if rerank <= 1 or self.vectors is None:
            return s[:, :k], i[:, :k].astype(np.int64)
        out_s = np.full((queries.shape[0], k), -np.inf, np.float32)
        out_i = np.full((queries.shape[0], k), -1, np.int64)
        for r in range(queries.shape[0]):
            cand = i[r][i[r] >= 0]
            if len(cand) == 0:
                continue
            vc = self.vectors[cand]
            ip = vc @ queries[r]
            if self.cb.metric == "l2":
                sc = 2 * ip - (vc * vc).sum(1) - queries[r] @ queries[r]
            else:
                sc = ip
            top = np.argsort(-sc, kind="stable")[:k]
            out_s[r, : len(top)] = sc[top]
            out_i[r, : len(top)] = cand[top]
        return out_s, out_i

    @property
    def compression_ratio(self) -> float:
        d = self.cb.m * self.cb.dsub
        return (d * 4) / self.cb.m
