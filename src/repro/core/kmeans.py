"""Batched k-means (Lloyd's) in JAX — used for IVF training and for the

centroid-assignment attribute of Section 4.1.1.

Matches FAISS's IVF training defaults in spirit: k = sqrt(n) by default,
a bounded number of Lloyd's iterations over a training sample, empty-cluster
re-seeding. Assignment (the hot part) is a tiled matmul; it reuses the same
masked-distance primitive as search (kernels/ops.py) so the Pallas path is
exercised by k-means too.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops


@functools.partial(jax.jit, static_argnames=("metric",))
def _assign(
    vectors: jax.Array, centroids: jax.Array, k: jax.Array, metric: str
) -> jax.Array:
    """Nearest-centroid assignment among the first ``k`` rows of ``centroids``
    (the rest are padding and never win). vectors [n,d], centroids [kp,d] ->
    int32[n]."""
    scores = _scores_of_first(vectors, centroids, k, metric)  # [n, kp] best=max
    return jnp.argmax(scores, axis=1).astype(jnp.int32)


def _scores_of_first(q, centroids, k, metric):
    scores = kops.pairwise_scores(q, centroids, metric=metric)
    return jnp.where(jnp.arange(centroids.shape[0]) < k, scores, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("k",))
def _update(vectors: jax.Array, assign: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Mean of each cluster; returns (centroids [k,d], counts [k])."""
    one_hot = jax.nn.one_hot(assign, k, dtype=vectors.dtype)  # [n, k]
    counts = one_hot.sum(axis=0)  # [k]
    sums = one_hot.T @ vectors  # [k, d]
    return sums / jnp.maximum(counts, 1.0)[:, None], counts


@functools.partial(jax.jit, static_argnames=("m", "metric"))
def _topm(q: jax.Array, centroids: jax.Array, k: jax.Array, m: int, metric: str) -> jax.Array:
    return jax.lax.top_k(_scores_of_first(q, centroids, k, metric), m)[1]


def _pow2(n: int, lo: int) -> int:
    return max(lo, 1 << (n - 1).bit_length())


def _pow2_pad(x: np.ndarray, lo: int = 256) -> np.ndarray:
    """Pad rows to the next power of two (repeating rows) so the jit'd

    k-means steps specialize on O(log n) shapes instead of one per
    partition — index build time is dominated by compiles otherwise."""
    n = x.shape[0]
    target = _pow2(n, lo)
    if target == n:
        return x
    reps = np.resize(np.arange(n), target - n)
    return np.concatenate([x, x[reps]], axis=0)


def _pad_centroids(centroids: np.ndarray) -> jax.Array:
    """Centroids zero-padded to a power-of-two count: with ``k`` passed as
    data, a program serves every partition whose count rounds to the same
    size (an index build otherwise compiles its k-means and probe programs
    once per partition)."""
    k, d = centroids.shape
    out = np.zeros((_pow2(k, 8), d), np.float32)
    out[:k] = centroids
    return jnp.asarray(out)


def train_kmeans(
    vectors: np.ndarray,
    k: int,
    *,
    iters: int = 10,
    metric: str = "l2",
    seed: int = 0,
    sample_cap: int = 262_144,
) -> np.ndarray:
    """Train k centroids; returns float32 [k, d]."""
    n, d = vectors.shape
    k = int(min(k, n))
    rng = np.random.default_rng(seed)
    if n > sample_cap:
        idx = rng.choice(n, size=sample_cap, replace=False)
        x = vectors[idx]
    else:
        x = vectors
    # padding with duplicate rows does not change cluster means materially
    # and keeps the jit cache small across many differently-sized partitions
    x = _pow2_pad(np.asarray(x, dtype=np.float32))
    # k-means++-lite init: random distinct points.
    init_idx = rng.choice(x.shape[0], size=k, replace=False)
    centroids = _pad_centroids(x[init_idx])
    kp = centroids.shape[0]
    x_dev = jnp.asarray(x)
    for _ in range(iters):
        assign = _assign(x_dev, centroids, k, metric)
        centroids, counts = _update(x_dev, assign, kp)
        # Re-seed empty clusters from random points (host-side; rare).
        empty = np.asarray(counts)[:k] == 0
        if empty.any():
            c = np.array(centroids)  # writable copy
            c[:k][empty] = x[rng.choice(x.shape[0], size=int(empty.sum()), replace=False)]
            centroids = jnp.asarray(c)
    return np.asarray(centroids, dtype=np.float32)[:k]


def assign_kmeans(vectors: np.ndarray, centroids: np.ndarray, *, metric: str = "l2", chunk: int = 65_536) -> np.ndarray:
    """Nearest-centroid id per vector (chunked to bound device memory;

    the tail chunk is pow2-padded so jit sees O(log n) shapes)."""
    n = vectors.shape[0]
    out = np.empty(n, dtype=np.int32)
    cents = _pad_centroids(np.asarray(centroids, dtype=np.float32))
    k = centroids.shape[0]
    for s in range(0, n, chunk):
        e = min(s + chunk, n)
        block = _pow2_pad(np.asarray(vectors[s:e], dtype=np.float32), lo=256)
        out[s:e] = np.asarray(_assign(jnp.asarray(block), cents, k, metric))[: e - s]
    return out


def topm_centroids(query_vectors: np.ndarray, centroids: np.ndarray, m: int, *, metric: str = "l2") -> np.ndarray:
    """m nearest centroids per query — int32 [nq, m] (Section 4.1.1 / Alg.3 line 6)."""
    nq, k = query_vectors.shape[0], centroids.shape[0]
    m = int(min(m, k))
    if nq == 0:
        return np.zeros((0, m), np.int32)
    q = _pow2_pad(np.asarray(query_vectors, dtype=np.float32), lo=8)
    idx = _topm(jnp.asarray(q), _pad_centroids(np.asarray(centroids, dtype=np.float32)), k, m, metric)
    return np.asarray(idx, dtype=np.int32)[:nq]
