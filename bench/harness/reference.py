"""The plain reference: exact filtered top-k, written without the program.

Every query is scored against every row (blocks of rows on the device, in
one compiled loop); rows that fail the query's template score -inf; the k
best survive. Scores follow the program's convention, best first: the inner
product for ``ip``, minus the squared distance for ``l2``.

``precision="f32"`` contracts at ``HIGHEST`` (a TPU otherwise rounds f32
operands to bf16). ``precision="bf16"`` is the control: the same reference
with queries and rows rounded to bf16 and one bf16 pass, the lower precision
a later change could be tempted to take.

``exact_scores`` recomputes the score of given (query, row) pairs in float64
on the host: the yardstick a returned score is held to.
"""
from __future__ import annotations

import functools

import numpy as np

BLOCK_ROWS = 32_768
QUERY_CHUNK = 512


@functools.lru_cache(maxsize=None)
def _block_topk(n_blocks: int, k: int, metric: str, precision: str):
    import jax
    import jax.numpy as jnp

    lax_prec = jax.lax.Precision.HIGHEST if precision == "f32" else jax.lax.Precision.DEFAULT
    dtype = jnp.float32 if precision == "f32" else jnp.bfloat16

    @jax.jit
    def run(q, q_tmpl, v, vnorm, masks):
        qc = q.shape[0]
        q = q.astype(dtype)
        qn = jnp.sum(jnp.square(q.astype(jnp.float32)), axis=1, keepdims=True)

        def body(b, carry):
            best_s, best_i = carry
            start = b * BLOCK_ROWS
            vb = jax.lax.dynamic_slice_in_dim(v, start, BLOCK_ROWS, axis=0)
            s = jax.lax.dot_general(
                q, vb, (((1,), (1,)), ((), ())), precision=lax_prec,
                preferred_element_type=jnp.float32,
            )
            if metric == "l2":
                nb = jax.lax.dynamic_slice_in_dim(vnorm, start, BLOCK_ROWS)
                s = 2.0 * s - qn - nb[None, :]
            mb = jax.lax.dynamic_slice_in_dim(masks, start, BLOCK_ROWS, axis=1)[q_tmpl]
            s = jnp.where(mb, s, -jnp.inf)
            blk_s, blk_pos = jax.lax.top_k(s, k)
            cat_s = jnp.concatenate([best_s, blk_s], axis=1)
            cat_i = jnp.concatenate([best_i, start + blk_pos.astype(jnp.int32)], axis=1)
            top_s, pos = jax.lax.top_k(cat_s, k)
            return top_s, jnp.take_along_axis(cat_i, pos, axis=1)

        init = (jnp.full((qc, k), -jnp.inf, jnp.float32), jnp.full((qc, k), -1, jnp.int32))
        return jax.lax.fori_loop(0, n_blocks, body, init)

    return run


def exact_topk(
    vectors: np.ndarray,  # f32 [n, d]
    metric: str,
    queries: np.ndarray,  # f32 [m, d]
    masks: np.ndarray,  # bool [T, n]: rows each template admits
    template_of: np.ndarray,  # [m]
    k: int,
    *,
    precision: str = "f32",
):
    """(scores f32 [m, k], ids i64 [m, k]) of the exact filtered top-k; an
    id of -1 (score -inf) where fewer than k rows pass."""
    import jax.numpy as jnp

    n, d = vectors.shape
    m = queries.shape[0]
    n_blocks = -(-n // BLOCK_ROWS)
    pad = n_blocks * BLOCK_ROWS - n
    dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
    v = jnp.pad(jnp.asarray(vectors), ((0, pad), (0, 0))).astype(dtype)
    vnorm = jnp.sum(jnp.square(v.astype(jnp.float32)), axis=1)
    dmask = jnp.pad(jnp.asarray(masks, dtype=bool), ((0, 0), (0, pad)))
    run = _block_topk(n_blocks, int(k), metric, precision)
    out_s = np.full((m, k), -np.inf, np.float32)
    out_i = np.full((m, k), -1, np.int64)
    for c0 in range(0, m, QUERY_CHUNK):
        c1 = min(m, c0 + QUERY_CHUNK)
        q = np.zeros((QUERY_CHUNK, d), np.float32)
        t = np.zeros(QUERY_CHUNK, np.int32)
        q[: c1 - c0] = queries[c0:c1]
        t[: c1 - c0] = template_of[c0:c1]
        s, i = run(jnp.asarray(q), jnp.asarray(t), v, vnorm, dmask)
        s, i = np.asarray(s)[: c1 - c0], np.asarray(i)[: c1 - c0]
        out_s[c0:c1] = s
        out_i[c0:c1] = np.where(np.isfinite(s), i, -1)
    return out_s, out_i


def exact_scores(vectors: np.ndarray, metric: str, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 [m, k] score of each (query, returned row); nan where id < 0."""
    ok = ids >= 0
    rows = vectors[np.where(ok, ids, 0)].astype(np.float64)  # [m, k, d]
    q = queries.astype(np.float64)[:, None, :]
    if metric == "ip":
        s = np.sum(q * rows, axis=2)
    else:
        s = -np.sum(np.square(q - rows), axis=2)
    return np.where(ok, s, np.nan)


def score_scale(vectors: np.ndarray, metric: str, queries: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 [m, k]: the size a score's rounding error is relative to —
    |q||v| for ``ip``, |q|^2 + |v|^2 for ``l2``."""
    rows = vectors[np.maximum(ids, 0)].astype(np.float64)
    qn = np.sum(np.square(queries.astype(np.float64)), axis=1)[:, None]
    vn = np.sum(np.square(rows), axis=2)
    if metric == "ip":
        return np.sqrt(qn * vn)
    return qn + vn
