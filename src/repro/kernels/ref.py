"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its reference here; tests sweep shapes and
dtypes asserting allclose between the kernel (interpret mode on CPU) and
these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = float(-3.4e38)


def normalize_merge_sentinels(
    scores: jax.Array, idx: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Canonical absent-result encoding shared by every merge path.

    Merge inputs carry two sentinel flavors — ``-inf`` (allocation padding)
    and the kernels' finite ``NEG_INF`` with idx -1 — and a top-k over them
    can pair a finite sentinel score with a real-looking index or vice versa.
    This maps every absent entry to exactly (-inf, -1): an entry is absent
    iff its idx is negative or its score is non-finite.
    """
    scores = jnp.where(idx < 0, -jnp.inf, scores)
    idx = jnp.where(jnp.isfinite(scores), idx, -1)
    return scores, idx


def segmented_merge_topk_ref(
    flat_s: jax.Array,  # f32 [C, kk] — candidate rows, any per-segment count
    flat_i: jax.Array,  # int [C, kk] — candidate ids (-1 = absent)
    seg_of: jax.Array,  # i32 [C] — owning segment per row, ASCENDING; >= n_segments = drop
    n_segments: int,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Ragged per-segment top-k oracle: CSR-style rows -> [n_segments, k].

    The segmented counterpart of ``merge_topk``: instead of a dense
    [m, n_slots, kk] tensor padded to the widest query, candidates arrive as
    a flat [C, kk] buffer whose rows belong to segments (queries) of varying
    width. One stable sort by (segment, -score) ranks every candidate inside
    its segment; rank < k survives. Stability preserves the original
    candidate order among EXACTLY equal scores, which is ``lax.top_k``'s
    smallest-index-first tie rule — so results are bit-identical to the
    dense merge over the same per-segment candidate sequence (the parity
    suite asserts ids AND scores). Rows whose ``seg_of`` is ``n_segments``
    or above are padding and are dropped.
    """
    C, kk = flat_s.shape
    if n_segments == 0:
        return (
            jnp.zeros((0, k), jnp.float32),
            jnp.zeros((0, k), flat_i.dtype),
        )
    n = C * kk
    s = flat_s.reshape(n)
    i = flat_i.reshape(n)
    seg = jnp.repeat(seg_of.astype(jnp.int32), kk)
    order = jnp.lexsort((-s, seg))  # stable: ties keep candidate order
    s_s, i_s, seg_s = s[order], i[order], seg[order]
    starts = jnp.searchsorted(seg_s, jnp.arange(n_segments, dtype=seg_s.dtype))
    pos = jnp.arange(n) - starts[jnp.clip(seg_s, 0, max(n_segments - 1, 0))]
    keep = (seg_s < n_segments) & (pos < k)
    rows = jnp.where(keep, seg_s, n_segments)  # out-of-range row -> dropped
    cols = jnp.where(keep, pos, 0)
    out_s = (
        jnp.full((n_segments, k), -jnp.inf, jnp.float32)
        .at[rows, cols].set(s_s.astype(jnp.float32), mode="drop")
    )
    out_i = (
        jnp.full((n_segments, k), -1, flat_i.dtype)
        .at[rows, cols].set(i_s, mode="drop")
    )
    return normalize_merge_sentinels(out_s, out_i)


def pairwise_scores_ref(q: jax.Array, v: jax.Array, metric: str = "ip") -> jax.Array:
    """Similarity scores, best = max. q [nq,d], v [nv,d] -> f32 [nq,nv].

    ip: q·v          l2: -||q - v||²  (negated so max = nearest)
    """
    q = q.astype(jnp.float32)
    v = v.astype(jnp.float32)
    # full f32 on a TPU's MXU too (its default rounds f32 operands to bf16),
    # matching the Pallas kernels' contraction
    ip = jnp.matmul(q, v.T, precision=jax.lax.Precision.HIGHEST)
    if metric == "ip":
        return ip
    if metric == "l2":
        qn = jnp.sum(q * q, axis=1, keepdims=True)  # [nq,1]
        vn = jnp.sum(v * v, axis=1)[None, :]  # [1,nv]
        return 2.0 * ip - qn - vn
    raise ValueError(metric)


def masked_topk_ref(
    q: jax.Array,
    v: jax.Array,
    valid: jax.Array,
    k: int,
    metric: str = "ip",
) -> tuple[jax.Array, jax.Array]:
    """Top-k masked similarity search oracle.

    q [nq,d], v [nv,d], valid bool [nv] (the pushdown bitmap of Section 4.2).
    Returns (scores f32 [nq,k] best-first, idx int32 [nq,k]); masked-out or
    absent entries have score -inf and idx -1.
    """
    scores = pairwise_scores_ref(q, v, metric)
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    top, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(top <= NEG_INF / 2, -1, idx).astype(jnp.int32)
    return top, idx


def adc_sum(term, m: int):
    """Σ_j term(j) over the M subspaces, added one subspace at a time in
    order. Each term is an exact table entry, so every ADC path that adds in
    this order — these references and the Pallas kernels — scores bit for
    bit alike, on any backend."""
    scores = term(0)
    for j in range(1, m):
        scores = scores + term(j)
    return scores


def adc_topk_ref(
    luts: jax.Array,  # f32 [nq, Mt >= M, 256] — per-query ADC lookup tables
    codes: jax.Array,  # uint8/int32 [nv, M]
    valid: jax.Array,  # bool [nv]
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """ADC scan + top-k oracle (the compressed counterpart of masked_topk_ref).

    score[q, v] = Σ_m lut[q, m, code[v, m]] — higher is better (adc_tables
    negates l2). Returns (scores f32 [nq, k] best-first, idx int32 [nq, k]);
    masked-out or absent entries are (-inf-ish, -1).
    """
    c = codes.astype(jnp.int32)  # [nv, M]; subspace rows of luts past M are padding
    scores = adc_sum(lambda j: luts[:, j, c[:, j]], c.shape[1])  # [nq, nv]
    scores = jnp.where(valid[None, :], scores, NEG_INF)
    top, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(top <= NEG_INF / 2, -1, idx).astype(jnp.int32)
    return top, idx


def workunit_pq_topk_ref(
    luts: jax.Array,  # f32 [W, TQ, Mt >= M, 256]
    codes: jax.Array,  # uint8/int32 [W, TV, M]
    valid: jax.Array,  # bool [W, TV]
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Batched work-unit ADC oracle: adc_topk_ref vmapped over the unit dim."""
    return jax.vmap(lambda l, c, v: adc_topk_ref(l, c, v, k))(luts, codes, valid)


def workunit_pq_topk_resident_ref(
    table: jax.Array,  # f32 [U, Mt >= M, 256] — resident ADC tables
    lut_idx: jax.Array,  # int [W, TQ] — table row per unit slot
    codes: jax.Array,  # uint8/int32 [W, TV, M]
    valid: jax.Array,  # bool [W, TV]
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """``workunit_pq_topk_ref`` over ``table[lut_idx]``, without that
    [W, TQ, M, 256] operand: each score gathers its M table entries straight
    from the resident table (the expansion is W·TQ·8 KB — 8 GB at 16k units
    of 64 slots, more than a chip holds). Same sum, same scores."""
    c = codes.astype(jnp.int32)
    rows = lut_idx[:, :, None]  # [W, TQ, 1]
    # one [W, TQ, TV] term per subspace: an [..., M] minor axis would pad M
    # to the TPU's 128 lanes (16x at M = 8)
    scores = adc_sum(lambda j: table[rows, j, c[:, None, :, j]], c.shape[-1])
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)  # [W, TQ, TV]
    top, idx = jax.lax.top_k(scores, k)
    idx = jnp.where(top <= NEG_INF / 2, -1, idx).astype(jnp.int32)
    return top, idx


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Reference attention. q [B,Hq,S,Dh], k/v [B,Hkv,T,Dh] (GQA: Hq % Hkv == 0).

    window (if set) = sliding-window size W: position i attends to
    (i - W, i]  (causal local attention, gemma3-style).
    """
    b, hq, s, dh = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / (dh**0.5)
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    kf = jnp.repeat(kf, group, axis=1)
    vf = jnp.repeat(vf, group, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf)
    t = kf.shape[2]
    qpos = jnp.arange(s)[:, None] + (t - s)  # right-aligned (decode: s << t)
    kpos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), dtype=bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vf)
    return out.astype(q.dtype)
