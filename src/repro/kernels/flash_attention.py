"""Pallas TPU flash attention (fwd): online softmax, causal + sliding window,

GQA-aware. The on-hardware hot path for the 32k prefill cells; the pure-JAX
chunked implementation (models/attention.py) is the oracle and the dry-run
path. Grid (batch, q_heads, q_blocks, kv_blocks), kv innermost so the
(m, l, acc) running state lives in VMEM across a query block's sweep.

Block shapes: q (1, bq, 1, dh), kv (1, bk, 1, dh) — dh is kept whole (128 or
less → lane-aligned); bq/bk default 128/256 keeping the MXU busy and the
VMEM footprint ≈ bq·dh + 2·bk·dh + bq·bk floats ≈ 400 KB at defaults.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .platform import default_interpret

NEG_INF = float(-3.0e38)


def _flash_kernel(
    q_ref,  # [1, bq, 1, dh]
    k_ref,  # [1, bk, 1, dh]
    v_ref,  # [1, bk, 1, dh]
    o_ref,  # [1, bq, 1, dh]
    m_ref,  # scratch [bq, 1]
    l_ref,  # scratch [bq, 1]
    acc_ref,  # scratch [bq, dh]
    *,
    bq: int,
    bk: int,
    nk: int,
    seq_k: int,
    causal: bool,
    window: int,
    scale: float,
):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # [bq, dh]
    k = k_ref[0, :, 0, :].astype(jnp.float32)  # [bk, dh]
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k
    if causal:
        mask &= k_pos <= q_pos
    if window > 0:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[:, 0] = l_ref[:, 0] * alpha + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[:, 0] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, S, Hq, dh]
    k: jax.Array,  # [B, T, Hkv, dh]
    v: jax.Array,  # [B, T, Hkv, dh]
    *,
    causal: bool = True,
    window: int = 0,  # 0 = global
    bq: int = 128,
    bk: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    b, s, hq, dh = q.shape
    t, hkv = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = dh**-0.5
    bq = min(bq, max(8, s))
    bk = min(bk, max(8, t))
    sp = ((s + bq - 1) // bq) * bq
    tp = ((t + bk - 1) // bk) * bk
    qp = jnp.pad(q, ((0, 0), (0, sp - s), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
    nq, nk = sp // bq, tp // bk

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=nk, seq_k=t,
        causal=causal, window=window, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, dh), lambda bi, h, qi, ki: (bi, qi, h, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, h, qi, ki: (bi, ki, h // group, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda bi, h, qi, ki: (bi, ki, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, dh), lambda bi, h, qi, ki: (bi, qi, h, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sp, hq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        interpret=default_interpret(interpret),
    )(qp, kp, vp)
    return out[:, :s]
