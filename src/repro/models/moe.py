"""Mixture-of-Experts layer: shared + routed experts, top-k routing,

capacity-based static dispatch (sort-free scatter), expert parallelism.

Dispatch is the standard static-shape formulation: flatten tokens, rank each
(token, slot) pair within its expert via a cumulative count, drop past
capacity, scatter into an [E, C, D] buffer, run all expert FFNs as one
batched einsum (sharded over the ``expert``/model axis), and combine with
router gates. Aux outputs: load-balancing loss (Switch-style), router z-loss,
dropped-token fraction (tests assert it stays sane at even load).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import shard_activation
from .layers import _dense_init, init_mlp, mlp


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int  # per-expert FFN width (fine-grained experts are narrow)
    n_shared_experts: int = 0
    d_ff_shared: Optional[int] = None  # defaults to d_ff_expert * n_shared
    capacity_factor: float = 1.25
    # serving path: capacity dropping would make decode outputs depend on the
    # batch composition — use a near-dropless factor there instead.
    serve_capacity_factor: float = 8.0
    router_noise: float = 0.0


def init_moe(key, d_model: int, cfg: MoEConfig) -> Dict[str, Any]:
    kr, ke, ks = jax.random.split(key, 3)
    e, f = cfg.n_experts, cfg.d_ff_expert
    k1, k2, k3 = jax.random.split(ke, 3)
    p: Dict[str, Any] = {
        "router": _dense_init(kr, (d_model, e)),
        "experts": {
            "w_up": _dense_init(k1, (e, d_model, f)),
            "w_gate": _dense_init(k2, (e, d_model, f)),
            "w_down": _dense_init(k3, (e, f, d_model)),
        },
    }
    if cfg.n_shared_experts > 0:
        d_sh = cfg.d_ff_shared or cfg.d_ff_expert * cfg.n_shared_experts
        p["shared"] = init_mlp(ks, d_model, d_sh)
    return p


def moe_layer(
    p: Dict[str, Any],
    x: jax.Array,  # [B, S, D]
    cfg: MoEConfig,
    serving: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Entry point: uses the explicit expert-parallel shard_map path when a

    mesh is active (training on the production mesh), else the dense
    single-device formulation below."""
    from ..distributed.sharding import OPT, get_rules

    rules = get_rules()
    if (
        not serving
        and OPT["moe_ep_data"]
        and rules is not None
        and rules.mesh is not None
        and "data" in rules.mesh.axis_names
        and cfg.n_experts % rules.mesh.shape["data"] == 0
    ):
        return moe_layer_ep(p, x, cfg, rules)
    return moe_layer_dense(p, x, cfg, serving)


def moe_layer_dense(
    p: Dict[str, Any],
    x: jax.Array,  # [B, S, D]
    cfg: MoEConfig,
    serving: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32)) @ p["router"].astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)  # [T, k]
    gates = gates / jnp.maximum(gates.sum(axis=-1, keepdims=True), 1e-9)

    # --- capacity-based dispatch --------------------------------------------
    cf = cfg.serve_capacity_factor if serving else cfg.capacity_factor
    cap = int(min(t, max(1, (t * k * cf) // e)))
    flat_e = eidx.reshape(-1)  # [T*k]
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gates.reshape(-1)
    # rank of each pair within its expert (stable by token order)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)  # [T*k, E]
    pos = (jnp.cumsum(onehot, axis=0) - 1)  # exclusive rank per expert
    rank = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]  # [T*k]
    keep = rank < cap
    dropped_frac = 1.0 - keep.mean()

    buf = jnp.zeros((e, cap, d), x.dtype)
    safe_rank = jnp.where(keep, rank, cap - 1)
    buf = buf.at[flat_e, safe_rank].add(
        jnp.where(keep[:, None], xt[flat_t], 0).astype(x.dtype)
    )
    buf = shard_activation(buf, "expert_buf")

    # --- expert FFNs as one batched einsum (EP over "expert") ----------------
    w_up = p["experts"]["w_up"].astype(x.dtype)
    w_gate = p["experts"]["w_gate"].astype(x.dtype)
    w_down = p["experts"]["w_down"].astype(x.dtype)
    h = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(h) * u
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)  # [E, C, D]

    # --- combine --------------------------------------------------------------
    gathered = out_buf[flat_e, safe_rank]  # [T*k, D]
    contrib = jnp.where(keep[:, None], gathered * flat_g[:, None].astype(x.dtype), 0)
    yt = jnp.zeros((t, d), x.dtype).at[flat_t].add(contrib)
    y = yt.reshape(b, s, d)

    if "shared" in p:
        y = y + mlp(p["shared"], x)

    # --- aux losses -----------------------------------------------------------
    # Switch load-balance: E * Σ_e (frac tokens to e) * (mean router prob e)
    me = jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), axis=0)
    pe = probs.mean(axis=0)
    lb_loss = e * jnp.sum(me * pe)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"lb_loss": lb_loss, "z_loss": z_loss, "dropped_frac": dropped_frac}
    return y, aux


# ---------------------------------------------------------------------------
# Expert parallelism via shard_map (the production path)
# ---------------------------------------------------------------------------
#
# Naive pjit lowering of the scatter-based dispatch degenerates into
# all-reduces of the FULL flat token tensor per layer (measured 1.9 GB ×
# ~8 ops × layers × microbatches on the 1T config — §Perf log). The explicit
# formulation below is the standard production schedule:
#
#   * experts sharded over "data" (E_loc = E / dp per shard), expert-FFN width
#     over "model" (TP);
#   * each data shard routes its tokens, packs them into per-(destination
#     shard, local expert) capacity slots, and exchanges ONE bf16 all_to_all;
#   * received tokens are already grouped per local expert → batched FFN
#     einsums; the down-projection partial sums psum over "model";
#   * a reverse all_to_all returns expert outputs to the token's home shard,
#     where gates combine them.
#
# Communication per device per layer ≈ 2 · T_loc · k · D bytes (bf16), vs the
# token-tensor all-reduces the automatic partitioner produced.


def _ep_local(xt, router, w_gate, w_up, w_down, cfg: MoEConfig, dp: int, cap_e: int):
    """Per-device body under shard_map. xt [T_loc, D] (this shard's tokens);

    experts local [E_loc, D, F_loc]. Returns (yt [T_loc, D], aux)."""
    t_loc, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // dp

    logits = xt.astype(jnp.float32) @ router.astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    flat_e = eidx.reshape(-1)  # [T*k] global expert ids
    flat_t = jnp.repeat(jnp.arange(t_loc), k)
    flat_g = gates.reshape(-1)
    # rank of each pair within its (global) expert
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0) - 1, flat_e[:, None], 1)[:, 0]
    keep = rank < cap_e
    dst = flat_e // e_loc  # destination data shard
    loc = flat_e % e_loc  # local expert id at the destination
    safe_rank = jnp.where(keep, rank, cap_e - 1)

    # pack into per-(dst, local expert, slot) send buffer
    sbuf = jnp.zeros((dp, e_loc, cap_e, d), xt.dtype)
    sbuf = sbuf.at[dst, loc, safe_rank].add(jnp.where(keep[:, None], xt[flat_t], 0))
    svalid = jnp.zeros((dp, e_loc, cap_e), jnp.bool_).at[dst, loc, safe_rank].max(keep)

    # exchange: rbuf[src] = what src sent to us
    rbuf = jax.lax.all_to_all(sbuf, "data", split_axis=0, concat_axis=0, tiled=False)
    rvalid = jax.lax.all_to_all(svalid, "data", split_axis=0, concat_axis=0, tiled=False)
    buf = jnp.moveaxis(rbuf, 0, 1).reshape(e_loc, dp * cap_e, d)  # [E_loc, C, D]
    bvalid = jnp.moveaxis(rvalid, 0, 1).reshape(e_loc, dp * cap_e)

    # local expert FFNs (F sharded over "model": psum the down partials)
    h = jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(buf.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, w_up.astype(buf.dtype))
    h = jax.nn.silu(h) * u
    out = jnp.einsum("ecf,efd->ecd", h, w_down.astype(buf.dtype))
    out = jax.lax.psum(out, "model")
    out = jnp.where(bvalid[..., None], out, 0)

    # return trip
    out_r = jnp.moveaxis(out.reshape(e_loc, dp, cap_e, d), 1, 0)  # [dst_src, E_loc, cap, D]
    back = jax.lax.all_to_all(out_r, "data", split_axis=0, concat_axis=0, tiled=False)
    # back[dst, loc, rank] = expert output for our pair routed to (dst, loc)
    fetched = back[dst, loc, safe_rank]  # [T*k, D]
    contrib = jnp.where(keep[:, None], fetched * flat_g[:, None].astype(xt.dtype), 0)
    yt = jnp.zeros((t_loc, d), xt.dtype).at[flat_t].add(contrib)

    # global routing statistics (pmean BEFORE the product so the loss equals
    # the dense single-device formulation exactly)
    me = jax.lax.pmean(jnp.mean(jax.nn.one_hot(eidx[:, 0], e, dtype=jnp.float32), axis=0), "data")
    pe = jax.lax.pmean(probs.mean(axis=0), "data")
    lb_loss = e * jnp.sum(me * pe)
    z_loss = jax.lax.pmean(jnp.mean(jax.nn.logsumexp(logits, -1) ** 2), "data")
    dropped = jax.lax.pmean(1.0 - keep.mean(), "data")
    return yt, lb_loss, z_loss, dropped


def moe_layer_ep(p, x, cfg: MoEConfig, rules) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    from jax.sharding import PartitionSpec as P

    mesh = rules.mesh
    dp = mesh.shape["data"]  # expert shards live on the data axis
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bp = 1
    for a in batch_axes:
        bp *= mesh.shape[a]
    b, s, d = x.shape
    if b % bp != 0:
        # batch not shardable over the batch axes (e.g. batch=1) — dense path
        return moe_layer_dense(p, x, cfg)
    t_loc = (b // bp) * s  # tokens per device; experts replicate across pods
    cap_e = int(max(1, (t_loc * cfg.top_k * cfg.capacity_factor) // cfg.n_experts))

    # tokens flattened per shard; weights: E over data, F over model
    fn = jax.shard_map(
        lambda xt, r, wg, wu, wd: _ep_local(xt, r, wg, wu, wd, cfg, dp, cap_e),
        mesh=mesh,
        in_specs=(
            P(batch_axes, None),
            P(),
            P("data", None, "model"),
            P("data", None, "model"),
            P("data", "model", None),
        ),
        out_specs=(P(batch_axes, None), P(), P(), P()),
        check_vma=False,
    )
    xt = x.reshape(b * s, d)
    yt, lb, zl, dr = fn(
        xt, p["router"], p["experts"]["w_gate"], p["experts"]["w_up"], p["experts"]["w_down"]
    )
    y = yt.reshape(b, s, d)
    if "shared" in p:
        y = y + mlp(p["shared"], x)
    return y, {"lb_loss": lb, "z_loss": zl, "dropped_frac": dr}
