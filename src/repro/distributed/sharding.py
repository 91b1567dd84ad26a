"""Logical-axis sharding rules → mesh shardings (DP / FSDP / TP / EP / SP).

Model code annotates activations with *logical* names via
``shard_activation(x, kind)`` and parameters are matched by path patterns.
The mapping from logical axes to mesh axes is a per-run table, so scaling
from the 8-device test mesh to the 512-chip multi-pod mesh only changes the
rules, never the model code.

Conventions (mesh axes: optional "pod", "data", "model"):
  batch        -> ("pod", "data")     activations' batch dim
  embed        -> None (replicated) or "data" under FSDP for params
  heads/mlp/kv -> "model"             tensor parallel param dims
  expert       -> "model"             expert parallel
  vocab        -> "model"
  seq          -> "model"             sequence parallelism for long prefill
"""
from __future__ import annotations

import dataclasses
import re
import threading
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()

# Beyond-paper optimization switches (EXPERIMENTS.md §Perf). Baseline mode
# (all False) reproduces the first-cut sharding/remat scheme so both variants
# stay measurable; dryrun.py --baseline flips them off.
OPT = {
    "kv_repeat": True,        # GQA: broadcast KV heads when TP > n_kv_heads
    "attn_inner_remat": True, # checkpoint the kv-scan body (flash-bwd style)
    "fsdp_dim0": True,        # FSDP over the stacked-layer dim (ZeRO-3 gathers)
    "moe_ep_data": True,      # experts sharded over data axis (EP) + TP inner
    "kv_cache_time_shard": False,  # decode: shard KV cache over time (see §Perf 6)
    "serve_bf16": False,      # serving params in bf16 (see §Perf 6)
}


def set_opt(**kw) -> None:
    for k, v in kw.items():
        if k not in OPT:
            raise KeyError(k)
        OPT[k] = v


def set_all_opt(value: bool) -> None:
    for k in OPT:
        OPT[k] = value


@dataclasses.dataclass
class ShardingRules:
    """How logical dims map to mesh axes for this run."""

    batch: Any = ("pod", "data")  # tuple = nested mapping over multiple axes
    seq: Any = None  # set to "model" for sequence-parallel prefill
    heads: Any = "model"
    kv: Any = "model"
    mlp: Any = "model"
    # EP axis: "data" (optimized — weights stationary per shard, tokens move
    # via a2a; composes with TP over "mlp") or "model" (baseline)
    expert: Any = None  # resolved lazily against OPT["moe_ep_data"]
    vocab: Any = "model"
    embed: Any = None  # "data" => FSDP: shard params' embed dim over data
    fsdp: bool = False
    # ZeRO-3-style stacked-dim placement: a big train win (per-layer gather
    # amortized over the batch) but a temp-memory loss for decode (§Perf 6) —
    # so it is a per-run choice, train-only by default.
    fsdp_stacked: bool = True
    mesh: Optional[Mesh] = None

    def axis(self, name: Optional[str]):
        if name is None:
            return None
        v = getattr(self, name)
        if name == "expert" and v is None:
            v = "data" if OPT["moe_ep_data"] else "model"
        if v is None:
            return None
        if isinstance(v, tuple):
            # only keep axes that exist in the mesh
            if self.mesh is None:
                return v
            kept = tuple(a for a in v if a in self.mesh.axis_names)
            return kept if kept else None
        if self.mesh is not None and v not in self.mesh.axis_names:
            return None
        return v


def set_rules(rules: Optional[ShardingRules]) -> None:
    _STATE.rules = rules


def get_rules() -> Optional[ShardingRules]:
    return getattr(_STATE, "rules", None)


class use_rules:
    def __init__(self, rules: Optional[ShardingRules]):
        self.rules = rules

    def __enter__(self):
        self.prev = get_rules()
        set_rules(self.rules)
        return self.rules

    def __exit__(self, *exc):
        set_rules(self.prev)


# -- activation annotations ---------------------------------------------------

_ACTIVATION_SPECS = {
    # kind -> logical dim names per trailing axis meaning; leading dims padded None
    "batch_seq": ("batch", "seq"),  # e.g. tokens [B, S]
    "hidden": ("batch", "seq", None),  # [B, S, D]
    "hidden_sp": ("batch", "seq", None),
    "mlp": ("batch", "seq", "mlp"),  # [B, S, F]
    "heads": ("batch", "seq", "heads", None),  # [B, S, H, Dh]
    "kv": ("batch", "seq", "kv", None),
    "kv_cache": ("batch", None, "kv", None),  # [B, T, Hkv, Dh]
    "logits": ("batch", "seq", "vocab"),
    "expert_buf": ("expert", None, None),  # [E, C, D]
}


def shard_activation(x: jax.Array, kind: str) -> jax.Array:
    """Annotate an intermediate with its logical sharding (no-op w/o rules)."""
    rules = get_rules()
    if rules is None:
        return x
    names = _ACTIVATION_SPECS[kind]
    if len(names) > x.ndim:
        return x
    pad = (None,) * (x.ndim - len(names))
    spec = P(*(pad + tuple(rules.axis(n) for n in names)))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x  # outside a mesh context


# -- parameter shardings ------------------------------------------------------

# path-pattern -> logical names per dim (matched right-aligned to the shape;
# leading stacked-layer dims are replicated). First match wins.
PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"router", ("embed", None)),  # router stays small; replicate experts dim
    # experts: EP over the expert dim + TP over the expert-ffn dim
    (r"experts?.*w_(up|gate)", ("expert", None, "mlp")),
    (r"experts?.*w_down", ("expert", "mlp", None)),
    (r"w_(up|gate)", ("embed", "mlp")),
    (r"w_down", ("mlp", "embed")),
    (r"(wq|w_q)", ("embed", "heads")),
    (r"(wk|w_k|wv|w_v)", ("embed", "kv")),
    (r"(wo|w_o)", ("heads", "embed")),
    (r"(bq)", ("heads",)),
    (r"(bk|bv)", ("kv",)),
    (r"embedding|unembed|lm_head", ("vocab", "embed")),
    (r"in_proj", ("embed", "mlp")),  # ssm projections: tp over inner dim
    (r"out_proj", ("mlp", "embed")),
    (r"conv", (None, "mlp")),
    (r".*", (None,)),  # default: replicate (norm scales, A_log, dt_bias, ...)
)


def _axes_size(rules: ShardingRules, axis) -> int:
    if axis is None or rules.mesh is None:
        return 1
    if isinstance(axis, tuple):
        out = 1
        for a in axis:
            out *= rules.mesh.shape[a]
        return out
    return rules.mesh.shape[axis]


def _sanitize(axes, shape, rules: ShardingRules):
    """Drop mesh axes that don't evenly divide their dim (jit requires it)

    and duplicate mesh-axis uses (keep the first occurrence)."""
    out = []
    seen = set()
    for a, d in zip(axes, shape):
        ok = a is not None and d % _axes_size(rules, a) == 0
        names = a if isinstance(a, tuple) else (a,)
        if ok and any(n in seen for n in names):
            ok = False
        if ok:
            seen.update(names)
        out.append(a if ok else None)
    return out


def param_spec_for(path: str, shape: Tuple[int, ...], rules: ShardingRules) -> P:
    ndim = len(shape)
    for pat, names in PARAM_RULES:
        if re.search(pat, path):
            names = tuple(names)
            if len(names) > ndim:
                names = names[-ndim:] if ndim > 0 else ()
            pad = (None,) * (ndim - len(names))
            axes = _sanitize([rules.axis(n) for n in pad + names], shape, rules)
            # FSDP / ZeRO: shard one remaining replicated dim over the batch
            # axes (pod, data). Prefer the second-to-last dim (embed for
            # matmuls), skip the stacked-layer dim 0 of scanned params when
            # another choice exists.
            if rules.fsdp and rules.mesh is not None and "data" in rules.mesh.axis_names:
                batch_ax = rules.axis("batch") or "data"
                used = set()
                for a in axes:
                    used.update(a if isinstance(a, tuple) else (a,))
                b_names = batch_ax if isinstance(batch_ax, tuple) else (batch_ax,)
                if not any(n in used for n in b_names):
                    # Prefer the stacked-layer dim (dim 0 of scanned params):
                    # the scan's dynamic-slice then lowers to a per-layer
                    # one-shot gather (ZeRO-3 schedule). Sharding a matmul's
                    # contraction dim instead makes XLA either all-gather the
                    # weights per use or psum activation-sized partials —
                    # both measured catastrophically worse (§Perf log).
                    if ndim >= 3 and OPT["fsdp_dim0"] and rules.fsdp_stacked:
                        order = [0] + [i for i in range(ndim - 2, 0, -1)] + [ndim - 1]
                    elif ndim >= 3:
                        order = [i for i in range(ndim - 2, 0, -1)] + [ndim - 1, 0]
                    else:
                        order = list(range(ndim - 2, -1, -1)) + ([ndim - 1] if ndim else [])
                    for i in order:
                        if axes[i] is None and shape[i] % _axes_size(rules, batch_ax) == 0:
                            axes[i] = batch_ax
                            break
            return P(*axes)
    return P()


def tree_param_specs(params: Any, rules: ShardingRules) -> Any:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs)."""

    def visit(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        return param_spec_for(pstr, tuple(leaf.shape), rules)

    return jax.tree_util.tree_map_with_path(visit, params)


def tree_shardings(params: Any, rules: ShardingRules, mesh: Mesh) -> Any:
    specs = tree_param_specs(params, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
