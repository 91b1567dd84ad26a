"""Roofline-term derivation from compiled dry-run artifacts.

Per (arch × shape × mesh) cell:
    compute term    = HLO_FLOPs_per_device / peak_FLOPs        (seconds)
    memory term     = HLO_bytes_per_device / HBM_bw            (seconds)
    collective term = collective_bytes_per_device / link_bw    (seconds)

``compiled.cost_analysis()`` reports the per-device (post-SPMD) module, so
per-device numbers are used directly (equivalent to the global-sum/chips
formulation). collective bytes are parsed from the compiled HLO text: the sum
of result-shape bytes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops.

Hardware constants: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
LINK_BW = 50e9  # bytes/s per ICI link


@dataclasses.dataclass(frozen=True)
class HardwareTerms:
    """Peak terms the dispatch profiler normalizes achieved throughput by."""

    name: str
    peak_flops: float  # FLOP/s
    hbm_bw: float  # bytes/s
    link_bw: float  # bytes/s per link

    def as_dict(self) -> Dict[str, object]:
        return dataclasses.asdict(self)


# Published per-chip peaks, keyed by JAX's ``device_kind``. Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s of chip-to-chip interconnect (4 links of ~50 GB/s). A TPU whose kind
# is not listed here is an error, never mapped onto another chip's peaks.
TPU_PEAKS: Dict[str, HardwareTerms] = {
    "TPU v5 lite": HardwareTerms("TPU v5 lite", PEAK_FLOPS, HBM_BW, LINK_BW),
}

# Off a TPU (CPU, interpret mode) the profiler's roofline fractions are only
# regression-tracking ratios against a fixed denominator, not device metrics.
CPU_TERMS = HardwareTerms("cpu", 5e11, 5e10, 1e10)


def current_hardware() -> HardwareTerms:
    """Peak terms of the device JAX runs on: ``TPU_PEAKS[device_kind]`` on a
    TPU (raises for a kind with no published peaks), ``CPU_TERMS`` elsewhere."""
    from ..kernels import platform

    if not platform.on_tpu():
        return CPU_TERMS
    dev = platform.device()
    try:
        return TPU_PEAKS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind {dev.device_kind!r}; "
            f"known kinds: {sorted(TPU_PEAKS)}"
        ) from None


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:\([^)]*\)\s*)?"  # optional tuple result
    r"(?:[a-z0-9_]+\[[^\]]*\][^ ]*\s+)?"  # typed result
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(typestr: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(typestr):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-device bytes moved by each collective kind (result-shape sum)."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split(kind)[0]
        b = _shape_bytes(lhs)
        if b == 0:  # fall back to whole-line parse (covers tuple shapes)
            b = _shape_bytes(line.split(kind)[0])
        out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    coll_breakdown: Dict[str, int]
    model_flops: float  # analytic 6·N_active·D (train) / 2·N_active·D (serve)
    chips: int

    @property
    def compute_s(self) -> float:
        return self.flops_per_dev / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_per_dev / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_dev / LINK_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s, "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flop_ratio(self) -> float:
        hlo_global = self.flops_per_dev * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def step_time_s(self) -> float:
        """Roofline-model step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline-model step time."""
        t = self.step_time_s
        return (self.model_flops / (self.chips * PEAK_FLOPS)) / t if t else 0.0

    def as_dict(self) -> Dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_bytes_per_dev": self.coll_bytes_per_dev,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flop_ratio": self.useful_flop_ratio,
            "mfu": self.mfu,
        }


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS per arch/shape
# ---------------------------------------------------------------------------


def active_params(cfg) -> int:
    """Per-token active parameter count (MoE: shared + top-k routed only)."""
    d = cfg.d_model
    dh = cfg.dh
    emb = cfg.vocab * d

    def attn_params():
        return d * cfg.n_heads * dh + 2 * d * cfg.n_kv_heads * dh + cfg.n_heads * dh * d

    def dense_mlp(ff):
        return 3 * d * ff  # swiglu

    if cfg.family in ("dense", "vlm"):
        per_layer = attn_params() + dense_mlp(cfg.d_ff)
        return cfg.n_layers * per_layer + emb
    if cfg.family == "moe":
        m = cfg.moe
        routed = m.top_k * 3 * d * m.d_ff_expert
        shared = 3 * d * (m.d_ff_shared or m.d_ff_expert * m.n_shared_experts) if m.n_shared_experts else 0
        router = d * m.n_experts
        moe_layer = attn_params() + routed + shared + router
        dense_layer = attn_params() + dense_mlp(cfg.d_ff)
        return (cfg.n_layers - cfg.moe_first_dense) * moe_layer + cfg.moe_first_dense * dense_layer + emb
    if cfg.family == "ssm":
        s = cfg.ssm
        per = s.d_model * (2 * s.d_inner + 2 * s.d_state + s.n_heads) + s.d_inner * s.d_model
        return cfg.n_layers * per + emb
    if cfg.family == "hybrid":
        s = cfg.ssm
        per = s.d_model * (2 * s.d_inner + 2 * s.d_state + s.n_heads) + s.d_inner * s.d_model
        shared = attn_params() + dense_mlp(cfg.d_ff)
        groups = cfg.n_layers // cfg.hybrid_attn_every
        return cfg.n_layers * per + groups * shared + emb
    if cfg.family == "encdec":
        dec = cfg.n_layers * (2 * attn_params() + 2 * d * cfg.d_ff)  # self+cross, ungated mlp
        enc = cfg.encoder_layers * (attn_params() + 2 * d * cfg.d_ff)
        return dec + enc + emb
    raise ValueError(cfg.family)


def total_params(cfg) -> int:
    if cfg.family != "moe":
        return active_params(cfg)
    d = cfg.d_model
    dh = cfg.dh
    m = cfg.moe
    attn = d * cfg.n_heads * dh + 2 * d * cfg.n_kv_heads * dh + cfg.n_heads * dh * d
    routed_all = m.n_experts * 3 * d * m.d_ff_expert
    shared = 3 * d * (m.d_ff_shared or m.d_ff_expert * m.n_shared_experts) if m.n_shared_experts else 0
    moe_layer = attn + routed_all + shared + d * m.n_experts
    dense_layer = attn + 3 * d * cfg.d_ff
    return (
        (cfg.n_layers - cfg.moe_first_dense) * moe_layer
        + cfg.moe_first_dense * dense_layer
        + cfg.vocab * d
    )


def model_flops(cfg, kind: str, batch: int, seq_len: int) -> float:
    n_act = active_params(cfg)
    if kind == "train":
        return 6.0 * n_act * batch * seq_len
    if kind == "prefill":
        return 2.0 * n_act * batch * seq_len
    if kind == "decode":
        return 2.0 * n_act * batch  # one token per sequence
    raise ValueError(kind)
