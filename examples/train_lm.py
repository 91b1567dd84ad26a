"""End-to-end training driver: train a ~100M-param LM for a few hundred steps

with the full production loop — microbatched train step, WSD schedule,
async checkpointing, restart-safe deterministic data.

    PYTHONPATH=src python examples/train_lm.py            # ~100M params
    PYTHONPATH=src python examples/train_lm.py --tiny     # CI-speed
"""
import argparse
import dataclasses

import jax

from repro.configs import get_reduced
from repro.data.pipeline import DataConfig
from repro.models import api
from repro.models.transformer import ModelConfig
from repro.train.fault_tolerance import LoopConfig, TrainLoop
from repro.train.optimizer import OptConfig
from repro.train.train_step import TrainConfig
from repro.compile_cache import enable_compile_cache

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--tiny", action="store_true")
ap.add_argument("--steps", type=int, default=None)
args = ap.parse_args()

if args.tiny:
    cfg = get_reduced("minicpm-2b")
    steps = args.steps or 30
    batch, seq = 8, 32
else:
    # ~100M-param llama-style LM (minicpm family wiring, scaled down)
    cfg = ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=12, d_ff=2048, vocab=32_000, q_chunk=256, kv_chunk=256,
    )
    steps = args.steps or 300
    batch, seq = 16, 256

n_params = api.count_params(api.init_model(cfg, jax.random.key(0)))
print(f"model: {cfg.name}  params={n_params/1e6:.1f}M")

tcfg = TrainConfig(
    opt=OptConfig(name="adamw", schedule="wsd", peak_lr=3e-4,
                  warmup_steps=max(10, steps // 20), total_steps=steps),
    microbatches=2,
)
dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
loop = TrainLoop(cfg, tcfg, dcfg,
                 LoopConfig(ckpt_dir="/tmp/repro_train_lm", ckpt_every=max(50, steps // 4),
                            log_every=max(1, steps // 20)))
loop.maybe_restore()
hist = loop.run(steps)
first, last = hist[0]["loss"], hist[-1]["loss"]
print(f"loss: {first:.3f} -> {last:.3f} over {steps} steps")
assert last < first, "training must reduce the loss"
print("OK")
