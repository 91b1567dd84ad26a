"""Sharded engine accounting: per-rank scan traffic, gather width, parity.

Reports, for one HQI workload on a |model|-rank mesh (8 virtual host devices
on CPU — the same harness the mesh-parity tests use):

  * distributed/parity_exact       — sharded vs single-device engine results
                                     (must be 1.000: bit-identical)
  * distributed/search_meshR       — wall time of the sharded search
  * distributed/per_rank_bytes     — mean bytes scanned per rank vs the
                                     single-device scan (~1/|model| each)
  * distributed/gathered_per_query — candidate columns all-gathered per
                                     query: O(k·|model|), independent of N
  * distributed/balance            — max/mean per-rank scan bytes (skew)

Under ``JAX_PLATFORMS=cpu`` jax must see the virtual device pool before it
initializes, so ``main()`` re-execs this module as a subprocess with
XLA_FLAGS set and re-emits the child's CSV rows into the suite
(BENCH_distributed.json still lands in the parent). The decision reads only
the environment: a parent that asked jax for its devices would hold an
accelerator the child then needs, so anywhere else (a TPU host) the suite
runs in-process over the devices there are.
"""
from __future__ import annotations

import os
import subprocess
import sys

from .common import FAST, N, D, Q, emit, timed

DEVICES = 8


def _run() -> None:
    import numpy as np
    import jax
    from jax.sharding import Mesh

    from repro.core import HQIConfig, HQIIndex
    from repro.core.plan import PlanConfig
    from repro.core.workload import kg_style

    kg = kg_style(n=min(N, 5000 if FAST else 50_000), d=D, queries_per_split=Q, seed=0)
    wl = kg.splits[0]
    hqi = HQIIndex.build(
        kg.db, wl, HQIConfig(min_partition_size=max(256, N // 64), max_leaves=64,
                             plan=PlanConfig(use_pallas=False))
    )
    nprobe = 8

    ref = hqi.search(wl, nprobe=nprobe, batch_vec=True)
    t_single = timed(lambda: hqi.search(wl, nprobe=nprobe, batch_vec=True), warmup=1, iters=2)
    emit("distributed/search_single", t_single * 1e6, f"{wl.m / t_single:.0f} qps")

    R = min(DEVICES, len(jax.devices()))
    hqi.cfg.mesh = Mesh(np.asarray(jax.devices()[:R]), ("model",))
    res = hqi.search(wl, nprobe=nprobe, batch_vec=True)
    t_shard = timed(lambda: hqi.search(wl, nprobe=nprobe, batch_vec=True), warmup=1, iters=2)

    exact = float(np.array_equal(ref.scores, res.scores) and np.array_equal(ref.ids, res.ids))
    st = res.shard_stats
    single = int(ref.bytes_scanned)  # the INDEPENDENT mesh-less measurement
    mean_rank = int(st.per_rank_bytes.sum()) / max(1, R)
    emit("distributed/parity_exact", 0.0, f"{exact:.3f}")
    emit(
        f"distributed/search_mesh{R}", t_shard * 1e6,
        f"{wl.m / t_shard:.0f} qps on {R} host ranks",
    )
    emit(
        "distributed/per_rank_bytes", 0.0,
        f"{mean_rank:.0f} B/rank = {mean_rank / max(single, 1):.3f} of the "
        f"single-device scan ({single} B; target 1/{R} = {1 / R:.3f})",
    )
    emit(
        "distributed/gathered_per_query", 0.0,
        f"{st.gathered_per_query} candidate cols (k={wl.k} x {R} ranks; O(k·|model|), not O(n))",
    )
    emit(
        "distributed/balance", 0.0,
        f"max/mean per-rank bytes = {st.per_rank_bytes.max() / max(mean_rank, 1):.2f}",
    )

    # --- segmented vs dense merge footprint at mesh R (skewed routing) -------
    # the dense sharded merge stacks [R, m, n_slots, k] before the gather, so
    # the ragged win compounds with mesh size; ci.yml requires >= 4x here
    from repro.kernels import ops as kops

    nprobe_skew = {ti: (12 if ti == 0 else 1) for ti in range(len(wl.templates))}
    peaks, results = {}, {}
    for layout in ("dense", "segmented"):
        hqi.cfg.plan.merge_layout = layout
        kops.reset_dispatch_stats()
        results[layout] = hqi.search(wl, nprobe=nprobe_skew, batch_vec=True)
        peaks[layout] = int(kops.dispatch_stats().peak_candidate_bytes)
    hqi.cfg.plan.merge_layout = "segmented"
    parity = float(
        np.array_equal(results["dense"].scores, results["segmented"].scores)
        and np.array_equal(results["dense"].ids, results["segmented"].ids)
    )
    ratio = peaks["dense"] / max(peaks["segmented"], 1)
    emit(
        "distributed/skewed_peak_dense_bytes", float(peaks["dense"]),
        f"R={R} stacked dense merge buffer, skewed routing",
    )
    emit(
        "distributed/skewed_peak_segmented_bytes", float(peaks["segmented"]),
        f"per-rank ragged gather ({ratio:.1f}x smaller at R={R})",
    )
    emit("distributed/skewed_parity_exact", 0.0, f"{parity:.3f}")


def main() -> None:
    pool_flag = f"--xla_force_host_platform_device_count={DEVICES}"
    xla_flags = os.environ.get("XLA_FLAGS", "")
    if os.environ.get("JAX_PLATFORMS") != "cpu" or pool_flag in xla_flags:
        _run()
        return
    # host-only run: re-exec with the virtual pool
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"{xla_flags} {pool_flag}".strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    env.get("PYTHONPATH", "")] if p
    )
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_distributed"],
        capture_output=True, text=True, env=env, timeout=1800,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bench_distributed child failed:\n{r.stdout}\n{r.stderr}")
    for line in r.stdout.splitlines():
        # re-emit the child's CSV rows so the parent's suite JSON sees them
        if line.startswith("distributed/"):
            name, us, derived = line.split(",", 2)
            emit(name, float(us), derived)


if __name__ == "__main__":
    print("name,us_per_call,derived")
    _run()
