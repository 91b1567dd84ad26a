"""Chip smoke: drive HQI's main path once on a TPU, at a KG deployment's size.

    python chip_smoke.py             # one chip: batch search + WAL-backed service
    python chip_smoke.py --chips 4   # the sharded engine on four chips, only

Deployment: the KG-entity corpus of ``repro.core.workload.kg_style`` at
n = 1,000,000 entities and d = 200 f32 (the width of the public
PyTorch-BigGraph Wikidata entity embeddings, cut from ~78M rows to 1M), seed
0; the ten Table-1 templates; split t0 of 2,000 queries at k = 10.

One chip: both f32 scan grids are first checked against the jnp reference
on one random batch. The index is built with ``scan_mode="f32"``; ``search(nprobe=8)``
runs on the compiled Pallas kernels and on the jnp path, which must agree by
the engine-parity rule (``repro.core.metrics.results_mismatch``); the same
holds after ``attach_pq`` for the ``scan_mode="pq"`` override (streamed ADC
kernel + exact re-rank). Recall@10 against ``exhaustive_search`` must not fall
below ``RECALL_FLOOR``. Then an ``HQIService`` over a WAL store answers
queries across inserts, deletes and a ``refresh()``; every answer must be
``ok``, none degraded, and equal to an offline search of the same state.

Four chips: the same deployment, searched on device 0 and then with
``HQIConfig.mesh`` over four devices (f32 and pq over 256 queries of t0, one
service flush); the sharded answers must agree with the single-device ones.

Lines before the last are smoke readings, not benchmark metrics. The last
line is one JSON object, ``{"ok": true, "device": {...}}``; without a TPU, or
when any check fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

N_ENTITIES = 1_000_000
DIM = 200
N_QUERIES = 2_000
SEED = 0
NPROBE = 8
PQ_M = 8
REFINE_FACTOR = 4
# recall@10 of the jnp path on the CPU at this deployment (nprobe=8 over 108
# partitions), for k-means seeds 0-3: 0.56645, 0.56745, 0.56675, 0.5927. An
# index built on another backend cuts its lists differently, since k-means
# follows float rounding. The floor is the lowest reading less the spread
# (0.02625), rounded down: it catches a broken scan, not a different
# clustering.
RECALL_FLOOR = 0.54
# service traffic: queries per submit round, rows inserted, ids deleted
SERVICE_QUERIES, SERVICE_INSERTS, SERVICE_DELETES = 512, 4_096, 1_024


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def expect_same(a, b, what: str) -> None:
    """``a`` and ``b`` are (scores, ids) pairs; fail unless they agree."""
    from repro.core import results_mismatch

    msg = results_mismatch(a[0], a[1], b[0], b[1])
    check(msg is None, f"{what}: {msg}")


class CompileClock:
    """XLA compiles of this process: how many were requested, the seconds
    they took, and the seconds the persistent cache saved (the original
    compile time of each hit), so a warm run also tells what a cold run
    would spend compiling."""

    def __init__(self) -> None:
        import jax.monitoring

        self.count, self.secs, self.saved = 0, 0.0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.secs += secs
        elif name == "/jax/compilation_cache/compile_time_saved_sec":
            self.saved += secs


@functools.lru_cache(maxsize=None)
def compile_clock() -> CompileClock:
    return CompileClock()


@contextlib.contextmanager
def phase(name: str):
    """Logs a phase's wall time and its compiles. Every phase ends in numpy
    results, so its wall time includes the device work."""
    clock = compile_clock()
    c0, s0, v0, t0 = clock.count, clock.secs, clock.saved, time.perf_counter()
    yield
    log(
        f"phase {name}: {time.perf_counter() - t0:.1f} s wall; "
        f"{clock.count - c0} compiles, {clock.secs - s0:.1f} s compiling, "
        f"{clock.saved - v0:.1f} s saved by the persistent cache"
    )


# --------------------------------------------------------------- phases


def kernel_phase(d: int, *, seed: int = SEED) -> None:
    """Both f32 scan grids against the jnp reference on one masked batch that
    spans several query and vector tiles. The db-stationary grid rewrites its
    output block at every vector tile; only the last write may stand."""
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.fused_knn import fused_knn, fused_knn_db_stationary

    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(200, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(5_000, d)), jnp.float32)
    valid = jnp.asarray(rng.random(5_000) < 0.5)
    want = [np.asarray(x) for x in ref.masked_topk_ref(q, v, valid, 10, "ip")]
    for kernel in (fused_knn, fused_knn_db_stationary):
        got = [np.asarray(x) for x in kernel(q, v, valid, k=10)]
        expect_same(got, want, f"{kernel.__name__} vs jnp")
    log("kernels: fused_knn and fused_knn_db_stationary agree with jnp")


def build_deployment(n: int, d: int, n_queries: int, seed: int = SEED):
    """(kg dataset, f32 HQIIndex built on split t0 with HQIConfig defaults)."""
    from repro.core import HQIConfig, HQIIndex, kg_style

    t0 = time.perf_counter()
    kg = kg_style(n=n, d=d, queries_per_split=n_queries, seed=seed)
    t1 = time.perf_counter()
    index = HQIIndex.build(kg.db, kg.splits[0], HQIConfig(scan_mode="f32"))
    t2 = time.perf_counter()
    log(
        f"built n={n} d={d}: data {t1 - t0:.1f} s, index {t2 - t1:.1f} s, "
        f"{len(index.partitions)} partitions"
    )
    return kg, index


def search(index, wl, *, use_pallas: bool, **kw):
    """``index.search(wl, nprobe=8, **kw)`` on the chosen kernel backend;
    returns (SearchResult, wall seconds)."""
    index.cfg.plan = dataclasses.replace(index.cfg.plan, use_pallas=use_pallas)
    t0 = time.perf_counter()
    res = index.search(wl, nprobe=NPROBE, **kw)  # numpy out: synced with the device
    return res, time.perf_counter() - t0


def batch_phase(kg, index, *, seed: int = SEED) -> dict:
    """Pallas vs jnp parity for f32 and pq searches, and recall@10 of both
    scan modes against the exact reference. Leaves a codebook attached."""
    from repro.core import exhaustive_search, recall_at_k, train_pq
    from repro.kernels import ops

    wl = kg.splits[0]
    before = ops.dispatch_stats().snapshot()
    pal, t_cold = search(index, wl, use_pallas=True)
    # (W, TQ, TV, k) per f32 bucket; TV >= 4·TQ runs the db-stationary grid
    shapes = sorted(ops.dispatch_stats().delta_since(before).shapes)
    n_db = sum(tv >= ops._DB_STATIONARY_RATIO * tq for _, tq, tv, _ in shapes)
    log(f"f32 buckets (W, TQ, TV, k): {shapes}; {n_db} on the db-stationary grid")
    clock = compile_clock()
    c0 = clock.count
    pal, t_warm = search(index, wl, use_pallas=True)
    warm_compiles = clock.count - c0
    ref, t_jnp = search(index, wl, use_pallas=False)
    log(
        f"f32 search of {wl.m} queries: pallas cold {t_cold:.2f} s, warm "
        f"{t_warm:.2f} s ({warm_compiles} compiles), jnp {t_jnp:.2f} s"
    )
    expect_same((pal.scores, pal.ids), (ref.scores, ref.ids), "f32 pallas vs jnp")

    t0 = time.perf_counter()
    truth = exhaustive_search(kg.db, wl)
    t_exact = time.perf_counter() - t0
    recall_f32 = recall_at_k(pal, truth)

    t0 = time.perf_counter()
    index.attach_pq(
        train_pq(kg.db.vectors, PQ_M, metric=kg.db.metric,
                 iters=index.cfg.kmeans_iters, seed=seed)
    )
    t_pq_build = time.perf_counter() - t0
    pq_kw = dict(scan_mode="pq", refine_factor=REFINE_FACTOR)
    pal_pq, t_pq = search(index, wl, use_pallas=True, **pq_kw)
    ref_pq, _ = search(index, wl, use_pallas=False, **pq_kw)
    expect_same((pal_pq.scores, pal_pq.ids), (ref_pq.scores, ref_pq.ids), "pq pallas vs jnp")
    recall_pq = recall_at_k(pal_pq, truth)
    index.cfg.plan = dataclasses.replace(index.cfg.plan, use_pallas=None)
    log(
        f"exact reference {t_exact:.2f} s; codebook M={PQ_M} {t_pq_build:.1f} s; "
        f"pq search (cold) {t_pq:.2f} s"
    )
    log(f"recall@{wl.k}: f32 {recall_f32:.4f}, pq refine {REFINE_FACTOR} {recall_pq:.4f}")
    return {"recall_f32": recall_f32, "recall_pq": recall_pq}


def _submit_all(svc, wl) -> list:
    return [svc.submit(wl.vectors[i], wl.templates[wl.template_of[i]]) for i in range(wl.m)]


def _answers(svc, handles) -> tuple:
    """(scores, ids) of drained handles; fails unless every one is ok and exact."""
    check(all(h.done and h.ok for h in handles), "a service query failed")
    check(not any(h.degraded for h in handles), "a service answer was degraded")
    return np.stack([h.scores for h in handles]), np.stack([h.ids for h in handles])


def offline_answers(svc, wl) -> tuple:
    """What the service's current state should answer: ``HQIIndex.search``
    over the indexed rows minus tombstones, merged with an exact scan of the
    live rows not yet folded into the index. Returns (scores, ids)."""
    from repro.core import exhaustive_search

    index, k = svc.index, wl.k
    live = svc.live_ids()
    mask = np.zeros(index.db.n, dtype=bool)
    mask[live[live < index.db.n]] = True
    res = index.search(wl, nprobe=svc.cfg.nprobe, live_mask=mask)
    scores, ids = res.scores, res.ids
    delta_db, delta_live = svc.delta.snapshot()
    if delta_db is not None and delta_live.any():
        rows = np.nonzero(delta_live)[0]
        ex = exhaustive_search(delta_db.take(rows), wl)
        gid = np.where(ex.ids >= 0, svc.delta.first_id + rows[np.maximum(ex.ids, 0)], -1)
        scores = np.concatenate([scores, ex.scores], axis=1)
        ids = np.concatenate([ids, gid], axis=1)
        top = np.argsort(-scores, axis=1, kind="stable")[:, :k]
        scores = np.take_along_axis(scores, top, axis=1)
        ids = np.take_along_axis(ids, top, axis=1)
    return np.where(ids >= 0, scores, -np.inf), ids


def service_phase(
    kg, index, store_dir: Path, *, n_queries: int, n_insert: int, n_delete: int,
    seed: int = SEED,
) -> dict:
    """WAL-backed HQIService: submit, insert, delete, drain, refresh, submit,
    drain — every answer ok, none degraded, equal to the offline search."""
    from repro.service import ServiceConfig
    from repro.store import init_store

    db, wl = kg.db, kg.splits[1]
    rng = np.random.default_rng(seed + 1)
    shutil.rmtree(store_dir, ignore_errors=True)
    t0 = time.perf_counter()
    svc = init_store(str(store_dir), index, cfg=ServiceConfig(pad_pow2=True))
    t_init = time.perf_counter() - t0
    try:
        first = wl.subset(np.arange(n_queries))
        handles = _submit_all(svc, first)
        src = rng.integers(0, db.n, n_insert)
        vecs = db.vectors[src] + 0.01 * rng.normal(size=(n_insert, db.d)).astype(np.float32)
        ins = svc.insert(
            vecs,
            {name: col.values[src] for name, col in db.columns.items()},
            {name: col.null_mask[src] for name, col in db.columns.items()},
        )
        n_dead_new = n_delete // 4
        dead = np.concatenate([
            rng.choice(db.n, n_delete - n_dead_new, replace=False),
            rng.choice(ins, n_dead_new, replace=False),
        ])
        check(svc.delete(dead) == n_delete, "not every delete was applied")
        t0 = time.perf_counter()
        check(svc.drain() == n_queries, "drain did not answer every query")
        t_drain1 = time.perf_counter() - t0
        expect_same(_answers(svc, handles), offline_answers(svc, first),
                    "service vs offline, unfolded writes")

        t0 = time.perf_counter()
        check(svc.refresh() == n_insert, "refresh did not fold every insert")
        t_refresh = time.perf_counter() - t0
        second = wl.subset(np.arange(n_queries, 2 * n_queries))
        handles = _submit_all(svc, second)
        t0 = time.perf_counter()
        check(svc.drain() == n_queries, "drain did not answer every query")
        t_drain2 = time.perf_counter() - t0
        expect_same(_answers(svc, handles), offline_answers(svc, second),
                    "service vs offline, after refresh")
        health = svc.health()
        check(health.status == "ok", f"service health is {health.status!r}")
    finally:
        svc.wal.close()
        shutil.rmtree(store_dir, ignore_errors=True)
    log(
        f"service: store init {t_init:.1f} s; {n_queries} queries + {n_insert} "
        f"inserts + {n_delete} deletes drained in {t_drain1:.2f} s; refresh "
        f"{t_refresh:.2f} s; {n_queries} more drained in {t_drain2:.2f} s; "
        f"health {health.status}"
    )
    return {"health": health.status}


def sharded_phase(
    kg, index, devices, *, n_search: int = 256, n_flush: int = 128, seed: int = SEED
) -> dict:
    """Single-device answers vs the engine sharded over ``devices``: f32 and
    pq searches of the first ``n_search`` queries of split t0 and one service
    flush, each compared as soon as both sides exist. Returns whether f32 and
    pq were bit-identical. (Fewer queries than the one-chip batch: a
    four-chip call costs four times the chip time.)"""
    import jax
    from jax.sharding import Mesh

    from repro.core import train_pq
    from repro.service import HQIService, ServiceConfig

    wl = kg.splits[0].subset(np.arange(min(n_search, kg.splits[0].m)))
    index.attach_pq(
        train_pq(kg.db.vectors, PQ_M, metric=kg.db.metric,
                 iters=index.cfg.kmeans_iters, seed=seed)
    )
    mesh = Mesh(np.asarray(devices), ("model",))

    def on_one_device(fn):
        index.cfg.mesh = None
        with jax.default_device(devices[0]):
            return fn()

    def sharded(fn):
        index.cfg.mesh = mesh
        t0 = time.perf_counter()
        try:
            return fn(), time.perf_counter() - t0
        finally:
            index.cfg.mesh = None

    def log_memory(after: str) -> None:
        stats = [dev.memory_stats() or {} for dev in devices]
        log(f"device memory after {after}: bytes_in_use "
            f"{[st.get('bytes_in_use') for st in stats]}, peak_bytes_in_use "
            f"{[st.get('peak_bytes_in_use') for st in stats]}")

    identical = {}
    pq_kw = dict(scan_mode="pq", refine_factor=REFINE_FACTOR)
    for mode, kw in (("f32", {}), ("pq", pq_kw)):
        ref = on_one_device(lambda: index.search(wl, nprobe=NPROBE, **kw))
        res, dt = sharded(lambda: index.search(wl, nprobe=NPROBE, **kw))
        identical[mode] = bool(
            np.array_equal(res.ids, ref.ids) and np.array_equal(res.scores, ref.scores)
        )
        expect_same((res.scores, res.ids), (ref.scores, ref.ids),
                    f"{mode} sharded vs single device")
        log(f"{mode} search of {wl.m} queries on {len(devices)} devices: {dt:.2f} s, "
            f"bit-identical to one device: {identical[mode]}")
        log_memory(f"the sharded {mode} search")

    flush_wl = kg.splits[1].subset(np.arange(min(n_flush, kg.splits[1].m)))
    ref = on_one_device(lambda: index.search(flush_wl, nprobe=NPROBE))
    svc = HQIService(index, ServiceConfig(pad_pow2=True))
    handles = _submit_all(svc, flush_wl)
    flushed, dt = sharded(svc.flush)
    check(flushed == flush_wl.m, "the sharded flush did not answer every query")
    expect_same(_answers(svc, handles), (ref.scores, ref.ids),
                "sharded service flush vs single-device search")
    check(svc.health().status == "ok", "sharded service health is not ok")
    log(f"sharded service flush of {flushed} queries: {dt:.2f} s, agrees with one device")
    return identical


# ----------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded phase on four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    log(f"device: platform={dev['platform']} kind={dev['kind']} count={dev['count']}")
    if dev["platform"] != "tpu":
        log("no TPU: this smoke runs only on the chip")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}")
        return 1

    from repro.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    log(f"deployment: kg_style n={N_ENTITIES} d={DIM} f32, seed {SEED}, "
        f"{N_QUERIES} queries (t0), k=10, nprobe={NPROBE}; n cut from ~78M")
    try:
        with phase("all"):
            if args.chips == 1:
                with phase("kernels"):
                    kernel_phase(DIM)
            with phase("build"):
                kg, index = build_deployment(N_ENTITIES, DIM, N_QUERIES)
            if args.chips == 4:
                with phase("sharded"):
                    sharded_phase(kg, index, devices[:4])
            else:
                with phase("batch"):
                    batch = batch_phase(kg, index)
                check(batch["recall_f32"] >= RECALL_FLOOR,
                      f"f32 recall {batch['recall_f32']:.4f} below floor {RECALL_FLOOR}")
                with phase("service"):
                    service_phase(
                        kg, index, ROOT / "smoke_out" / "store",
                        n_queries=SERVICE_QUERIES, n_insert=SERVICE_INSERTS,
                        n_delete=SERVICE_DELETES,
                    )
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    log("all checks passed")
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
