"""Batch replay: whole passes of one query log through ``HQIIndex.search``.

Traffic keys: ``log`` (the query log replayed), ``build_log`` (the
historical workload the index is built on).

Set-up makes the data from the seed, builds the index and runs one pass,
which compiles every shape the window uses. The window runs passes back to
back; the pass in flight when ``--seconds`` is up is finished and counted.
``batch_qps`` is the queries answered over the window's whole length.

After the window the program's state is freed, and every answer of every
pass is compared with the plain reference (``harness.check``); its recall
against the reference's exact top-k is both ``recall_at_10`` and, as
``recall_miss``, one of the numbers that decide ``correct``.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from harness import check, device, program
from harness.dataset import plain_mask
from harness.reference import exact_topk
from harness.session import Outcome, log
from harness.work import scan_work


def answer_numbers(ds, log_, masks, truth, ids, scores, unanswered: int) -> dict:
    """The numbers that decide ``correct`` for answers to ``log_`` (rows of
    ``ids``/``scores`` repeat the log once per pass; ``truth`` holds the
    exact filtered top-k ids of one pass)."""
    reps = ids.shape[0] // log_.m
    queries = np.tile(log_.vectors, (reps, 1))
    t_of = np.tile(log_.template_of, reps)
    in_range = (ids >= 0) & (ids < ds.n)
    admitted = in_range & masks[t_of[:, None], np.where(in_range, ids, 0)]
    return {
        "unanswered": int(unanswered),
        "bad_answers": check.bad_answers(ids, scores, admitted),
        "score_gap": check.score_gap(ds.vectors, ds.metric, queries, np.where(admitted, ids, -1), scores),
        "recall_miss": check.recall_miss(ids, np.tile(truth, (reps, 1))),
    }


def run(session) -> Outcome:
    cell, cfg, tr = session.cell, session.cell.config, session.cell.traffic
    gen = cell.generator()
    with session.phase("data"):
        ds = gen.generate(cfg, session.seed, logs=tuple({tr["build_log"], tr["log"]}))
        ds.freeze()
    log_ = ds.logs[tr["log"]]
    with session.phase("build"):
        index = program.build_index(ds, cfg, tr["build_log"])
        wl = program.program_workload(ds, log_)
    nprobe = int(cfg["nprobe"])

    def search():
        return index.search(wl, nprobe=nprobe)

    from repro.kernels import ops

    with session.phase("warm"):
        before = ops.dispatch_stats().snapshot()
        search()
        log(f"scan buckets (W, TQ, TV, k): {sorted(ops.dispatch_stats().delta_since(before).shapes)}")
    session.setup_done()

    results, ends = [], []
    with session.window():
        t0 = time.perf_counter()
        while True:
            res = search()  # numpy out: the pass has finished on the device
            results.append((res.ids, res.scores))
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= session.seconds:
                break
        t1 = ends[-1]
    passes = len(results)
    log(f"{passes} passes of {log_.m} queries in {t1 - t0:.3f} s; per pass "
        f"{[round(b - a, 3) for a, b in zip([t0] + ends[:-1], ends)]} s")

    mem_peak = device.memory_peak(session.devices)
    readings = session.readings()
    readings.passes = passes
    masks = np.stack([plain_mask(t, ds.columns) for t in ds.templates])
    if session.trace:
        readings.work = scan_work(index, masks, log_.vectors, log_.template_of, nprobe, ds.metric)
        log(f"useful scan work per pass: {readings.work}")
    del index, wl, res
    gc.collect()

    k = ds.k
    unanswered = 0
    ids_all, scores_all = [], []
    for ids, scores in results:
        ids = np.asarray(ids, dtype=np.int64)
        scores = np.asarray(scores, dtype=np.float32)
        if ids.shape != (log_.m, k) or scores.shape != (log_.m, k):
            got = min(ids.shape[0], log_.m) if ids.ndim == 2 and ids.shape[1] == k else 0
            unanswered += log_.m - got
            fixed_i = np.full((log_.m, k), -1, np.int64)
            fixed_s = np.full((log_.m, k), -np.inf, np.float32)
            if got:
                fixed_i[:got], fixed_s[:got] = ids[:got], scores[:got, :k]
            ids, scores = fixed_i, fixed_s
        ids_all.append(ids)
        scores_all.append(scores)
    ids_all = np.concatenate(ids_all)
    scores_all = np.concatenate(scores_all)

    t_ref = time.perf_counter()
    _, truth = exact_topk(ds.vectors, ds.metric, log_.vectors, masks, log_.template_of, k)
    numbers = answer_numbers(ds, log_, masks, truth, ids_all, scores_all, unanswered)
    log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s")

    control = None
    if session.control:
        cs, ci = exact_topk(
            ds.vectors, ds.metric, log_.vectors, masks, log_.template_of, k, precision="bf16"
        )
        control = answer_numbers(ds, log_, masks, truth, ci, cs, 0)

    return Outcome(
        end_to_end={
            "batch_qps": log_.m * passes / (t1 - t0),
            "recall_at_10": 1.0 - numbers["recall_miss"],
        },
        numbers=numbers,
        attempted=log_.m * passes,
        failed=unanswered,
        memory_peak_bytes=mem_peak,
        readings=readings,
        control_numbers=control,
    )
