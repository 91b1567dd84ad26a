"""The roofline's useful-work count against a brute-force count, and the
plain reference against ``HQIIndex.search``, at a tiny size on the CPU."""
import json

import numpy as np
import pytest

from tinycheckout import BENCH

from harness import check, program
from harness.dataset import plain_mask
from harness.reference import exact_topk
from harness.spec import load_module
from harness.work import scan_work


@pytest.fixture(scope="module", params=["kg-pbg-wikidata-1m", "msturing-range-1m"])
def built(request):
    cfg = json.loads((BENCH / "configs" / f"{request.param}.json").read_text())
    cfg.update(n=5000, queries_per_split=120, n_query_vectors=6)
    if "assumed" in cfg and "n_clusters" in cfg["assumed"]:
        cfg["assumed"] = dict(cfg["assumed"], n_clusters=40)
    log_name = "t0" if request.param.startswith("kg") else "range"
    ds = load_module(BENCH / "gen" / f"{cfg['generator']}.py").generate(cfg, 3, logs=(log_name,))
    ds.freeze()
    index = program.build_index(ds, cfg, log_name)
    masks = np.stack([plain_mask(t, ds.columns) for t in ds.templates])
    return ds, index, masks, ds.logs[log_name]


def brute_force_work(index, masks, log, nprobe, metric):
    pairs, rows = 0, set()
    for part in index.partitions:
        ivf, prow = part.ivf, np.asarray(part.rows)
        offsets = np.asarray(ivf.offsets)
        members = [prow[np.asarray(ivf.order)[offsets[i]:offsets[i + 1]]] for i in range(len(offsets) - 1)]
        cents = np.asarray(ivf.centroids)[: len(members)]
        for q, t in zip(log.vectors, log.template_of):
            if not masks[t, prow].any():
                continue
            if metric == "ip":
                score = cents @ q
            else:
                score = -((cents - q) ** 2).sum(axis=1)
            for li in np.argsort(-score, kind="stable")[:nprobe]:
                hit = [r for r in members[li] if masks[t, r]]
                pairs += len(hit)
                rows.update(hit)
    return pairs, len(rows)


def test_work_count_matches_brute_force(built):
    ds, index, masks, log = built
    got = scan_work(index, masks, log.vectors, log.template_of, 8, ds.metric)
    pairs, rows = brute_force_work(index, masks, log, 8, ds.metric)
    assert got["pairs"] == pairs and got["rows"] == rows
    assert got["flops"] == 2.0 * ds.d * pairs
    assert got["bytes"] == 4.0 * ds.d * (rows + log.m)
    # the program's own count of distances over passing rows agrees
    from repro.core.ivf import ScanStats
    from repro.core.plan import build_plan

    wl = program.program_workload(ds, log)
    stats = ScanStats()
    tasks, _, _ = index._engine_tasks(wl, nprobe=8, batch_vec=True, stats=stats)
    build_plan(index.arena, tasks, wl.vectors, m=wl.m, k=wl.k, stats=stats)
    assert stats.dists_computed == pairs


def test_reference_agrees_with_index_when_every_list_is_probed(built):
    """With every posting list probed the index is exact, so its answers are
    the plain reference's."""
    ds, index, masks, log = built
    wl = program.program_workload(ds, log)
    res = index.search(wl, nprobe=10_000)
    want_s, want_i = exact_topk(ds.vectors, ds.metric, log.vectors, masks, log.template_of, ds.k)
    assert np.array_equal(np.sort(res.ids, axis=1), np.sort(want_i, axis=1))
    ok = want_i >= 0
    assert np.allclose(res.scores[ok], want_s[ok], rtol=1e-5, atol=1e-5)
    admitted = (res.ids >= 0) & masks[log.template_of[:, None], np.maximum(res.ids, 0)]
    assert check.bad_answers(res.ids, res.scores, admitted) == 0
    assert check.score_gap(ds.vectors, ds.metric, log.vectors, res.ids, res.scores) < 1e-5
    hits, totals = check.recall_hits(res.ids, want_i)
    assert hits.sum() == totals.sum()


def test_bf16_control_reads_a_wider_score_gap(built):
    ds, index, masks, log = built
    wl = program.program_workload(ds, log)
    res = index.search(wl, nprobe=8)
    prog = check.score_gap(ds.vectors, ds.metric, log.vectors, res.ids, res.scores)
    cs, ci = exact_topk(ds.vectors, ds.metric, log.vectors, masks, log.template_of, ds.k, precision="bf16")
    ctrl = check.score_gap(ds.vectors, ds.metric, log.vectors, ci, cs)
    assert ctrl > 30 * prog, (ctrl, prog)
