"""The benchmark's generators: the same seed gives the same data, and the
data has its source's shapes."""
import json

import numpy as np
import pytest

from tinycheckout import BENCH

from harness.dataset import exact_counts, plain_mask
from harness.spec import load_module


def config(name, **over):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(over)
    return cfg


def generate(name, seed, **over):
    cfg = config(name, **over)
    return load_module(BENCH / "gen" / f"{cfg['generator']}.py").generate(cfg, seed, logs=_logs(name)), cfg


def _logs(name):
    return ("t0", "t1") if name.startswith("kg") else ("range",)


def same(a, b):
    assert np.array_equal(a.vectors, b.vectors)
    assert a.templates == b.templates
    for name, col in a.columns.items():
        assert np.array_equal(col["values"], b.columns[name]["values"])
        assert np.array_equal(col["null"], b.columns[name]["null"])
    for name, log in a.logs.items():
        assert np.array_equal(log.vectors, b.logs[name].vectors)
        assert np.array_equal(log.template_of, b.logs[name].template_of)


@pytest.mark.parametrize("name", ["kg-pbg-wikidata-1m", "msturing-range-1m"])
def test_same_seed_same_data(name):
    big = 2**40 + 12345  # seeds past 32 bits, as the driver draws them
    a, _ = generate(name, big, n=3000, **({"n_query_vectors": 5} if "turing" in name else {"queries_per_split": 100}))
    b, _ = generate(name, big, n=3000, **({"n_query_vectors": 5} if "turing" in name else {"queries_per_split": 100}))
    same(a, b)
    c, _ = generate(name, big + 1, n=3000, **({"n_query_vectors": 5} if "turing" in name else {"queries_per_split": 100}))
    # another seed: the same rows and the same queries, in another order
    assert np.array_equal(a.vectors, c.vectors)
    for log_name, log in a.logs.items():
        other = c.logs[log_name]
        assert not np.array_equal(log.vectors, other.vectors)
        key = lambda lg: sorted(zip(lg.template_of.tolist(), map(bytes, lg.vectors)))  # noqa: E731
        assert key(log) == key(other)


def test_kg_shapes():
    ds, cfg = generate("kg-pbg-wikidata-1m", 5, n=40_000, queries_per_split=2000)
    assert ds.vectors.shape == (40_000, 200) and ds.vectors.dtype == np.float32
    assert ds.metric == "ip" and ds.k == 10
    assert np.allclose(np.linalg.norm(ds.vectors, axis=1), 1.0, atol=1e-5)
    assert set(ds.columns) == {"type", "height", "release_date", "popularity", "country"}
    assert len(ds.templates) == 10
    # each split holds its Table-1 mix in whole queries
    table = load_module(BENCH / "gen" / "kg_entities.py").TABLE1
    for s, split in enumerate(("t0", "t1")):
        counts = np.bincount(ds.logs[split].template_of, minlength=10)
        assert counts.sum() == 2000
        assert np.array_equal(np.sort(exact_counts([r[s] for r in table], 2000)),
                              np.sort(np.repeat(np.arange(10), counts)))
    # templates are cut down to their Table-1 selectivity (T9, whose popularity
    # cut applies to a base already missing 30% NULL countries, lands under it
    # in the source as well); the broad ones land on it
    sel = [plain_mask(t, ds.columns).mean() for t in ds.templates]
    assert all(s <= 1.1 * table[i][4] + 1e-4 for i, s in enumerate(sel)), sel
    for i in (6, 7, 9):
        assert abs(sel[i] - table[i][4]) < 0.25 * table[i][4], (i, sel[i])


def test_msturing_shapes():
    ds, cfg = generate("msturing-range-1m", 5, n=40_000, n_query_vectors=20)
    assert ds.vectors.shape == (40_000, 100) and ds.metric == "l2"
    assert len(ds.templates) == 20
    log = ds.logs["range"]
    assert log.m == 20 * 20
    assert np.array_equal(np.bincount(log.template_of), np.full(20, 20))
    sel = np.array([plain_mask(t, ds.columns).mean() for t in ds.templates])
    want = np.tile(2.0 ** -np.arange(10), 2)
    assert np.all(np.abs(sel - want) < 0.1 * want + 2e-3)
    # clustered: a row's nearest other row is much nearer than a random one
    q = ds.vectors[:50]
    d2 = ((q[:, None, :] - ds.vectors[None, :5000, :]) ** 2).sum(-1)
    d2[np.arange(50), np.arange(50)] = np.inf
    assert np.median(d2.min(axis=1)) < 0.8 * np.median(d2)
