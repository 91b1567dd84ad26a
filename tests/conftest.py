import numpy as np
import pytest

from repro.core.metrics import results_mismatch
from repro.core.predicates import Between, Cmp, Contains, In, NotNull, make_filter
from repro.core.types import Column, VectorDatabase, Workload


def assert_same_results(a_s, a_i, b_s, b_i):
    """Scores allclose (with -inf normalized) and per-row candidate-set
    equality modulo exact-tie ordering — the engine-parity assertion shared
    by the engine/service/pq suites (``repro.core.metrics.results_mismatch``)."""
    msg = results_mismatch(a_s, a_i, b_s, b_i)
    assert msg is None, msg


def small_db(n=2000, d=16, seed=0, metric="l2"):
    rng = np.random.default_rng(seed)
    vecs = rng.normal(size=(n, d)).astype(np.float32)
    a = rng.random(n).astype(np.float32)
    b = rng.random(n).astype(np.float32)
    cat = rng.integers(0, 8, n).astype(np.int32)
    null = rng.random(n) < 0.3
    member = rng.random((n, 6)) < 0.25
    member[np.arange(n), rng.integers(0, 6, n)] = True
    return VectorDatabase(
        vectors=vecs,
        columns={
            "A": Column.numeric("A", a),
            "B": Column.numeric("B", b, null_mask=null),
            "cat": Column.categorical("cat", cat),
            "tags": Column.setcat("tags", member),
        },
        metric=metric,
    )


def small_workload(db, n_queries=60, seed=1, k=5):
    rng = np.random.default_rng(seed)
    templates = [
        make_filter(Between("A", 0.0, 0.1)),
        make_filter(Between("A", 0.0, 0.5), NotNull("B")),
        make_filter(Contains("tags", 2)),
        make_filter(In("cat", frozenset({0, 1})), Between("B", 0.2, 0.9)),
        make_filter(NotNull("B")),
        make_filter(),  # pure vector search
    ]
    t_of = rng.integers(0, len(templates), n_queries).astype(np.int32)
    qv = rng.normal(size=(n_queries, db.d)).astype(np.float32)
    return Workload(vectors=qv, templates=templates, template_of=t_of, k=k)


@pytest.fixture(scope="session")
def db():
    return small_db()


@pytest.fixture(scope="session")
def workload(db):
    return small_workload(db)
