"""The one place the harness hands plain data to the system under test.

Turns a ``Dataset`` into the program's ``VectorDatabase`` and ``Workload``
objects and builds its ``HQIIndex``. The arrays are shared, not copied:
``Dataset.freeze`` has made them read-only first, so the program cannot
change what the reference later reads.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from harness.dataset import Dataset, QueryLog


def program_filter(template: list) -> tuple:
    from repro.core.predicates import make_filter, predicate_from_state

    return make_filter(*(predicate_from_state(p) for p in template))


def program_columns(columns: Dict[str, dict]) -> dict:
    from repro.core.types import Column

    out = {}
    for name, c in columns.items():
        if c["kind"] == "setcat":
            out[name] = Column(name, "setcat", c["values"], c["null"])
        else:
            out[name] = Column(name, c["kind"], c["values"], c["null"])
    return out


def program_db(ds: Dataset):
    from repro.core.types import VectorDatabase

    return VectorDatabase(vectors=ds.vectors, columns=program_columns(ds.columns), metric=ds.metric)


def program_workload(ds: Dataset, log: QueryLog):
    from repro.core.types import Workload

    return Workload(
        vectors=log.vectors,
        templates=[program_filter(t) for t in ds.templates],
        template_of=np.asarray(log.template_of, dtype=np.int32),
        k=ds.k,
    )


def build_index(ds: Dataset, cfg: dict, build_log: str):
    """``HQIIndex.build`` over the dataset, on the configuration's index
    settings, with ``build_log`` as the historical workload."""
    from repro.core import HQIConfig, HQIIndex

    index_cfg = HQIConfig(**cfg.get("index", {}))
    return HQIIndex.build(program_db(ds), program_workload(ds, ds.logs[build_log]), index_cfg)
