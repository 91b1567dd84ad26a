"""Plain data the generators make and the reference reads.

A ``Dataset`` holds the rows (vectors f32 [n, d] and columns), the query
templates as lists of predicate dicts, and the query logs. Nothing here
imports the program: ``program.py`` turns these into its objects, and the
plain reference evaluates the same dicts with ``plain_mask``.

Columns are dicts ``{"kind": "numeric" | "categorical" | "setcat",
"values": array, "null": bool [n]}``; predicates are dicts with a ``kind``
of ``cmp``, ``between``, ``in``, ``contains`` or ``notnull`` (the program's
own state format for predicates).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class QueryLog:
    vectors: np.ndarray  # f32 [m, d]
    template_of: np.ndarray  # i32 [m], index into Dataset.templates

    @property
    def m(self) -> int:
        return int(self.vectors.shape[0])


@dataclasses.dataclass
class Dataset:
    vectors: np.ndarray  # f32 [n, d]
    columns: Dict[str, dict]
    metric: str  # "ip" or "l2"
    templates: List[list]
    logs: Dict[str, QueryLog]
    k: int

    @property
    def n(self) -> int:
        return int(self.vectors.shape[0])

    @property
    def d(self) -> int:
        return int(self.vectors.shape[1])

    def freeze(self) -> None:
        """Make every array read-only, so the program cannot change the rows
        the reference is later computed from."""
        self.vectors.flags.writeable = False
        for col in self.columns.values():
            col["values"].flags.writeable = False
            col["null"].flags.writeable = False


def seeded_rng(seed: int, label: str) -> np.random.Generator:
    """A numpy generator for one purpose of one seed (any size of seed)."""
    return np.random.default_rng(
        np.random.SeedSequence(int(seed), spawn_key=(zlib.crc32(label.encode()),))
    )


def seed_key(seed: int):
    """A JAX threefry key from a seed of any size."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, dtype=jnp.uint32), impl="threefry2x32")


def reseed(ds: Dataset, seed: int) -> Dataset:
    """The run's data from its seed: the configuration's corpus and query
    logs (drawn from its fixed ``corpus_seed``), each log in an order drawn
    from ``seed``.

    Every seed sends the same queries over the same rows, in another order,
    so the index each run builds has the same partitions, posting lists and
    work, and its answers the same recall: runs on different seeds differ
    only as runs of one seed do. (A transform of the rows drawn from the
    seed, such as a rotation or a signed permutation of the coordinates,
    keeps every score only up to rounding, and the index's k-means turns
    that rounding into other partitions and another recall.)
    """
    logs = {}
    for name in sorted(ds.logs):
        log = ds.logs[name]
        order = seeded_rng(seed, f"order-{name}").permutation(log.m)
        logs[name] = QueryLog(vectors=np.ascontiguousarray(log.vectors[order]),
                              template_of=log.template_of[order])
    return dataclasses.replace(ds, logs=logs)


def exact_counts(freqs: Sequence[float], m: int) -> np.ndarray:
    """Template index per query: ``m`` queries split by ``freqs`` in whole
    numbers (largest remainder), in template order."""
    f = np.asarray(freqs, dtype=np.float64)
    f = f / f.sum()
    base = np.floor(f * m).astype(np.int64)
    rest = m - int(base.sum())
    order = np.argsort(-(f * m - base), kind="stable")
    base[order[:rest]] += 1
    return np.repeat(np.arange(len(f), dtype=np.int32), base)


def plain_predicate(p: dict, columns: Dict[str, dict]) -> np.ndarray:
    """Rows (bool [n]) that satisfy one predicate; NULL satisfies nothing
    but its absence is what ``notnull`` asks."""
    col = columns[p["attr"]]
    vals, null = col["values"], col["null"]
    kind = p["kind"]
    if kind == "notnull":
        return ~null
    if kind == "cmp":
        x, op = p["value"], p["op"]
        out = {
            "<": vals < x, "<=": vals <= x, ">": vals > x, ">=": vals >= x, "==": vals == x,
        }[op]
    elif kind == "between":
        out = (vals >= p["lo"]) & (vals < p["hi"])
    elif kind == "in":
        out = np.isin(vals, np.asarray(list(p["values"]), dtype=vals.dtype))
    elif kind == "contains":
        out = vals[:, int(p["value"])]
    else:
        raise ValueError(f"unknown predicate kind {kind!r}")
    return out & ~null


def plain_mask(template: list, columns: Dict[str, dict]) -> np.ndarray:
    """Rows (bool [n]) that satisfy every predicate of a template."""
    n = next(iter(columns.values()))["null"].shape[0]
    out = np.ones(n, dtype=bool)
    for p in template:
        out &= plain_predicate(p, columns)
    return out
