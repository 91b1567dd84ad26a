"""KG entity corpus and its Table-1 query logs, made from one seed.

The benchmark's own copy of ``repro.core.workload.kg_style`` (the HQI
paper's industrial KG workload, arXiv 2304.01926 Table 1), kept here so the
yardstick does not move with the program. Two departures, both for steadier
runs and neither a change of the deployment's shape:

* the entity vectors are drawn on the device in one jitted call from the
  seed (same law: a type centre times 2 plus unit noise, normalised), so a
  run's set-up does not spend ten seconds in host random numbers;
* each split's template counts are the Table-1 frequencies rounded to whole
  queries (largest remainder), not a multinomial draw, so every seed sends
  the same amount of work of each kind, in another order.

The corpus and its logs come from the configuration's ``corpus_seed``;
the run's seed orders the logs (``harness.dataset.reseed``).

Everything is plain data: vectors and columns as numpy arrays, templates as
lists of predicate dicts (``{"kind": "contains", "attr": "type", "value":
0}``), so the plain reference can evaluate them without the program.
"""
from __future__ import annotations

import numpy as np

from harness.dataset import Dataset, QueryLog, exact_counts, plain_mask, reseed, seed_key, seeded_rng

# Table 1: frequency at t0..t3, then the template's target selectivity.
TABLE1 = [
    (0.15, 0.17, 0.17, 0.18, 0.00005),  # T1
    (0.26, 0.26, 0.26, 0.26, 0.001),  # T2
    (0.01, 0.01, 0.01, 0.01, 0.001),  # T3
    (0.24, 0.20, 0.20, 0.20, 0.005),  # T4
    (0.11, 0.12, 0.11, 0.12, 0.005),  # T5
    (0.02, 0.02, 0.02, 0.02, 0.01),  # T6
    (0.03, 0.03, 0.04, 0.03, 0.025),  # T7
    (0.15, 0.15, 0.15, 0.14, 0.30),  # T8
    (0.01, 0.01, 0.01, 0.01, 0.58),  # T9
    (0.04, 0.04, 0.04, 0.04, 0.60),  # T10
]
SPLITS = ("t0", "t1", "t2", "t3")


def _vectors(seed: int, n: int, d: int, n_types: int):
    """(type_of int [n], unit vectors f32 [n, d]) from the seed, on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        k_type, k_centre, k_noise = jax.random.split(key, 3)
        type_of = jax.random.randint(k_type, (n,), 0, n_types)
        centres = jax.random.normal(k_centre, (n_types, d), jnp.float32) * 2.0
        v = centres[type_of] + jax.random.normal(k_noise, (n, d), jnp.float32)
        v = v / (jnp.linalg.norm(v, axis=1, keepdims=True) + 1e-6)
        return type_of, v

    type_of, v = draw(seed_key(seed))
    return np.asarray(type_of, dtype=np.int64), np.asarray(v, dtype=np.float32)


def _numeric(rng, n, present_by_type, type_of):
    present = rng.random(n) < present_by_type[type_of]
    return {"kind": "numeric", "values": rng.random(n).astype(np.float32), "null": ~present}


def generate(cfg: dict, seed: int, logs=("t0",)) -> Dataset:
    return reseed(corpus(cfg, int(cfg["corpus_seed"]), logs), seed)


def corpus(cfg: dict, seed: int, logs) -> Dataset:
    n, d, n_types = int(cfg["n"]), int(cfg["d"]), int(cfg["n_types"])
    m = int(cfg["queries_per_split"])
    type_of, vecs = _vectors(seed, n, d, n_types)
    rng = seeded_rng(seed, "attributes")

    membership = np.zeros((n, n_types), dtype=bool)
    membership[np.arange(n), type_of] = True
    extra = np.nonzero(rng.random(n) < 0.2)[0]
    membership[extra, rng.integers(0, n_types, size=len(extra))] = True

    pres = np.full(n_types, 0.02)
    pres[0] = 0.9  # "Person"-like types carry a height
    height = _numeric(rng, n, pres, type_of)
    pres = np.full(n_types, 0.05)
    pres[1] = pres[2] = 0.8  # "Song"/"Album"-like types carry a release date
    release = _numeric(rng, n, pres, type_of)
    popularity = _numeric(rng, n, np.full(n_types, 0.7), type_of)
    country = {
        "kind": "categorical",
        "values": rng.integers(0, 50, size=n).astype(np.int32),
        "null": rng.random(n) < 0.3,
    }
    columns = {
        "type": {"kind": "setcat", "values": membership, "null": ~membership.any(axis=1)},
        "height": height,
        "release_date": release,
        "popularity": popularity,
        "country": country,
    }

    def contains(t):
        return {"kind": "contains", "attr": "type", "value": t}

    def notnull(a):
        return {"kind": "notnull", "attr": a}

    def isin(a, vals):
        return {"kind": "in", "attr": a, "values": list(vals)}

    raw = [
        [contains(0), notnull("height"), isin("country", range(2))],  # T1
        [contains(0), notnull("height")],  # T2
        [contains(1), notnull("release_date"), isin("country", range(5))],  # T3
        [contains(1), notnull("release_date")],  # T4
        [contains(2), notnull("release_date")],  # T5
        [contains(3), notnull("popularity")],  # T6
        [isin("country", range(10)), notnull("popularity")],  # T7
        [notnull("popularity"), {"kind": "cmp", "attr": "popularity", "op": ">=", "value": 0.0}],  # T8
        [notnull("country")],  # T9
        [notnull("popularity")],  # T10
    ]

    def calibrated(base, target):
        """Narrow a template to its Table-1 selectivity with a popularity cut."""
        mask = plain_mask(base, columns)
        frac = mask.mean()
        if frac <= target or frac == 0:
            return base
        pop = columns["popularity"]
        vals = pop["values"][mask & ~pop["null"]]
        if len(vals) == 0:
            return base
        x = float(np.quantile(vals, min(1.0, target / frac)))
        return base + [{"kind": "cmp", "attr": "popularity", "op": "<", "value": x}, notnull("popularity")]

    templates = [calibrated(t, TABLE1[i][4]) for i, t in enumerate(raw)]

    out = {}
    for s, split in enumerate(SPLITS):
        if split not in logs:
            continue
        qrng = seeded_rng(seed, f"log-{split}")
        t_of = qrng.permutation(exact_counts([row[s] for row in TABLE1], m))
        # query vectors: an entity of the template's relevant type (four
        # tries, as in the source) plus noise
        base_type = np.where(
            t_of <= 5, np.array([0, 0, 1, 1, 2, 3, 0, 0, 0, 0])[t_of],
            qrng.integers(0, n_types, size=m),
        )
        cand = qrng.integers(0, n, size=(m, 5))
        hit = type_of[cand[:, :4]] == base_type[:, None]
        first = np.where(hit.any(axis=1), hit.argmax(axis=1), 4)
        ent = cand[np.arange(m), first]
        qv = vecs[ent] + 0.05 * qrng.normal(size=(m, d)).astype(np.float32)
        out[split] = QueryLog(vectors=qv.astype(np.float32), template_of=t_of.astype(np.int32))

    return Dataset(
        vectors=vecs, columns=columns, metric=cfg["metric"], templates=templates,
        logs=out, k=int(cfg["k"]),
    )
