"""MS Turing-shaped rows under the HQI paper's range-filter protocol.

The filters follow the public-dataset protocol of the HQI paper (arXiv
2304.01926, Section 6.1), as ``repro.core.workload.synthetic_bigann_style``
models it: two attributes A and B drawn uniformly from [0, 1), and
``2 * levels`` range templates ``lo <= attr < 2**-i`` for i = 0..levels-1 on
each; the query log is every query vector with every template.

The vectors are not i.i.d. Gaussian, which in 100 dimensions has no near
neighbours (IVF recall would then describe the generator, not the index).
They come from a seeded Gaussian mixture drawn on the device in one jitted
call: ``n_clusters`` centres of scale ``centre_scale`` plus unit noise. The
query vectors are drawn from the same mixture. The mixture's parameters are
assumptions of the configuration, listed under its ``assumed``. The
corpus and log come from the configuration's ``corpus_seed``; the run's
seed orders the log (``harness.dataset.reseed``).
"""
from __future__ import annotations

import numpy as np

from harness.dataset import Dataset, QueryLog, reseed, seed_key, seeded_rng


def _mixture(seed: int, n: int, nq: int, d: int, n_clusters: int, centre_scale: float):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        k_c, k_a, k_n, k_qa, k_qn = jax.random.split(key, 5)
        centres = jax.random.normal(k_c, (n_clusters, d), jnp.float32) * centre_scale
        rows = centres[jax.random.randint(k_a, (n,), 0, n_clusters)]
        rows = rows + jax.random.normal(k_n, (n, d), jnp.float32)
        qs = centres[jax.random.randint(k_qa, (nq,), 0, n_clusters)]
        qs = qs + jax.random.normal(k_qn, (nq, d), jnp.float32)
        return rows, qs

    rows, qs = draw(seed_key(seed))
    return np.asarray(rows, dtype=np.float32), np.asarray(qs, dtype=np.float32)


def generate(cfg: dict, seed: int, logs=("range",)) -> Dataset:
    return reseed(corpus(cfg, int(cfg["corpus_seed"]), logs), seed)


def corpus(cfg: dict, seed: int, logs) -> Dataset:
    n, d = int(cfg["n"]), int(cfg["d"])
    nq, levels = int(cfg["n_query_vectors"]), int(cfg["levels"])
    mix = cfg["assumed"]
    vecs, qvecs = _mixture(
        seed, n, nq, d, int(mix["n_clusters"]), float(mix["centre_scale"])
    )
    rng = seeded_rng(seed, "attributes")
    columns = {
        attr: {
            "kind": "numeric",
            "values": rng.random(n).astype(np.float32),
            "null": np.zeros(n, dtype=bool),
        }
        for attr in ("A", "B")
    }
    templates = [
        [{"kind": "between", "attr": attr, "lo": 0.0, "hi": float(2.0 ** -i)}]
        for attr in ("A", "B")
        for i in range(levels)
    ]
    T = len(templates)
    # every query vector with every template
    vectors = np.repeat(qvecs, T, axis=0)
    template_of = np.tile(np.arange(T, dtype=np.int32), nq)
    return Dataset(
        vectors=vecs, columns=columns, metric=cfg["metric"], templates=templates,
        logs={"range": QueryLog(vectors=vectors, template_of=template_of)},
        k=int(cfg["k"]),
    )
