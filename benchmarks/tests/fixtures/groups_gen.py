"""A generator of the mapping form, for ``test_contract.py``: rows in query
groups of 1 to 40 (``qid`` sorted), a graded label and a weight a row. What
it returns goes to ``RayDMatrix`` as keyword arguments."""

import numpy as np


def make(rows, features, seed, stream=0, grades=5):
    rng = np.random.default_rng([seed, stream])
    sizes = rng.integers(1, 41, size=rows)
    qid = np.repeat(np.arange(rows), sizes)[:rows].astype(np.int32)
    x = rng.standard_normal((rows, features), dtype=np.float32)
    score = x[:, 0] + 0.5 * x[:, 1] * x[:, 2] + 0.3 * rng.standard_normal(rows)
    edges = np.quantile(score, np.linspace(0, 1, grades + 1)[1:-1])
    return {"data": x,
            "label": np.searchsorted(edges, score).astype(np.float32),
            "qid": qid,
            "weight": rng.uniform(0.5, 1.5, rows).astype(np.float32)}
