"""A ``dense_vector`` field: standard normal float32 vectors, one per doc.
Real embeddings are clustered; these are not (PERF.md, cell 2)."""

import numpy as np


def build(rng, n: int, spec: dict) -> np.ndarray:
    return rng.standard_normal((n, spec["dims"]), dtype=np.float32)


def mapping(spec: dict) -> dict:
    return {"type": "dense_vector", "dims": spec["dims"],
            "similarity": spec["similarity"]}
