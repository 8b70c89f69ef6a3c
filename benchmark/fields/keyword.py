"""A single-valued ``keyword`` field of ``values`` skewed values: value
``c000`` is the most common (chip_smoke.py's ``make_tags``, PR 21)."""

import numpy as np


def values(spec: dict) -> list:
    return [f"c{i:03d}" for i in range(spec["values"])]


def build(rng, n: int, spec: dict) -> np.ndarray:
    m = spec["values"]
    return np.minimum(rng.random(n) ** spec["skew"] * m,
                      m - 1).astype(np.int32)


def mapping(spec: dict) -> dict:
    return {"type": "keyword"}
