"""Top-level ``knn`` on a dense_vector field.

Query vectors are standard normal, rounded to multiples of 1/256 so that
the JSON text holds each value exactly and the reference sees what the
server parsed.
"""

from __future__ import annotations

import numpy as np

from benchmark import oracle


def vectors(data, params: dict) -> np.ndarray:
    """The field's vectors. The reference scores one shard; a sharded
    configuration is refused."""
    if len(data) != 1:
        raise ValueError(f"knn bodies serve a single-shard configuration; "
                         f"this one has {len(data)} shards")
    return data[0][params["field"]]


def queries(data, params: dict, rng, n: int) -> np.ndarray:
    dims = vectors(data, params).shape[1]
    v = rng.standard_normal((n, dims), dtype=np.float32)
    return np.round(v * 256.0) / 256.0


def encode(q, params: dict) -> bytes:
    vec = ",".join(map(repr, q.astype(np.float64).tolist()))
    return ('{"knn": {"field": "%s", "query_vector": [%s], "k": %d, '
            '"num_candidates": %d}, "size": %d, "_source": false}'
            % (params["field"], vec, params["k"],
               params["num_candidates"], params["size"])).encode()


def reference(data, qs, params: dict):
    """Exact cosine scores (1 + cos) / 2 in float64, one array per query."""
    return oracle.cosine_exact(vectors(data, params),
                               np.asarray(qs, np.float32))


def control(data, qs, params: dict):
    """The same scores computed in bfloat16."""
    return oracle.cosine_bf16(vectors(data, params),
                              np.asarray(qs, np.float32))


def warm(node, index: str, params: dict) -> None:
    from benchmark.mount import warm_knn
    warm_knn(node, index, params["field"], params["num_candidates"])
