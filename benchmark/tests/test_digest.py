"""The existing cells of one shard get the data, queries, sample, bodies
and reference that they got before the harness learned to split an index
into shards: digests pinned at the tiny sizes."""

import hashlib

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.tiny import TINY

SEED = 2718281828459

PINNED = {
    "msmarco-bm25-top1000-open":
        "2b5a40a8e74e5c964df361593ab4234ec677cce13228355af9ddef019a2d4895",
    "msmarco-knn768-top10-open":
        "3cbb918f4df7cd855ca21d62d8c00c11d911561617d2e51af39309dd5c55b2f4",
}


def _feed(h, x):
    if isinstance(x, dict):
        for k in sorted(x):
            h.update(k.encode())
            _feed(h, x[k])
    elif x is None:
        h.update(b"None")
    else:
        a = np.ascontiguousarray(x)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_one_shard_cells_read_as_before(cell):
    _, config, traffic, _ = harness.cell(cell, dict(TINY[cell]))
    body = harness.module("bodies", traffic["body"])
    params = traffic["params"]
    data = harness.make_data(config, SEED)
    assert len(data) == 1
    got = harness.inputs(traffic, body, data, params, SEED, 2.0)
    h = hashlib.sha256()
    _feed(h, data[0])
    for x in (got.warm_due, got.due, got.sample):
        _feed(h, x)
    for q in list(got.warm_qs) + list(got.qs):
        h.update(body.encode(q, params))
    for s in body.reference(data, [got.qs[i] for i in got.sample[:3]],
                            params):
        _feed(h, s)
    assert h.hexdigest() == PINNED[cell]
