"""``match`` on a text field: a disjunction of 1-8 terms, scored BM25.

Queries come from the copied ``make_queries`` (three df bands of the
index-wide df, trimmed until no shard needs more than ``max_blocks``
postings blocks), fresh from the seed, with no term set repeated in a
run.

Each primary shard scores with its own statistics (df, doc count,
average length), as Elasticsearch's ``query_then_fetch`` does. The
reference's scores are laid out shard-major, one shard after the other,
so that ``oracle.topk`` breaks ties in (shard, docid) order: the order in
which the shards' top hits merge.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import oracle
from benchmark.corpus import bm25_exact, make_queries, term_name


def queries(data, params: dict, rng, n: int) -> list:
    """``n`` distinct term sets of at most ``max_blocks`` postings blocks
    in any shard. A repeat would hit the fast path's theta cache, which
    this kind leaves to a cell of its own."""
    shards = [d[params["field"]] for d in data]
    out, seen = [], set()
    for _ in range(100):
        for q in make_queries(rng, shards, n - len(out) + 16,
                              max_blocks=params["max_blocks"]):
            if tuple(q) not in seen and len(out) < n:
                seen.add(tuple(q))
                out.append(q)
        if len(out) == n:
            return out
    raise ValueError(f"the corpus yields fewer than {n} distinct queries")


def encode(q, params: dict) -> bytes:
    text = " ".join(term_name(t) for t in q)
    return json.dumps({"query": {"match": {params["field"]: text}},
                       "size": params["size"],
                       "_source": False}).encode()


def reference(data, qs: list, params: dict):
    """float64 BM25 of every doc, one shard-major array per query."""
    for q in qs:
        yield np.concatenate([bm25_exact(d[params["field"]], q)
                              for d in data])


def control(data, qs: list, params: dict):
    """The same scores computed in bfloat16."""
    for q in qs:
        yield np.concatenate([oracle.bm25_bf16(d[params["field"]], q)
                              for d in data])


def postings(data, params: dict, q) -> int:
    """Postings the query's terms hold: the work a BM25 scorer must read."""
    q = np.asarray(q, np.int64)
    return int(sum(np.sum(d[params["field"]]["df"][q]) for d in data))
