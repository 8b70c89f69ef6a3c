"""``match`` on a text field: a disjunction of 1-8 terms, scored BM25.

Queries come from the copied ``make_queries`` (three df bands, trimmed to
``max_blocks`` postings blocks), fresh from the seed, with no term set
repeated in a run.
"""

from __future__ import annotations

import json

import numpy as np

from benchmark import oracle
from benchmark.corpus import BLOCK, bm25_exact, make_queries, term_name


def queries(data, params: dict, rng, n: int) -> list:
    """``n`` distinct term sets of at most ``max_blocks`` postings
    blocks. A repeat would hit the fast path's theta cache, which this
    kind leaves to a cell of its own."""
    corpus = data[params["field"]]
    out, seen = [], set()
    for _ in range(100):
        for q in make_queries(rng, corpus["df"], len(corpus["lens"]),
                              n - len(out) + 16,
                              max_blocks=params["max_blocks"]):
            # make_queries cannot trim a single term: one whose postings
            # alone exceed max_blocks (a stop word) leaves the fast path
            if (tuple(q) not in seen and len(out) < n
                    and blocks(corpus, q) <= params["max_blocks"]):
                seen.add(tuple(q))
                out.append(q)
        if len(out) == n:
            return out
    raise ValueError(f"the corpus yields fewer than {n} distinct queries")


def blocks(corpus, q) -> int:
    nb = (corpus["df"] + BLOCK - 1) // BLOCK
    return int(np.sum(nb[np.asarray(q, np.int64)]))


def encode(q, params: dict) -> bytes:
    text = " ".join(term_name(t) for t in q)
    return json.dumps({"query": {"match": {params["field"]: text}},
                       "size": params["size"],
                       "_source": False}).encode()


def reference(data, qs: list, params: dict):
    """float64 BM25 of every doc, one array per query."""
    corpus = data[params["field"]]
    for q in qs:
        yield bm25_exact(corpus, q)


def control(data, qs: list, params: dict):
    """The same scores computed in bfloat16."""
    corpus = data[params["field"]]
    for q in qs:
        yield oracle.bm25_bf16(corpus, q)


def postings(data, params: dict, q) -> int:
    """Postings the query's terms hold: the work a BM25 scorer must read."""
    df = data[params["field"]]["df"]
    return int(np.sum(df[np.asarray(q, np.int64)]))
