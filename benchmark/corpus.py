"""Synthetic MS MARCO-passage-shaped corpus and its exact float64 BM25.

A copy of ``elasticsearch_tpu/bench/corpus.py`` (PR 21), kept here so that
no program PR can move the yardstick: Zipf(1.07) terms over a
``vocab``-term dictionary, lognormal doc lengths around 40 tokens, and a
geometric burst process that gives tf a heavy tail (real text repeats its
topical words). ``bm25_exact`` scores in float64 straight from the
postings and imports nothing of the program.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

BLOCK = 128
K1, B = 1.2, 0.75


def build_corpus(rng, n_docs: int, vocab: int, avg_len: int = 40,
                 burst: float = 0.35,
                 zipf: float = 1.07) -> Dict[str, np.ndarray]:
    """Postings of ``n_docs`` synthetic docs, sorted by (term, doc), plus
    the padded block layout (one reserved all-zero block at the end)."""
    lens = np.clip(rng.lognormal(np.log(avg_len), 0.4, n_docs),
                   5, 200).astype(np.int32)
    total = int(lens.sum())
    u = rng.random(total)
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -zipf)
    cdf /= cdf[-1]
    terms = np.searchsorted(cdf, u).astype(np.int64)
    doc_of = np.repeat(np.arange(n_docs, dtype=np.int64), lens)
    if burst > 0:
        # each token repeats the previous token of the SAME doc with
        # probability ``burst``
        copy = rng.random(total) < burst
        doc_start = np.zeros(total, bool)
        doc_start[0] = True
        doc_start[np.cumsum(lens)[:-1]] = True
        copy &= ~doc_start
        src = np.where(~copy, np.arange(total), 0)
        np.maximum.accumulate(src, out=src)
        terms = terms[src]
    keys = terms * n_docs + doc_of
    del terms, doc_of, u
    uniq, tf = np.unique(keys, return_counts=True)
    del keys
    term_of = (uniq // n_docs).astype(np.int32)
    doc_ids = (uniq % n_docs).astype(np.int32)
    del uniq
    tf = tf.astype(np.float32)
    n_postings = len(doc_ids)

    df = np.bincount(term_of, minlength=vocab)
    nb = (df + BLOCK - 1) // BLOCK
    tbs = np.zeros(vocab + 1, np.int64)
    np.cumsum(nb, out=tbs[1:])
    total_blocks = int(tbs[-1]) + 1   # +1 reserved zero block

    group_start = np.zeros(vocab + 1, np.int64)
    np.cumsum(df, out=group_start[1:])
    rank_in_term = (np.arange(n_postings, dtype=np.int64)
                    - group_start[term_of])
    dest = tbs[term_of] * BLOCK + rank_in_term
    block_docids = np.zeros(total_blocks * BLOCK, np.int32)
    block_tfs = np.zeros(total_blocks * BLOCK, np.float32)
    block_docids[dest] = doc_ids
    block_tfs[dest] = tf
    del dest, rank_in_term
    return dict(block_docids=block_docids.reshape(total_blocks, BLOCK),
                block_tfs=block_tfs.reshape(total_blocks, BLOCK),
                tbs=tbs, nb=nb, df=df, lens=lens.astype(np.float32),
                doc_ids=doc_ids, tf=tf, group_start=group_start,
                n_postings=n_postings)


def make_queries(rng, shards: Sequence[dict], n_queries: int,
                 max_blocks: int = 4096) -> List[List[int]]:
    """Up to ``n_queries`` queries of 1-8 distinct terms drawn across the
    df bands (rare → common) of the index that the corpora ``shards``
    make up, one per primary shard. Each is trimmed of its most common
    terms until no shard needs more than ``max_blocks`` postings blocks
    for it; one left with a single term that alone needs more (a stop
    word) is dropped."""
    df = sum(c["df"] for c in shards)
    n_docs = sum(len(c["lens"]) for c in shards)
    nb = np.stack([c["nb"] for c in shards])
    most = nb.max(axis=0)
    bands = [
        np.nonzero((df > 200) & (df <= n_docs // 100))[0],
        np.nonzero((df > n_docs // 100) & (df <= n_docs // 20))[0],
        np.nonzero(df > n_docs // 20)[0],
    ]
    bands = [b for b in bands if len(b) > 0]
    queries = []
    for _ in range(n_queries):
        n_terms = int(rng.integers(1, 9))
        terms = []
        for _ in range(n_terms):
            band = bands[min(int(rng.integers(0, len(bands))),
                             len(bands) - 1)]
            terms.append(int(rng.choice(band)))
        q = sorted(set(terms))
        while len(q) > 1 and int(nb[:, q].sum(axis=1).max()) > max_blocks:
            q.remove(max(q, key=lambda t: int(most[t])))
        if int(nb[:, q].sum(axis=1).max()) <= max_blocks:
            queries.append(q)
    return queries


def term_name(t: int) -> str:
    return f"t{t:06d}"


def bm25_exact(corpus, terms: Sequence[int]) -> np.ndarray:
    """float64 BM25 of every doc for a disjunction of ``terms``."""
    lens = corpus["lens"].astype(np.float64)
    gs, d_all, tf_all = (corpus["group_start"], corpus["doc_ids"],
                         corpus["tf"])
    n = len(lens)
    norm = K1 * (1.0 - B + B * lens / lens.mean())
    scores = np.zeros(n, np.float64)
    for t in terms:
        d = d_all[gs[t]:gs[t + 1]]
        f = tf_all[gs[t]:gs[t + 1]].astype(np.float64)
        df = len(d)
        if df == 0:
            continue
        idf = np.log1p((n - df + 0.5) / (df + 0.5))
        scores[d] += idf * f / (f + norm[d])
    return scores
