"""The one adapter that reaches into the program's internals.

Indexing 2,000,000 docs through ``_bulk`` takes longer than a run may
(PR 21 measured ~6.4k docs/s text-only and ~680 docs/s with vectors), so
the benchmark lays its generated data out as one refreshed, read-only
``Segment`` and swaps it into the index's engine, as ``chip_smoke.mount``
does (PR 21). The engine then serves it like any refreshed segment. Also
here: the wait for the fast path's registration, the kNN warm-up, and the
counters that have no public surface yet (``KnnBatcher``). A public
mount entry and public batcher counters are program changes for a later
PR (PERF.md, Open questions).
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from benchmark.corpus import BLOCK, term_name


def _postings_field(field: str, terms: List[str], df: np.ndarray,
                    tbs: np.ndarray, block_docids: np.ndarray,
                    block_tfs: np.ndarray, lens: np.ndarray):
    from elasticsearch_tpu.index.segment import PostingsField
    ln = lens[block_docids]
    ln[block_tfs == 0] = np.inf
    mn = ln.min(axis=1)
    return PostingsField(
        field=field, terms=terms, doc_freq=df.astype(np.int32),
        total_term_freq=np.bincount(
            np.repeat(np.arange(len(df)), np.diff(tbs)),
            weights=block_tfs.sum(axis=1),
            minlength=len(df)).astype(np.int64),
        term_block_start=tbs[:-1].astype(np.int32),
        term_block_count=np.diff(tbs).astype(np.int32),
        block_docids=block_docids, block_tfs=block_tfs,
        block_max_tf=block_tfs.max(axis=1).astype(np.float32),
        block_min_len=np.where(np.isfinite(mn), mn, 0.0).astype(np.float32),
        field_lengths=lens, sum_total_term_freq=int(lens.sum()),
        sum_doc_freq=int(df.sum()), doc_count=int((lens > 0).sum()))


def _text(field: str, corpus, blocks: int):
    """The text field, its postings padded to the fixed count ``blocks``
    so that every seed hands the device the same shapes and a run finds
    every kernel in the compile cache. The program wants each block to
    belong to a term, so the padding is the postings of one more term,
    ``term_name(vocab)``, that no doc holds and no query names."""
    vocab = len(corpus["df"])
    used = int(corpus["tbs"][-1])
    if used > blocks:
        raise ValueError(f"{used} postings blocks exceed the fixed "
                         f"{blocks} of the configuration")
    bd = np.zeros((blocks, BLOCK), np.int32)
    bt = np.zeros((blocks, BLOCK), np.float32)
    bd[:used] = corpus["block_docids"][:used]
    bt[:used] = corpus["block_tfs"][:used]
    return _postings_field(
        field, [term_name(t) for t in range(vocab + 1)],
        np.append(corpus["df"], 0), np.append(corpus["tbs"], blocks),
        bd, bt, corpus["lens"])


def _keyword(field: str, values: List[str], ords: np.ndarray):
    from elasticsearch_tpu.index.segment import KeywordDocValues
    n = len(ords)
    docs = np.argsort(ords, kind="stable").astype(np.int32)
    df = np.bincount(ords, minlength=len(values))
    nb = (df + BLOCK - 1) // BLOCK
    tbs = np.zeros(len(values) + 1, np.int64)
    np.cumsum(nb, out=tbs[1:])
    start = np.zeros(len(values) + 1, np.int64)
    np.cumsum(df, out=start[1:])
    o = ords[docs]
    dest = tbs[o] * BLOCK + (np.arange(n) - start[o])
    bd = np.zeros(int(tbs[-1]) * BLOCK, np.int32)
    bt = np.zeros(int(tbs[-1]) * BLOCK, np.float32)
    bd[dest] = docs
    bt[dest] = 1.0
    pf = _postings_field(field, list(values), df, tbs,
                         bd.reshape(-1, BLOCK), bt.reshape(-1, BLOCK),
                         np.ones(n, np.float32))
    kv = KeywordDocValues(field, list(values), ords.astype(np.int32),
                          np.arange(n + 1, dtype=np.int64),
                          ords.astype(np.int32))
    return pf, kv


def segment(name: str, n: int, fields: Dict[str, dict],
            data: Dict[str, object], id_base: int):
    """One ``Segment`` of ``n`` docs holding every field of the
    configuration (``fields``: name -> spec, ``data``: name -> what the
    field's builder made), with ids ``id_base`` .. ``id_base + n - 1``
    and no stored source."""
    from elasticsearch_tpu.index.segment import (Segment, StoredFields,
                                                 VectorValues)
    from benchmark.fields import keyword
    postings, keywords, vectors = {}, {}, {}
    for f, spec in fields.items():
        kind = spec["type"]
        if kind == "text":
            postings[f] = _text(f, data[f], spec["blocks"])
        elif kind == "keyword":
            postings[f], keywords[f] = _keyword(f, keyword.values(spec),
                                                data[f])
        elif kind == "dense_vector":
            vectors[f] = VectorValues(f, data[f], np.ones(n, bool),
                                      spec["dims"], spec["similarity"])
        else:
            raise ValueError(f"no mount for field type {kind!r}")
    stored = StoredFields(offsets=np.zeros(n + 1, np.int64), data=b"",
                          ids=[str(id_base + i) for i in range(n)])
    return Segment(name, n, postings=postings, numerics={},
                   keywords=keywords, vectors=vectors, stored=stored)


def mount(node, index: str, segs: List) -> None:
    """Swap each primary shard ``s`` of the index onto ``segs[s]``."""
    shards = node.indices_service.get(index).shards
    if len(shards) != len(segs):
        raise ValueError(f"{index!r} has {len(shards)} shards, "
                         f"{len(segs)} segments to mount")
    for eng, seg in zip(shards, segs):
        with eng._lock:
            eng._segments = [seg]
            eng._epoch += 1


def fast_path(node):
    return getattr(getattr(node, "_http", None), "fastpath", None)


def wait_fast_path(node, index: str, timeout_s: float) -> None:
    """Block until the fast path has registered ``index`` (it registers
    only after its kernel shapes are warm)."""
    fp = fast_path(node)
    if fp is None:
        raise RuntimeError("no native front / FastPathServer serves")
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        reg = fp._reg
        if reg is not None and reg["index"] == index:
            return
        time.sleep(0.1)
    raise RuntimeError(f"fast path did not register {index!r} in "
                       f"{timeout_s:.0f} s")


def warm_knn(node, index: str, field: str, num_candidates: int) -> None:
    """Compile (or load from the compile cache) the kNN cohort kernel at
    every Q bucket of the one cut bucket that ``num_candidates`` selects,
    through the batcher's own launch path."""
    from elasticsearch_tpu.search import batching
    idx = node.indices_service.get(index)
    seg = idx.shards[0].segments[0]
    dev = idx.device_cache.get(seg)
    dv = dev.vectors[field]
    cut = min(batching._cut_bucket(num_candidates),
              int(dv.vectors.shape[0]))
    kb = node.search_service.knn_batcher
    q = np.ones(dv.vectors.shape[1], np.float32)
    for b in batching._Q_BUCKETS:
        entries = [batching._KnnEntry(q, cut, profiled=False, t_enq=0,
                                      t_fr=0.0, tenant=None, wclass=None)
                   for _ in range(b)]
        kb._run(entries, dv, dev.live, cut)


def counters(node) -> Dict[str, int]:
    """In-process counters without a public surface: the kNN batcher's
    launches and the queries they carried."""
    kb = node.search_service.knn_batcher
    return {"knn_launches": kb.launches,
            "knn_batched_queries": kb.batched_queries}
