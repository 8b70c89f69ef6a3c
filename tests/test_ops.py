"""Kernel correctness vs scalar references (model: AbstractQueryTestCase's
round-trip discipline — every kernel is property-tested against a pure
numpy implementation, SURVEY.md §4)."""

import numpy as np
import pytest

from elasticsearch_tpu.index.mapper import MapperService
from elasticsearch_tpu.index.segment import SegmentWriter
from elasticsearch_tpu.ops import bm25 as bm25_ops
from elasticsearch_tpu.ops import topk as topk_ops
from elasticsearch_tpu.ops import vector as vec_ops
from elasticsearch_tpu.ops.device import DeviceSegment, block_bucket

VOCAB = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa"]


def random_corpus(rng, n_docs=500):
    # zipf-ish: earlier vocab words much more frequent
    probs = 1.0 / np.arange(1, len(VOCAB) + 1)
    probs /= probs.sum()
    docs = []
    for _ in range(n_docs):
        length = int(rng.integers(1, 40))
        words = rng.choice(VOCAB, size=length, p=probs)
        docs.append({"body": " ".join(words)})
    return docs


def build_device_segment(docs):
    svc = MapperService(mappings={"properties": {"body": {"type": "text"}}})
    w = SegmentWriter()
    for i, src in enumerate(docs):
        w.add(svc.parse(str(i), src))
    seg = w.build("s0")
    return seg, DeviceSegment(seg)


def test_bm25_kernel_matches_reference(rng):
    docs = random_corpus(rng)
    seg, dev = build_device_segment(docs)
    pf = seg.postings["body"]
    dp = dev.postings["body"]
    k1, b = 1.2, 0.75
    n = seg.n_docs

    query_terms = ["alpha", "gamma", "kappa", "notthere"]
    tids = [pf.term_id(t) for t in query_terms]
    idfs = [bm25_ops.idf(int(pf.doc_freq[tid]), pf.doc_count) if tid >= 0 else 0.0
            for tid in tids]

    sel, ws = dp.select_blocks(tids, idfs)
    scores = np.asarray(bm25_ops.bm25_block_scores(
        dp.block_docids, dp.block_tfs, sel, ws, dp.doc_lens,
        np.float32(dp.avg_len), k1, b))[:n]

    ref = bm25_ops.bm25_reference_scores(
        [pf.postings(t) for t in query_terms if pf.term_id(t) >= 0],
        [w for w, tid in zip(idfs, tids) if tid >= 0],
        pf.field_lengths, pf.avg_field_length, k1, b)
    np.testing.assert_allclose(scores, ref, rtol=2e-5, atol=1e-6)
    # non-matching docs are exactly zero
    matched = set()
    for t in query_terms:
        d, _ = pf.postings(t)
        matched.update(d.tolist())
    unmatched = [d for d in range(n) if d not in matched]
    assert np.all(scores[unmatched] == 0.0)


def test_bm25_topk_ordering_matches_reference(rng):
    docs = random_corpus(rng, 800)
    seg, dev = build_device_segment(docs)
    pf = seg.postings["body"]
    dp = dev.postings["body"]
    tids = [pf.term_id("alpha"), pf.term_id("beta")]
    idfs = [bm25_ops.idf(int(pf.doc_freq[t]), pf.doc_count) for t in tids]
    sel, ws = dp.select_blocks(tids, idfs)
    scores = bm25_ops.bm25_block_scores(
        dp.block_docids, dp.block_tfs, sel, ws, dp.doc_lens,
        np.float32(dp.avg_len), 1.2, 0.75)
    vals, ids = topk_ops.masked_topk(scores, dev.live & (scores > 0), 10)
    vals, ids = np.asarray(vals), np.asarray(ids)

    ref = bm25_ops.bm25_reference_scores(
        [pf.postings("alpha"), pf.postings("beta")], idfs,
        pf.field_lengths, pf.avg_field_length, 1.2, 0.75)
    order = np.lexsort((np.arange(len(ref)), -ref))[:10]
    np.testing.assert_array_equal(ids, order)
    np.testing.assert_allclose(vals, ref[order], rtol=2e-5)


def test_masked_topk_excludes_deleted_and_nonmatching(rng):
    docs = [{"body": "x common"}, {"body": "common"}, {"body": "other"}]
    seg, dev = build_device_segment(docs)
    seg.delete(0)
    dev = DeviceSegment(seg)
    pf, dp = seg.postings["body"], dev.postings["body"]
    tid = pf.term_id("common")
    sel, ws = dp.select_blocks([tid], [1.0])
    scores = bm25_ops.bm25_block_scores(
        dp.block_docids, dp.block_tfs, sel, ws, dp.doc_lens,
        np.float32(dp.avg_len), 1.2, 0.75)
    vals, ids = topk_ops.masked_topk(scores, dev.live & (scores > 0), 3)
    vals = np.asarray(vals)
    assert ids[0] == 1            # doc 0 deleted, doc 2 non-matching
    assert np.isinf(vals[1]) and vals[1] < 0
    assert np.isinf(vals[2]) and vals[2] < 0


def test_match_mask_and_count(rng):
    docs = [{"body": "a b"}, {"body": "a"}, {"body": "b c"}, {"body": "c"}]
    seg, dev = build_device_segment(docs)
    pf, dp = seg.postings["body"], dev.postings["body"]
    sel_a, _ = dp.select_blocks([pf.term_id("a")], [1.0])
    mask = np.asarray(bm25_ops.match_mask(
        dp.block_docids, dp.block_tfs, sel_a, dev.n_docs_padded))
    assert mask[:4].tolist() == [True, True, False, False]

    # two clauses: (a) and (b) — docs matching both: only doc 0
    sel_b, _ = dp.select_blocks([pf.term_id("b")], [1.0])
    sel = np.concatenate([sel_a, sel_b])
    cids = np.concatenate([np.zeros(len(sel_a), np.int32),
                           np.ones(len(sel_b), np.int32)])
    counts = np.asarray(bm25_ops.match_count(
        dp.block_docids, dp.block_tfs, sel, cids, 2, dev.n_docs_padded))
    assert counts[:4].tolist() == [2, 1, 1, 0]


def test_block_max_is_upper_bound(rng):
    docs = random_corpus(rng, 400)
    seg, dev = build_device_segment(docs)
    pf, dp = seg.postings["body"], dev.postings["body"]
    k1, b = 1.2, 0.75
    for term in ["alpha", "iota"]:
        tid = pf.term_id(term)
        w = bm25_ops.idf(int(pf.doc_freq[tid]), pf.doc_count)
        sel, ws = dp.select_blocks([tid], [w])
        bounds = np.asarray(bm25_ops.block_max_scores(
            dp.block_max_tf, dp.block_min_len, sel, ws,
            np.float32(dp.avg_len), k1, b))
        scores = np.asarray(bm25_ops.bm25_block_scores(
            dp.block_docids, dp.block_tfs, sel, ws, dp.doc_lens,
            np.float32(dp.avg_len), k1, b))
        assert scores.max() <= bounds.max() + 1e-5


def test_merge_topk_tie_break():
    va = np.array([3.0, 1.0], np.float32)
    ia = np.array([5, 7], np.int32)
    vb = np.array([3.0, 2.0], np.float32)
    ib = np.array([2, 9], np.int32)
    v, i = topk_ops.merge_topk(va, ia, vb, ib, 3)
    assert np.asarray(v).tolist() == [3.0, 3.0, 2.0]
    assert np.asarray(i).tolist() == [2, 5, 9]  # tie at 3.0 → lower id first


def test_cosine_dot_l2_match_reference(rng):
    nd, d = 200, 32
    vectors = rng.standard_normal((nd, d)).astype(np.float32)
    vectors[17] = 0.0  # zero vector edge case
    queries = rng.standard_normal((3, d)).astype(np.float32)

    # float32 path: exact parity
    import jax.numpy as jnp
    prepped, norms = vec_ops.prepare_vectors(vectors, "cosine", np.float32)
    cos = np.asarray(vec_ops.cosine_scores(queries, prepped))
    for qi in range(3):
        np.testing.assert_allclose(
            cos[qi], vec_ops.cosine_reference(queries[qi], vectors),
            rtol=1e-5, atol=1e-5)

    prepped, norms = vec_ops.prepare_vectors(vectors, "dot", np.float32)
    dots = np.asarray(vec_ops.dot_scores(queries, prepped))
    for qi in range(3):
        np.testing.assert_allclose(
            dots[qi], vec_ops.dot_reference(queries[qi], vectors), rtol=1e-4)

    l2 = np.asarray(vec_ops.l2_scores(queries, prepped, norms * norms))
    for qi in range(3):
        np.testing.assert_allclose(
            l2[qi], vec_ops.l2_reference(queries[qi], vectors),
            rtol=1e-3, atol=1e-3)


def test_bf16_cosine_recall(rng):
    """bf16 slab must preserve top-k recall ≥ 0.9 vs float32 exact."""
    nd, d = 2000, 64
    vectors = rng.standard_normal((nd, d)).astype(np.float32)
    query = rng.standard_normal((1, d)).astype(np.float32)
    prepped16, _ = vec_ops.prepare_vectors(vectors, "cosine")
    approx = np.asarray(vec_ops.cosine_scores(query, prepped16))[0]
    exact = vec_ops.cosine_reference(query[0], vectors)
    k = 100
    top_approx = set(np.argsort(-approx)[:k].tolist())
    top_exact = set(np.argsort(-exact)[:k].tolist())
    assert len(top_approx & top_exact) / k >= 0.9


def test_block_bucket():
    assert block_bucket(1) == 8
    assert block_bucket(8) == 8
    assert block_bucket(9) == 16
    assert block_bucket(1000) == 1024


def test_device_segment_padding(rng):
    docs = random_corpus(rng, 10)
    seg, dev = build_device_segment(docs)
    assert dev.n_docs_padded % 1024 == 0
    live = np.asarray(dev.live)
    assert live[: seg.n_docs].all()
    assert not live[seg.n_docs:].any()


def test_bm25_sorted_topk_batch_matches_single():
    """The batched (vmapped) kernel must agree with per-query launches
    (the continuous-batching serving path)."""
    import jax.numpy as jnp
    from elasticsearch_tpu.ops.bm25 import (bm25_sorted_topk,
                                            bm25_sorted_topk_batch)
    rng = np.random.default_rng(9)
    tb, blk, nd = 17, 8, 60
    docids = rng.integers(0, nd, size=(tb, blk)).astype(np.int32)
    tfs = rng.integers(0, 4, size=(tb, blk)).astype(np.float32)
    docids[-1] = 0
    tfs[-1] = 0.0                       # reserved zero block
    lens = rng.uniform(5, 50, nd).astype(np.float32)
    live = np.ones(nd, bool)
    sels = np.array([[0, 3, 5, 16], [1, 2, 16, 16], [7, 8, 9, 10]],
                    np.int32)
    ws = np.array([[1.0, 0.5, 0.25, 0.0], [2.0, 1.0, 0.0, 0.0],
                   [1.0, 1.0, 1.0, 1.0]], np.float32)
    k = 10
    bvals, bids = bm25_sorted_topk_batch(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sels),
        jnp.asarray(ws), jnp.asarray(lens), jnp.asarray(live),
        np.float32(lens.mean()), 1.2, 0.75, k)
    for qi in range(len(sels)):
        svals, sids = bm25_sorted_topk(
            jnp.asarray(docids), jnp.asarray(tfs),
            jnp.asarray(sels[qi]), jnp.asarray(ws[qi]),
            jnp.asarray(lens), jnp.asarray(live),
            np.float32(lens.mean()), 1.2, 0.75, k)
        np.testing.assert_allclose(np.asarray(bvals[qi]),
                                   np.asarray(svals), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(bids[qi]),
                                      np.asarray(sids))
