"""Plan-compiler / fused-kernel tests: the serving fast path must agree
exactly with the dense executor (the AbstractQueryTestCase discipline —
every plannable query class is property-checked both ways)."""

import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.index.mapper import MapperService
from elasticsearch_tpu.index.segment import SegmentWriter
from elasticsearch_tpu.ops import bm25 as bm25_ops
from elasticsearch_tpu.ops import plan as plan_ops
from elasticsearch_tpu.search.context import DeviceSegmentCache
from elasticsearch_tpu.search.plan import compile_plan
from elasticsearch_tpu.search.queries import parse_query
from elasticsearch_tpu.search.searcher import ShardSearcher

MAPPINGS = {
    "properties": {
        "title": {"type": "text"},
        "body": {"type": "text"},
        "tag": {"type": "keyword"},
        "views": {"type": "long"},
    }
}

VOCAB = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "eta",
         "theta", "iota", "kappa", "wolf", "fox", "dog", "cat", "bird",
         "fish", "tree", "rock", "lake", "hill"]
TAGS = ["red", "green", "blue", "yellow"]


@pytest.fixture(scope="module")
def searcher():
    rng = np.random.default_rng(7)
    svc = MapperService(mappings=MAPPINGS)
    segments = []
    doc_no = 0
    for seg_i in range(3):
        w = SegmentWriter()
        for _ in range(rng.integers(40, 120)):
            n_title = int(rng.integers(1, 8))
            n_body = int(rng.integers(2, 20))
            doc = {
                "title": " ".join(rng.choice(VOCAB, n_title)),
                "body": " ".join(rng.choice(VOCAB, n_body)),
                "tag": str(rng.choice(TAGS)),
                "views": int(rng.integers(0, 100)),
            }
            w.add(svc.parse(str(doc_no), doc))
            doc_no += 1
        segments.append(w.build(f"s{seg_i}"))
    return ShardSearcher(segments, svc, DeviceSegmentCache())


def both_ways(searcher, body, size=10, post_filter=None):
    query = parse_query(body)
    fast = searcher.query_phase(query, size, post_filter=post_filter)
    # collect_masks forces the dense executor (aggs need full masks)
    dense = searcher.query_phase(query, size, post_filter=post_filter,
                                 collect_masks=True)
    return fast, dense


def assert_agree(searcher, body, size=500, post_filter=None,
                 require_plan=True):
    """Same doc set, same per-doc scores, both orderings score-descending.

    Exact sequence equality is NOT required: the two paths sum float32
    contributions in different orders (segmented cumsum vs scatter-add),
    so near-ties may swap — with size ≥ corpus both must return the same
    full set."""
    if require_plan:
        query = parse_query(body).rewrite(searcher)
        assert compile_plan(query, searcher, post_filter) is not None, body
    fast, dense = both_ways(searcher, body, size, post_filter)
    f = {(d.segment_idx, d.docid): d.score for d in fast.docs}
    e = {(d.segment_idx, d.docid): d.score for d in dense.docs}
    assert set(f) == set(e), (body, set(f) ^ set(e))
    for key in f:
        assert f[key] == pytest.approx(e[key], rel=2e-4, abs=1e-5), (body, key)
    for res in (fast, dense):
        ss = [d.score for d in res.docs]
        assert all(a >= b - 1e-6 for a, b in zip(ss, ss[1:])), body
    assert fast.total_hits == dense.total_hits, body
    if fast.docs:
        assert fast.max_score == pytest.approx(dense.max_score, rel=2e-4)


CASES = [
    {"match": {"title": "alpha wolf"}},
    {"match": {"body": {"query": "alpha beta gamma", "operator": "and"}}},
    {"match": {"body": {"query": "alpha beta gamma delta",
                        "minimum_should_match": 2}}},
    {"match": {"body": {"query": "alpha beta gamma delta",
                        "minimum_should_match": "75%"}}},
    {"term": {"tag": "red"}},
    {"term": {"title": "fox"}},
    {"terms": {"tag": ["red", "blue"]}},
    {"multi_match": {"query": "wolf lake", "fields": ["title", "body"]}},
    {"multi_match": {"query": "wolf lake", "fields": ["title", "body"],
                     "type": "most_fields"}},
    {"multi_match": {"query": "wolf lake", "fields": ["title", "body"],
                     "tie_breaker": 0.3}},
    {"dis_max": {"queries": [{"match": {"title": "alpha"}},
                             {"match": {"body": "wolf fox"}}],
                 "tie_breaker": 0.5}},
    {"constant_score": {"filter": {"term": {"tag": "green"}}, "boost": 2.0}},
    {"bool": {"must": [{"match": {"title": "alpha beta"}}],
              "filter": [{"term": {"tag": "red"}}]}},
    {"bool": {"must": [{"match": {"body": "wolf"}}],
              "must_not": [{"term": {"tag": "blue"}}]}},
    {"bool": {"should": [{"match": {"title": "alpha"}},
                         {"match": {"body": "fox dog"}}],
              "minimum_should_match": 1}},
    {"bool": {"should": [{"match": {"title": "alpha"}},
                         {"match": {"body": "fox"}},
                         {"term": {"tag": "red"}}],
              "minimum_should_match": 2}},
    {"bool": {"must": [{"match": {"body": "lake hill rock"}}],
              "filter": [{"range": {"views": {"gte": 20, "lt": 80}}}]}},
    {"bool": {"must": [{"match": {"title": "wolf"}},
                       {"match": {"body": "alpha"}}],
              "filter": [{"term": {"tag": "red"}},
                         {"range": {"views": {"gte": 10}}}],
              "must_not": [{"term": {"tag": "yellow"}},
                           {"range": {"views": {"gte": 95}}}]}},
    {"bool": {"must": [{"match": {"title": "fox"}}],
              "should": [{"match": {"body": "alpha"}},
                         {"match": {"body": "beta"}}]}},
    {"bool": {"filter": [{"match": {"body": {"query": "alpha beta",
                                             "operator": "and"}}}]}},
    {"match": {"title": {"query": "wolf fox", "boost": 2.5}}},
    {"bool": {"must": [{"match": {"title": "wolf"}},
                       {"range": {"views": {"gte": 5}}}]}},
]


@pytest.mark.parametrize("body", CASES, ids=[str(i) for i in range(len(CASES))])
def test_plan_matches_dense(searcher, body):
    assert_agree(searcher, body)


def test_post_filter_folds(searcher):
    assert_agree(searcher, {"match": {"body": "wolf fox"}},
                 post_filter=parse_query({"term": {"tag": "red"}}))


def test_non_plannable_falls_back(searcher):
    # scripts and nested bools use the dense executor
    for body in [
        {"match_all": {}},
        {"bool": {"must": [{"bool": {"must": [
            {"match": {"title": "wolf"}}]}}]}},
        {"range": {"views": {"gte": 5}}},
    ]:
        query = parse_query(body).rewrite(searcher)
        assert compile_plan(query, searcher) is None, body
        # and the dense path still answers
        res = searcher.query_phase(query, 5)
        assert res is not None


def test_negative_boost_falls_back(searcher):
    query = parse_query({"match": {"title": {"query": "wolf",
                                             "boost": -2.0}}})
    assert compile_plan(query.rewrite(searcher), searcher) is None


def test_track_total_hits_false(searcher):
    query = parse_query({"match": {"title": "wolf"}}).rewrite(searcher)
    res = searcher.query_phase(query, 5, track_total_hits=False)
    assert res.total_hits == 0  # same contract as the dense executor


def test_search_after_score_stays_on_plan(searcher):
    """_score-cursor paging walks the full result set exactly once."""
    query = parse_query({"match": {"body": "alpha wolf fox"}})
    full = searcher.query_phase(query, 500)
    everything = [(d.segment_idx, d.docid) for d in full.docs]
    walked = []
    cursor = None
    while True:
        res = searcher.query_phase(query, 7, search_after=cursor)
        if not res.docs:
            break
        walked.extend((d.segment_idx, d.docid) for d in res.docs)
        cursor = [res.docs[-1].score]
    # ties on the cursor score are excluded by search_after semantics
    # (reliable tie paging requires a _doc tiebreaker), so walked is a
    # subset in order; with distinct scores it is the exact sequence
    assert len(walked) == len(set(walked))
    assert set(walked) <= set(everything)
    assert walked == [e for e in everything if e in set(walked)]


def test_plan_large_k(searcher):
    # k larger than the query's total postings: kernel pads with -inf
    assert_agree(searcher, {"match": {"title": "alpha"}}, size=2000)


def test_sorted_dense_builders_match_scatter(rng):
    """The scatter-free dense builders agree with the scatter originals."""
    n_docs, n_blocks, B = 512, 24, 128
    docids = rng.integers(0, n_docs, size=(n_blocks, B)).astype(np.int32)
    docids.sort(axis=1)
    tfs = rng.integers(0, 4, size=(n_blocks, B)).astype(np.float32)
    zero = np.zeros((1, B))
    docids = np.concatenate([docids, zero.astype(np.int32)])
    tfs = np.concatenate([tfs, zero.astype(np.float32)])
    lens = rng.integers(1, 50, size=n_docs).astype(np.float32)
    sel = np.array([0, 3, 5, 7, 9, 11, 24, 24], np.int32)
    ws = np.array([1.5, 1.1, 0.7, 0.5, 0.9, 1.3, 0.0, 0.0], np.float32)
    avg = jnp.float32(lens.mean())

    ref = bm25_ops.bm25_block_scores(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.asarray(ws), jnp.asarray(lens), avg, 1.2, 0.75)
    got = plan_ops.bm25_dense_scores_sorted(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.asarray(ws), jnp.asarray(lens), avg, 1.2, 0.75)
    # summation order differs (segmented cumsum vs scatter-add): float32
    # associativity tolerance
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=1e-5)

    cids = np.array([0, 0, 1, 1, 2, 2, 3, 3], np.int32)
    ref_c = bm25_ops.match_count(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.asarray(cids), 4, n_docs)
    got_c = plan_ops.match_count_sorted(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.asarray(cids), jnp.zeros(n_docs, bool))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(ref_c))

    ref_m = bm25_ops.match_mask(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel), n_docs)
    got_m = plan_ops.match_mask_sorted(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.zeros(n_docs, bool))
    np.testing.assert_array_equal(np.asarray(got_m), np.asarray(ref_m))


def test_dense_scores_over_32_terms(rng):
    """A doc matched by >32 term instances keeps EVERY contribution
    (advisor r3 high: the fixed 32-step scan cap silently dropped all
    but the last 32 — callers now pass scan_run_bound(n_terms))."""
    from elasticsearch_tpu.ops.bm25 import scan_run_bound
    n_docs, B, n_terms = 64, 128, 40
    # every term's single block hits every doc once
    base = np.tile(np.arange(n_docs, dtype=np.int32), B // n_docs)
    base.sort()
    docids = np.tile(base, (n_terms, 1))
    tfs = np.ones((n_terms, B), np.float32)
    lens = np.full(n_docs, float(B // n_docs), np.float32)
    sel = np.arange(n_terms, dtype=np.int32)
    ws = np.linspace(0.5, 2.0, n_terms).astype(np.float32)
    avg = jnp.float32(lens.mean())
    got = plan_ops.bm25_dense_scores_sorted(
        jnp.asarray(docids), jnp.asarray(tfs), jnp.asarray(sel),
        jnp.asarray(ws), jnp.asarray(lens), avg, 1.2, 0.75,
        max_run=scan_run_bound(n_terms * (B // n_docs)))
    ref = bm25_ops.bm25_reference_scores(
        [(docids[t], tfs[t]) for t in range(n_terms)], ws, lens,
        float(lens.mean()), 1.2, 0.75)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=3e-4)
    assert scan_run_bound(16) == 32
    assert scan_run_bound(33) == 64
    assert scan_run_bound(100) == 128


def test_randomized_plan_vs_dense(searcher):
    """Fuzz: random plannable query trees agree with the dense executor."""
    rng = np.random.default_rng(11)

    def rand_match(field):
        n = int(rng.integers(1, 4))
        spec = {"query": " ".join(rng.choice(VOCAB, n))}
        r = rng.random()
        if r < 0.25:
            spec["operator"] = "and"
        elif r < 0.5 and n > 1:
            spec["minimum_should_match"] = int(rng.integers(1, n + 1))
        return {"match": {field: spec}}

    def rand_leaf():
        r = rng.random()
        if r < 0.5:
            return rand_match(str(rng.choice(["title", "body"])))
        if r < 0.7:
            return {"term": {"tag": str(rng.choice(TAGS))}}
        return {"terms": {"tag": [str(t) for t in
                                  rng.choice(TAGS, 2, replace=False)]}}

    for trial in range(30):
        body = {"bool": {}}
        b = body["bool"]
        if rng.random() < 0.8:
            b["must"] = [rand_leaf() for _ in range(rng.integers(1, 3))]
        if rng.random() < 0.5:
            b["filter"] = [rand_leaf()]
        if rng.random() < 0.4:
            b["filter"] = b.get("filter", []) + [
                {"range": {"views": {"gte": int(rng.integers(0, 60))}}}]
        if rng.random() < 0.4:
            b["must_not"] = [rand_leaf()]
        if rng.random() < 0.5:
            b["should"] = [rand_leaf() for _ in range(rng.integers(1, 3))]
        if not b:
            b["must"] = [rand_leaf()]
        if not any(k in b for k in ("must", "filter")) or rng.random() < 0.2:
            if "should" in b:
                b["minimum_should_match"] = int(
                    rng.integers(1, len(b["should"]) + 1))
        # full-window: truncated top-k may cut exact const-score ties in a
        # different (both-valid) order at the k boundary
        assert_agree(searcher, body, require_plan=False)


def test_script_score_rides_the_plan_path(searcher):
    """Expression-tier script_score compiles into the kernel (BASELINE
    config 3 on the batched path) and agrees with the dense executor."""
    body = {"script_score": {
        "query": {"match": {"title": "alpha beta"}},
        "script": {"source": "doc['views'].value * 0.5 + _score"}}}
    q2 = parse_query(body).rewrite(searcher)
    plan = compile_plan(q2, searcher)
    assert plan is not None and plan.script is not None
    assert_agree(searcher, body)


def test_script_score_with_params_and_functions(searcher):
    body = {"script_score": {
        "query": {"bool": {"must": [{"match": {"title": "wolf"}}],
                           "filter": [{"term": {"tag": "red"}}]}},
        "script": {
            "source": "saturation(doc['views'].value, params.pivot) "
                      "+ Math.log(1 + _score)",
            "params": {"pivot": 10}}}}
    q2 = parse_query(body).rewrite(searcher)
    assert compile_plan(q2, searcher) is not None
    assert_agree(searcher, body)


def test_statement_script_score_falls_back_dense(searcher):
    """Loop/statement scripts interpret per doc — NOT plannable."""
    body = {"script_score": {
        "query": {"match": {"title": "alpha"}},
        "script": {"source": """
            double s = 0;
            for (int i = 0; i < 2; i++) { s += doc['views'].value; }
            return s + _score;
        """}}}
    q2 = parse_query(body).rewrite(searcher)
    assert compile_plan(q2, searcher) is None
    assert_agree(searcher, body, require_plan=False)


def test_script_score_min_score_falls_back(searcher):
    body = {"script_score": {
        "query": {"match": {"title": "alpha"}},
        "script": {"source": "_score * 2"},
        "min_score": 1.5}}
    q2 = parse_query(body).rewrite(searcher)
    assert compile_plan(q2, searcher) is None
    assert_agree(searcher, body, require_plan=False)


# ---------------------------------------------------------------------------
# float-pack id invariant (ops/plan.py pack_result: ids ride readbacks
# as float32 casts, exact only < 2^24)
# ---------------------------------------------------------------------------

def test_check_packed_id_limit_boundary():
    plan_ops.check_packed_id_limit(plan_ops.PACKED_ID_LIMIT - 1, "ok")
    with pytest.raises(ValueError, match="2\\^24"):
        plan_ops.check_packed_id_limit(plan_ops.PACKED_ID_LIMIT, "boom")


def test_device_segment_build_enforces_pack_limit(monkeypatch):
    """The invariant is enforced LOUDLY at device-postings build time,
    not as silent wraparound in a later readback."""
    from elasticsearch_tpu.ops.device import DeviceSegment
    svc = MapperService(mappings=MAPPINGS)
    w = SegmentWriter()
    w.add(svc.parse("0", {"title": "alpha"}))
    seg = w.build("packlimit0")
    monkeypatch.setattr(plan_ops, "PACKED_ID_LIMIT", 64)  # < DOC_PAD
    with pytest.raises(ValueError, match="float32-packed"):
        DeviceSegment(seg)


def test_segsum_keeps_run_precision_over_a_long_selection():
    """The plan kernel's segmented sums stay accurate to the run's own
    scale over a corpus-scale selection (a plain float32 cumsum minus
    the run-start prefix lost ~1e-4 relative and reordered boundary
    docs at 2M docs)."""
    import jax

    from elasticsearch_tpu.ops import plan as plan_ops

    rng = np.random.default_rng(0)
    p = 1 << 19
    x = (rng.random(p) * 5).astype(np.float32)
    start = rng.random(p) < 0.2
    start[0] = True
    cs = np.cumsum(x.astype(np.float64))
    run = np.cumsum(start) - 1
    base = np.concatenate([[0.0], cs])[np.nonzero(start)[0]][run]
    exact = cs - base
    got = np.asarray(jax.jit(plan_ops._segsum)(x, start), np.float64)
    assert np.max(np.abs(got - exact)) < 1e-5
    big = exact > 0.5
    assert np.max(np.abs(got - exact)[big] / exact[big]) < 2.5e-7
