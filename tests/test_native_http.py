"""Native HTTP front + fast path (rest/native_http.py, search/fastpath.py,
native/src/estpu_http.cpp).

The contract under test: the C++ fast path is an OPTIMIZATION, never a
semantic fork — every fast-served response must match what the Python
path returns for the same body (ids, scores, totals), and everything the
fast parser rejects must flow through the fallback unchanged (ref: the
reference's netty front is transparent to RestController semantics,
Netty4HttpServerTransport.java)."""

import json
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest import native_http

pytestmark = pytest.mark.skipif(not native_http.available(),
                                reason="native http front unavailable")

WORDS = ["alpha", "beta", "gamma", "delta", "epsilon", "zeta", "fox",
         "dog", "cat", "bird", "fish", "lion"]


@pytest.fixture()
def served(tmp_path):
    # small kernel shapes: the CPU backend executes these for real, and a
    # (32, 4096·128) sort per cohort would make the suite crawl
    node = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "64,128",
                            "fast_max_k": 200}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    assert isinstance(node._http, native_http.NativeHttpFront), \
        "native front should win on a plain node"
    rng = np.random.default_rng(42)
    lines = []
    for i in range(300):
        doc = " ".join(rng.choice(WORDS, size=int(rng.integers(3, 12))))
        lines.append(json.dumps({"index": {"_index": "books",
                                           "_id": str(i)}}))
        lines.append(json.dumps({"title": doc}))
    req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
    req(port, "POST", "/books/_refresh")
    # deterministic fast-path registration (the drain loop would get
    # there within a second; tests shouldn't sleep)
    node._http.fastpath.refresh_registration()
    assert node._http.fastpath._reg is not None
    yield node, port
    node.close()


def req(port, method, path, body=None, ndjson=False, headers=None,
        raw=False):
    if body is None:
        data = None
    elif isinstance(body, str):
        data = body.encode()
    else:
        data = json.dumps(body).encode()
    h = {"Content-Type":
         "application/x-ndjson" if ndjson else "application/json"}
    h.update(headers or {})
    r = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                               data=data, method=method, headers=h)
    with urllib.request.urlopen(r) as resp:
        payload = resp.read()
        return payload if raw else (json.loads(payload) if payload
                                    else None)


def hits_of(resp):
    return [(h["_id"], h["_score"]) for h in resp["hits"]["hits"]]


def assert_equivalent(fast, slow):
    """Same totals; positionwise scores equal to float32 noise; a doc-id
    difference is only acceptable between near-tied scores — the two
    paths sum float32 contributions in different orders (tree-order
    segmented scan vs sequential dense add), so last-ulp rounding can
    swap docs at a tie boundary, never move a clearly-better doc."""
    assert fast["hits"]["total"] == slow["hits"]["total"]
    fh, sh = hits_of(fast), hits_of(slow)
    assert len(fh) == len(sh)
    f_sorted = sorted(fh, key=lambda x: (-x[1], int(x[0])))
    s_sorted = sorted(sh, key=lambda x: (-x[1], int(x[0])))
    for (fi, fs), (si, ss) in zip(f_sorted, s_sorted):
        assert fs == pytest.approx(ss, rel=2e-3)
        if fi != si:
            assert abs(fs - ss) <= 2e-3 * max(1.0, abs(fs)), \
                (fi, fs, si, ss)


def dispatch(node, body):
    status, resp = node.rest_controller.dispatch(
        "POST", "/books/_search", None, body)
    assert status == 200
    return resp


def fast_count(node):
    return node._http.stats()["fast"]


def test_match_identity_and_fast_served(served):
    node, port = served
    for text, size in [("fox gamma", 20), ("alpha", 5),
                       ("fox dog cat bird", 100), ("zeta zeta", 10)]:
        body = {"query": {"match": {"title": text}}, "size": size,
                "_source": False}
        before = fast_count(node)
        fast = req(port, "POST", "/books/_search", body)
        assert fast_count(node) == before + 1, f"not fast-served: {text}"
        assert_equivalent(fast, dispatch(node, body))


def test_bool_filter_identity(served):
    node, port = served
    body = {"query": {"bool": {
        "must": [{"match": {"title": "fox gamma"}}],
        "filter": [{"match": {"title": "dog"}},
                   {"match": {"title": "cat"}}]}},
        "size": 50, "_source": False}
    before = fast_count(node)
    fast = req(port, "POST", "/books/_search", body)
    assert fast_count(node) == before + 1
    assert_equivalent(fast, dispatch(node, body))


def test_unknown_terms_and_empty(served):
    node, port = served
    body = {"query": {"match": {"title": "qqqqq zzzzz"}}, "size": 10,
            "_source": False}
    fast = req(port, "POST", "/books/_search", body)
    assert fast["hits"]["total"]["value"] == 0
    assert fast["hits"]["hits"] == []
    assert fast["hits"]["max_score"] is None
    # mixed known/unknown term must still score the known one
    body2 = {"query": {"match": {"title": "qqqqq fox"}}, "size": 10,
             "_source": False}
    assert_equivalent(req(port, "POST", "/books/_search", body2),
                      dispatch(node, body2))


def test_unrecognized_bodies_fall_back(served):
    node, port = served
    fallbacks = [
        {"query": {"match": {"title": "fox"}}, "size": 10},  # _source on
        {"query": {"match": {"other_field": "fox"}}, "_source": False},
        {"query": {"match_all": {}}, "_source": False},
        {"query": {"match": {"title": "fox"}}, "aggs": {
            "a": {"terms": {"field": "title.keyword"}}},
         "_source": False},
        {"query": {"match": {"title": "fox"}}, "from": 3, "size": 5,
         "_source": False},
        {"query": {"match": {"title": "fox"}}, "sort": ["_doc"],
         "_source": False},
    ]
    for body in fallbacks:
        before = fast_count(node)
        resp = req(port, "POST", "/books/_search", body)
        assert fast_count(node) == before, f"wrongly fast: {body}"
        slow = dispatch(node, body)
        assert resp["hits"]["total"] == slow["hits"]["total"]
    # non-ASCII query text must fall back, not mis-tokenize
    body = {"query": {"match": {"title": "fox été"}},
            "_source": False}
    before = fast_count(node)
    resp = req(port, "POST", "/books/_search", body)
    assert fast_count(node) == before
    assert resp["hits"]["total"] == dispatch(node, body)["hits"]["total"]


def test_fallback_routes_work(served):
    node, port = served
    # the whole route table flows through the fallback workers
    assert req(port, "GET", "/")["tagline"]
    health = req(port, "GET", "/_cluster/health")
    assert health["status"] in ("green", "yellow")
    cat = req(port, "GET", "/_cat/health", raw=True)
    assert b" " in cat
    doc = req(port, "GET", "/books/_doc/0")
    assert doc["found"]
    # HEAD gets headers only
    r = urllib.request.Request(f"http://127.0.0.1:{port}/books",
                               method="HEAD")
    with urllib.request.urlopen(r) as resp:
        assert resp.status == 200
        assert resp.read() == b""
    # 404 with a JSON error body
    with pytest.raises(urllib.error.HTTPError) as ei:
        req(port, "GET", "/no_such_index/_doc/1")
    assert ei.value.code == 404


def test_index_named_like_an_ndjson_route_gets_a_json_body(served):
    """Only the `_bulk` / `_msearch` ROUTES take ndjson: an index whose
    name ends in `_bulk` still parses its JSON body."""
    node, port = served
    req(port, "PUT", "/passages_bulk",
        {"mappings": {"properties": {"title": {"type": "text"}}}})
    body = ('{"index": {"_index": "passages_bulk", "_id": "1"}}\n'
            '{"title": "fox"}\n')
    assert not req(port, "POST", "/passages_bulk/_bulk", body,
                   ndjson=True)["errors"]
    req(port, "POST", "/passages_bulk/_refresh")
    hits = req(port, "POST", "/passages_bulk/_search",
               {"query": {"match": {"title": "fox"}}})["hits"]["hits"]
    assert [h["_id"] for h in hits] == ["1"]


def test_keepalive_and_concurrency(served):
    node, port = served
    bodies = [{"query": {"match": {"title": w}}, "size": 10,
               "_source": False} for w in WORDS]
    expected = {}
    for i, b in enumerate(bodies):
        expected[i] = dispatch(node, b)["hits"]["total"]["value"]
    errors = []

    def client(offset):
        try:
            for i in range(len(bodies)):
                idx = (offset + i) % len(bodies)
                r = req(port, "POST", "/books/_search", bodies[idx])
                assert r["hits"]["total"]["value"] == expected[idx]
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors


def test_loadgen_roundtrip(served):
    node, port = served
    import ctypes
    lib = native_http.get_lib()
    bodies = [json.dumps({"query": {"match": {"title": w}},
                          "size": 10, "_source": False}).encode()
              for w in WORDS[:4]]
    blob = b"".join(bodies)
    offs = np.zeros(len(bodies) + 1, np.int64)
    np.cumsum([len(b) for b in bodies], out=offs[1:])
    n = 64
    lat = np.zeros(n, np.float64)
    wall = ctypes.c_double()
    done = lib.es_loadgen(
        port, b"/books/_search", blob,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(bodies), 8, n, 30_000,
        lat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.byref(wall))
    assert done == n
    assert wall.value > 0
    assert (lat[:done] > 0).all()


def test_ip_filter_rejects_at_accept(tmp_path):
    node = Node(settings=Settings.from_dict({
        "http": {"ip_filter": {"deny": "127.0.0.0/8"}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    try:
        if not isinstance(node._http, native_http.NativeHttpFront):
            pytest.skip("front slot taken by another test's node")
        with pytest.raises((urllib.error.URLError, ConnectionError)):
            req(port, "GET", "/")
        assert node._http.stats()["ip_rejected"] >= 1
    finally:
        node.close()


def test_ip_filter_allow_only_implies_deny(tmp_path):
    """An allow-list with no deny rules must DENY non-matching addresses
    (x-pack IPFilter semantics) — not fail open."""
    node = Node(settings=Settings.from_dict({
        "http": {"ip_filter": {"allow": "10.7.0.0/16"}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    try:
        with pytest.raises((urllib.error.URLError, ConnectionError)):
            req(port, "GET", "/")
    finally:
        node.close()


def test_stdlib_server_enforces_ip_filter(tmp_path):
    """The stdlib fallback server enforces the same ip_filter settings —
    a configured security control must not silently vanish when the
    native front is unavailable."""
    node = Node(settings=Settings.from_dict({
        "http": {"native": False,
                 "ip_filter": {"deny": "127.0.0.0/8"}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    try:
        from elasticsearch_tpu.rest.http_server import HttpServer
        assert isinstance(node._http, HttpServer)
        with pytest.raises((urllib.error.URLError, ConnectionError,
                            TimeoutError)):
            r = urllib.request.Request(f"http://127.0.0.1:{port}/")
            urllib.request.urlopen(r, timeout=3)
    finally:
        node.close()


def test_delete_unregisters_fastpath(served):
    """A delete makes the segment's live mask non-trivial; the fast path
    must drop its registration (deleted docs must never come back
    through cached fast-path state)."""
    node, port = served
    fp = node._http.fastpath
    assert fp._reg is not None
    req(port, "DELETE", "/books/_doc/0")
    req(port, "POST", "/books/_refresh")
    fp.refresh_registration()
    body = {"query": {"match": {"title": "fox"}}, "size": 300,
            "_source": False}
    resp = req(port, "POST", "/books/_search", body)
    assert not any(h["_id"] == "0" for h in resp["hits"]["hits"])
    assert_equivalent(resp, dispatch(node, body))


def test_unmasked_cohorts_rest_on_a_segment_without_deletions(served):
    """A cohort with no filter row launches without the mask stack
    (ops/fastpath.py F_SLOTS). That rests on the registered segment
    having no deletions and on row 0 staying the live column: a
    filtered cohort takes the masked program and leaves row 0 alone, a
    delete and refresh drop the registration at the next check, and a
    filter column is ANDed with the live column."""
    node, port = served
    fp = node._http.fastpath
    reg = fp._reg
    n = reg["segment"].n_docs
    live = np.asarray(reg["dev"].live)
    assert live[:n].all() and not live[n:].any()
    plain = {"query": {"match": {"title": "fox gamma"}}, "size": 20,
             "_source": False}
    filtered = {"query": {"bool": {
        "must": [{"match": {"title": "fox gamma"}}],
        "filter": [{"match": {"title": "dog"}}]}},
        "size": 20, "_source": False}
    for body, unmasked in ((plain, 1), (filtered, 0)):
        cohorts = fp.stats["cohorts"]
        skipped = fp.stats["unmasked_cohorts"]
        before = fast_count(node)
        req(port, "POST", "/books/_search", body)
        assert fast_count(node) == before + 1
        assert fp.stats["cohorts"] == cohorts + 1
        assert fp.stats["unmasked_cohorts"] == skipped + unmasked
    assert np.array_equal(np.asarray(reg["mask_stack"][0]), live)
    serving = node.rest_controller.dispatch("GET", "/_kernels", {},
                                            None)[1]["serving"]
    assert serving["counters"]["unmasked_cohorts"] == \
        fp.stats["unmasked_cohorts"]

    # doc "0" and a term it holds, as a filter that admits it
    docid = list(reg["segment"].stored.ids).index("0")
    flat = np.asarray(reg["dp"].host.block_docids).reshape(-1)
    term = next(t for t in range(len(reg["post_len"]))
                if docid in flat[reg["post_start"][t]:
                                 reg["post_start"][t]
                                 + reg["post_len"][t]])
    req(port, "DELETE", "/books/_doc/0")
    req(port, "POST", "/books/_refresh")
    fp.refresh_registration()
    assert fp._reg is None
    idx = node.indices_service.indices["books"]
    dev = idx.device_cache.get(idx.shards[0].segments[0])
    assert not np.asarray(dev.live)[docid]
    col = fp._filter_col(dict(reg, dev=dev, filter_live={}), (term,))
    mask, _host = dev.composed_filter_mask(
        [(reg["field"], (reg["dp"].host.terms[term],), False)])
    assert np.asarray(mask)[docid] and not np.asarray(col)[docid]
    assert np.array_equal(np.asarray(col),
                          np.asarray(mask) & np.asarray(dev.live))


def test_registration_lays_each_postings_length_beside_its_tf(served):
    """Registration builds ``block_lens``, each posting's document
    length laid out as the block tfs, once on the device: it holds
    ``doc_lens[block_docids]`` on every lane with tf > 0, its bytes are
    charged to the segment's HBM (slab class ``posting_lens``, the hbm
    breaker) and reported as ``serving.posting_len_bytes``, and every
    launch, plain or filtered, counts in ``posting_len_cohorts``."""
    from elasticsearch_tpu.utils.breaker import CircuitBreaker
    node, port = served
    fp = node._http.fastpath
    reg = fp._reg
    dp, dev = reg["dp"], reg["dev"]
    bl = np.asarray(reg["block_lens"])
    bd, bt = np.asarray(dp.block_docids), np.asarray(dp.block_tfs)
    assert bl.shape == bt.shape and bl.dtype == np.float32
    hit = bt > 0
    assert hit.any()
    assert np.array_equal(bl[hit], np.asarray(dp.doc_lens)[bd[hit]])
    assert dev.posting_lens(reg["field"]) is reg["block_lens"]
    assert dev.hbm_bytes_by_class()["posting_lens"] == bl.nbytes
    cache = node.indices_service.device_cache
    assert node.breaker_service.get_breaker(CircuitBreaker.HBM).used == \
        sum(d.hbm_bytes() for _v, d in cache._cache.values())

    plain = {"query": {"match": {"title": "fox gamma"}}, "size": 20,
             "_source": False}
    filtered = {"query": {"bool": {
        "must": [{"match": {"title": "fox gamma"}}],
        "filter": [{"match": {"title": "dog"}}]}},
        "size": 20, "_source": False}
    for body in (plain, filtered):
        cohorts = fp.stats["cohorts"]
        reads = fp.stats["posting_len_cohorts"]
        before = fast_count(node)
        req(port, "POST", "/books/_search", body)
        assert fast_count(node) == before + 1
        assert fp.stats["cohorts"] == cohorts + 1
        assert fp.stats["posting_len_cohorts"] == reads + 1
    serving = node.rest_controller.dispatch("GET", "/_kernels", {},
                                            None)[1]["serving"]
    assert serving["counters"]["posting_len_cohorts"] == \
        fp.stats["posting_len_cohorts"] == fp.stats["cohorts"]
    assert serving["posting_len_bytes"] == bl.nbytes


def test_warm_ladder_compiles_exactly_the_routable_programs(
        tmp_path, monkeypatch):
    """start() compiles nothing; registering an index warms exactly the
    programs the router can reach: v2m masked and unmasked at every nb
    bucket, v1 masked and unmasked at the largest bucket (slot misfits,
    θ-lane refires and the truncated lane run there) and the essential
    lane at every ess bucket."""
    from types import SimpleNamespace

    from jax import monitoring

    from elasticsearch_tpu.ops import fastpath as kernels
    from elasticsearch_tpu.search.fastpath import FastPathServer
    warmed = []
    # (kernel, lane name, position of sel_blocks, position of masks)
    for name, lane, i_sel, i_mask in (
            ("bm25_topk_total_merge_batch", "v2m", 2, 5),
            ("bm25_topk_total_batch", "v1", 2, 5),
            ("bm25_essential_topk_batch", "ess", 4, 8)):
        def spy(*args, _lane=lane, _s=i_sel, _m=i_mask):
            warmed.append((_lane, args[_s].shape[1],
                           "unmasked" if args[_m] is None else "masked"))
            return SimpleNamespace(block_until_ready=lambda: None)
        monkeypatch.setattr(kernels, name, spy)
    compiles = []

    def on_event(event, _secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    node = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "64,128"}},
    }), data_path=str(tmp_path / "data"))
    monitoring.register_event_duration_secs_listener(on_event)
    try:
        port = node.start(0)
    finally:
        monitoring.unregister_event_duration_listener(on_event)
    try:
        assert compiles == [], "start() compiled before registration"
        assert warmed == []
        lines = []
        for i in range(40):
            lines.append(json.dumps({"index": {"_index": "books",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"title": " ".join(WORDS[i % 5:])}))
        req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
        req(port, "POST", "/books/_refresh")
        fp = node._http.fastpath
        fp.refresh_registration()
        assert fp._reg is not None
        want = [("v2m", nb, m) for nb in (64, 128)
                for m in ("masked", "unmasked")]
        want += [("v1", 128, "masked"), ("v1", 128, "unmasked")]
        want += [("ess", nb, "masked") for nb in FastPathServer.ESS_BUCKETS]
        assert sorted(warmed) == sorted(want)
    finally:
        node.close()


def test_theta_cached_essential_lane(tmp_path):
    """Second run of an identical query takes the θ-cached essential
    MaxScore lane (small sort + per-candidate patching) and returns
    results identical to the full exact kernel (ops/fastpath.py
    essential lane)."""
    node = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "64,128",
                            "fast_max_k": 10}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    try:
        lines = []
        # 12 docs with a HIGH-idf term (some also carry 'common'), 288
        # with only the low-idf term: θ at k=10 exceeds maxc(common),
        # so 'common' goes non-essential and gets patched back
        for i in range(300):
            text = ("rare common extra" if i < 12 else "common filler")
            lines.append(json.dumps({"index": {"_index": "books",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"title": text}))
        req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
        req(port, "POST", "/books/_refresh")
        fp = node._http.fastpath
        fp.refresh_registration()
        assert fp._reg is not None
        body = {"query": {"match": {"title": "rare common"}},
                "size": 10, "_source": False}
        first = req(port, "POST", "/books/_search", body)
        key = next(iter(fp._reg["theta"]), None)
        assert key is not None, "θ cache must fill after a full run"
        split = fp._essential_split(fp._reg, 10, list(key[0]), key[1])
        assert split is not None, "partition should find a ne term"
        second = req(port, "POST", "/books/_search", body)
        # let the async launch finish responding before reading stats
        assert fp.stats.get("ess_queries", 0) >= 1
        assert_equivalent(second, first)
        assert second["hits"]["total"] == first["hits"]["total"]
        # exact-order identity for the certified lane (both exact)
        assert [h["_id"] for h in second["hits"]["hits"]] == \
            [h["_id"] for h in first["hits"]["hits"]]
    finally:
        node.close()


def test_segment_change_reregisters(served):
    node, port = served
    fp = node._http.fastpath
    seg_before = fp._reg["segment"]
    lines = [json.dumps({"index": {"_index": "books", "_id": "n1"}}),
             json.dumps({"title": "fox fox fox"})]
    req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
    req(port, "POST", "/books/_refresh")
    req(port, "POST", "/books/_forcemerge?max_num_segments=1")
    fp.refresh_registration()
    # either a single merged segment re-registered, or (multi-segment)
    # the registration dropped — both are consistent states
    if fp._reg is not None:
        assert fp._reg["segment"] is not seg_before
        body = {"query": {"match": {"title": "fox"}}, "size": 5,
                "_source": False}
        fast = req(port, "POST", "/books/_search", body)
        assert any(h["_id"] == "n1" for h in fast["hits"]["hits"])


def test_impact_truncated_lane_serves_oversize(tmp_path):
    """A query whose block need exceeds the largest lane bucket rides
    the impact-truncated lane (mode "always") instead of bouncing: the
    fast path answers with relation "gte", per-bucket dispatch counts
    record the trunc lane, and the serving stats surface through
    GET /_kernels."""
    node = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "8,16",
                            "fast_max_k": 200,
                            "fast_impact": "always"}},
    }), data_path=str(tmp_path / "data"))
    port = node.start(0)
    assert isinstance(node._http, native_http.NativeHttpFront)
    rng = np.random.default_rng(7)
    lines = []
    for i in range(900):
        doc = " ".join(rng.choice(WORDS, size=int(rng.integers(4, 12))))
        lines.append(json.dumps({"index": {"_index": "books",
                                           "_id": str(i)}}))
        lines.append(json.dumps({"title": doc}))
    req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
    req(port, "POST", "/books/_refresh")
    fp = node._http.fastpath
    fp.refresh_registration()
    assert fp._reg is not None
    try:
        reg = fp._reg
        # an all-words query needs far more blocks than the 16 budget
        nb_need = int(reg["nb"].sum())
        assert nb_need > 16, nb_need
        resp = req(port, "POST", "/books/_search",
                   {"query": {"match": {"title": " ".join(WORDS)}},
                    "size": 10, "_source": False})
        assert resp["hits"]["hits"], resp
        assert resp["hits"]["total"]["relation"] == "gte"
        assert fp.stats.get("trunc_served", 0) >= 1
        assert any(k.startswith("trunc:") for k in fp.dispatch), \
            fp.dispatch
        # serving stats ride GET /_kernels
        kern = req(port, "GET", "/_kernels")
        assert "serving" in kern
        assert kern["serving"]["impact_mode"] == "always"
        # float32 rail: the v2m merge carries the contributions
        assert kern["serving"]["merge_payload"] == "contrib"
        assert any(k.startswith("trunc:")
                   for k in kern["serving"]["dispatch"])
        # truncated hits are real matches: every returned id appears in
        # the exact python-path result over ALL matches (observed
        # scores are lower bounds over covered blocks — never invented)
        full = req(port, "POST", "/books/_search",
                   {"query": {"match": {"title": " ".join(WORDS)}},
                    "size": 900})
        full_ids = {h["_id"] for h in full["hits"]["hits"]}
        got_ids = {h["_id"] for h in resp["hits"]["hits"]}
        assert got_ids <= full_ids
    finally:
        node.close()
