"""Multi-chip serving: the mesh backend on an 8-virtual-device CPU mesh
(conftest pins ``--xla_force_host_platform_device_count=8``).

The serving contract under test (ISSUE 9 acceptance): a mesh-served
`_search` is BYTE-identical to the single-device per-shard loop for the
pinned query mix (bm25, bool, knn) — scores, doc order, totals — and
every ineligible shape falls back CLEANLY (no error, typed
``fallback.<reason>`` counter) to the loop: one device, over-ceiling
corpora, dfs statistics, disabled backend. Plus the replica-axis cohort
fan-out (search/batching.py) and the `GET /_kernels` mesh surface
(dispatch counters + per-device residency) and per-chip profile
attribution."""

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node

VOCAB = ["amber", "basalt", "cedar", "dune", "ember", "fjord", "granite",
         "harbor", "islet", "juniper", "krill", "lagoon", "mesa", "nectar"]

DIMS = 8


@pytest.fixture
def node(tmp_path):
    n = Node(Settings.EMPTY, data_path=str(tmp_path / "data"))
    yield n
    n.close()


def do(node, method, path, params=None, body=None, expect=200):
    status, resp = node.rest_controller.dispatch(method, path, params, body)
    assert status == expect, f"{method} {path} -> {status}: {resp}"
    return resp


def seed(node, index, n_shards, n_docs=120, seed=5, forcemerge=True):
    rng = np.random.default_rng(seed)
    do(node, "PUT", f"/{index}", body={
        "settings": {"index": {"number_of_shards": n_shards}},
        "mappings": {"properties": {
            "title": {"type": "text"},
            "tag": {"type": "keyword"},
            "vec": {"type": "dense_vector", "dims": DIMS,
                    "similarity": "cosine"}}}})
    for i in range(n_docs):
        do(node, "PUT", f"/{index}/_doc/{i}",
           body={"title": " ".join(rng.choice(VOCAB, rng.integers(2, 10))),
                 "tag": str(rng.choice(["x", "y"])),
                 "vec": rng.standard_normal(DIMS).astype(
                     np.float32).tolist()},
           expect=201)
    do(node, "POST", f"/{index}/_refresh")
    if forcemerge:
        # one segment per shard — the mesh residency model
        do(node, "POST", f"/{index}/_forcemerge")
    return rng


def pinned_mix(rng):
    """The acceptance mix: bm25 match, bool (+filter, +msm), knn."""
    return [
        {"query": {"match": {"title": "amber dune"}}, "size": 50},
        {"query": {"match": {"title": {"query": "cedar fjord mesa",
                                       "operator": "and"}}}, "size": 50},
        {"query": {"bool": {
            "must": [{"match": {"title": "granite"}}],
            "filter": [{"term": {"tag": "x"}}]}}, "size": 50},
        {"query": {"bool": {
            "should": [{"match": {"title": "krill"}},
                       {"match": {"title": "lagoon harbor"}}],
            "minimum_should_match": 1}}, "size": 50},
        {"knn": {"field": "vec",
                 "query_vector": rng.standard_normal(DIMS).tolist(),
                 "k": 20, "num_candidates": 64},
         "_source": False, "size": 20},
    ]


def hits_of(r):
    return [(h["_id"], h["_score"]) for h in r["hits"]["hits"]]


def mesh_vs_loop(node, index, body, monkeypatch):
    """(mesh response, loop response, mesh_engaged) for one body."""
    svc = node.search_service
    before = svc.mesh_executor.mesh_searches
    r_mesh = do(node, "POST", f"/{index}/_search", body=dict(body))
    engaged = svc.mesh_executor.mesh_searches - before
    monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
    try:
        r_loop = do(node, "POST", f"/{index}/_search", body=dict(body))
    finally:
        monkeypatch.delenv("ESTPU_MESH_SERVING")
    return r_mesh, r_loop, engaged


# ---------------------------------------------------------------- parity


def test_pinned_mix_byte_identical(node, monkeypatch):
    """ACCEPTANCE: the pinned bm25/bool/knn mix on an 8-device mesh is
    byte-identical — raw float scores, doc order, totals — to the
    per-shard loop, and every body actually engaged the mesh."""
    rng = seed(node, "m8", n_shards=8)
    for body in pinned_mix(rng):
        r_mesh, r_loop, engaged = mesh_vs_loop(node, "m8", body,
                                               monkeypatch)
        assert engaged == 1, body
        assert hits_of(r_mesh) == hits_of(r_loop), body
        assert r_mesh["hits"]["total"] == r_loop["hits"]["total"], body
        assert r_mesh["hits"]["max_score"] == \
            r_loop["hits"]["max_score"], body


@pytest.mark.chaos(seed=17)
def test_chaos_seeded_parity_with_deletes(node, monkeypatch, chaos_seed):
    """Chaos-seeded sweep: random corpus, random query mix, random
    DELETES (live-mask refresh on the resident corpus) — every round
    stays byte-identical to the loop. Replays with --chaos-seed=N."""
    rng = seed(node, "cx", n_shards=4, n_docs=90, seed=chaos_seed)
    # random deletes flip live bits only — the mesh refreshes live
    # bitmaps in place (postings stay resident)
    victims = rng.choice(90, size=12, replace=False)
    for v in victims:
        do(node, "DELETE", f"/cx/_doc/{int(v)}")
    do(node, "POST", "/cx/_refresh")
    queries = []
    for _ in range(6):
        w = [str(x) for x in rng.choice(VOCAB, rng.integers(1, 4))]
        queries.append({"query": {"match": {"title": " ".join(w)}},
                       "size": 30})
        queries.append({"query": {"bool": {
            "should": [{"match": {"title": t}} for t in w],
            "minimum_should_match": 1,
            "filter": [{"term": {"tag": str(rng.choice(["x", "y"]))}}],
        }}, "size": 30})
    queries.append({"knn": {
        "field": "vec", "query_vector": rng.standard_normal(DIMS).tolist(),
        "k": 15, "num_candidates": 40}, "_source": False, "size": 15})
    for body in queries:
        r_mesh, r_loop, engaged = mesh_vs_loop(node, "cx", body,
                                               monkeypatch)
        assert engaged == 1, body
        assert hits_of(r_mesh) == hits_of(r_loop), body
        assert r_mesh["hits"]["total"] == r_loop["hits"]["total"], body
        # no deleted doc resurfaces through the mesh live mask
        for h in r_mesh["hits"]["hits"]:
            assert int(h["_id"]) not in set(int(v) for v in victims), body


def test_per_shard_idf_semantics(node, monkeypatch):
    """Mesh scoring uses each shard's OWN statistics (ES default), so
    the mesh equals the default loop exactly — while dfs_query_then_fetch
    (global stats) takes the loop with a typed fallback and produces the
    layout-independent scores the mesh path must not fake."""
    seed(node, "idf8", n_shards=8, n_docs=100, seed=7)
    body = {"query": {"match": {"title": "amber"}}, "size": 40}
    r_mesh, r_loop, engaged = mesh_vs_loop(node, "idf8", body,
                                           monkeypatch)
    assert engaged == 1
    assert hits_of(r_mesh) == hits_of(r_loop)
    svc = node.search_service
    fb = svc.mesh_executor.counters.get("fallback.dfs_stats", 0)
    before = svc.mesh_executor.mesh_searches
    r_dfs = do(node, "POST", "/idf8/_search",
               params={"search_type": "dfs_query_then_fetch"},
               body=dict(body))
    assert svc.mesh_executor.mesh_searches == before, \
        "dfs search must not ride the mesh (per-shard stats binding)"
    assert svc.mesh_executor.counters["fallback.dfs_stats"] == fb + 1
    assert r_dfs["hits"]["hits"], "dfs loop fallback must still answer"


# -------------------------------------------------------------- fallback


def test_fallback_one_device(node, monkeypatch):
    """With a single visible device the mesh declines (typed counter)
    and the loop answers — no error, same results."""
    seed(node, "one8", n_shards=4, n_docs=50, seed=3)
    svc = node.search_service
    monkeypatch.setattr(type(svc.mesh_executor), "available_devices",
                        staticmethod(lambda: 1))
    fb = svc.mesh_executor.counters.get("fallback.not_enough_devices", 0)
    before = svc.mesh_executor.mesh_searches
    r = do(node, "POST", "/one8/_search",
           body={"query": {"match": {"title": "amber"}}, "size": 20})
    assert svc.mesh_executor.mesh_searches == before
    assert svc.mesh_executor.counters["fallback.not_enough_devices"] \
        == fb + 1
    assert r["hits"]["total"]["value"] > 0


def test_fallback_disabled_env(node, monkeypatch):
    seed(node, "off8", n_shards=4, n_docs=40, seed=3)
    svc = node.search_service
    monkeypatch.setenv("ESTPU_MESH_SERVING", "0")
    fb = svc.mesh_executor.counters.get("fallback.disabled", 0)
    r = do(node, "POST", "/off8/_search",
           body={"query": {"match": {"title": "amber"}}, "size": 20})
    assert svc.mesh_executor.counters["fallback.disabled"] == fb + 1
    assert r["hits"]["total"]["value"] > 0


def test_fallback_knn_over_packed_ceiling(node, monkeypatch):
    """kNN over a corpus whose global-id space exceeds the float-pack
    ceiling declines cleanly (the bm25 analogue is pinned in
    test_mesh_executor) — loop still answers, counter ticks."""
    import elasticsearch_tpu.ops.plan as plan_mod
    rng = seed(node, "kovf", n_shards=4, n_docs=60, seed=3)
    monkeypatch.setattr(plan_mod, "PACKED_ID_LIMIT", 1)
    monkeypatch.setattr(plan_mod, "check_packed_id_limit",
                        lambda nd, where: None)
    svc = node.search_service
    fb = svc.mesh_executor.counters.get("fallback.packed_id_ceiling", 0)
    before = svc.mesh_executor.mesh_searches
    r = do(node, "POST", "/kovf/_search", body={
        "knn": {"field": "vec",
                "query_vector": rng.standard_normal(DIMS).tolist(),
                "k": 10, "num_candidates": 32},
        "_source": False, "size": 10})
    assert svc.mesh_executor.mesh_searches == before
    assert svc.mesh_executor.counters["fallback.packed_id_ceiling"] \
        == fb + 1
    assert r["hits"]["hits"]


def test_fallback_knn_with_filter(node, monkeypatch):
    """Filtered kNN is not mesh-resident yet — typed fallback, loop
    answers with the filter applied."""
    rng = seed(node, "kf", n_shards=4, n_docs=60, seed=3)
    svc = node.search_service
    fb = svc.mesh_executor.counters.get("fallback.knn_filter", 0)
    before = svc.mesh_executor.mesh_searches
    r = do(node, "POST", "/kf/_search", body={
        "knn": {"field": "vec",
                "query_vector": rng.standard_normal(DIMS).tolist(),
                "k": 10, "num_candidates": 32,
                "filter": {"term": {"tag": "x"}}},
        "_source": False, "size": 10})
    assert svc.mesh_executor.mesh_searches == before
    assert svc.mesh_executor.counters["fallback.knn_filter"] == fb + 1
    assert r["hits"]["hits"]


# ------------------------------------------------- replica-axis cohorts


def test_replica_cohort_byte_identical(node):
    """A continuous-batching cohort launched replica-sharded over the
    mesh (corpus replicated, Q axis split) returns byte-identical
    packed rows to the single-device launch, and counts dispatches."""
    from elasticsearch_tpu.search.batching import PlanBatcher, _Entry
    from elasticsearch_tpu.search.plan import bind_plan, compile_plan
    from elasticsearch_tpu.search.queries import parse_query
    seed(node, "rb", n_shards=1, n_docs=200, seed=3)
    searcher = node.indices_service.get("rb").shard_searchers()[0]
    ctx = searcher._contexts()[0]
    q = parse_query({"match": {"title": "amber dune"}}).rewrite(searcher)
    bp = bind_plan(compile_plan(q, searcher), ctx)
    k, k1, b = 10, searcher.k1, searcher.b

    solo = PlanBatcher()
    e1 = [_Entry(bp) for _ in range(16)]
    solo._run(e1, ctx, k, k1, b)
    assert solo.mesh_cohorts == 0

    meshed = PlanBatcher()
    meshed.mesh = node.search_service.mesh_executor
    before = meshed.mesh.counters.get("dispatch.replica", 0)
    e2 = [_Entry(bp) for _ in range(16)]
    meshed._run(e2, ctx, k, k1, b)
    assert meshed.mesh_cohorts == 1
    assert meshed.mesh.counters["dispatch.replica"] == before + 16

    for a, b_ in zip(e1, e2):
        va, ia, ta = a.result
        vb, ib, tb = b_.result
        assert np.array_equal(va, vb) and np.array_equal(ia, ib)
        assert ta == tb
    assert "mesh_cohorts" in meshed.stats()


def test_replica_mesh_sizing(node):
    """replica_mesh_for: largest pow2 ≤ min(cohort, devices); None
    below two devices or for 1-row cohorts."""
    be = node.search_service.mesh_executor
    assert be.replica_mesh_for(1) is None
    assert be.replica_mesh_for(2).devices.size == 2
    assert be.replica_mesh_for(32).devices.size == 8
    assert be.replica_mesh_for(12).devices.size == 8


def test_fastpath_mesh_cohorts(tmp_path, monkeypatch):
    """ESTPU_FASTPATH_MESH=1: the native front's v1 cohorts launch
    replica-sharded over the mesh — responses match the Python path
    (the native-front parity contract) and the dispatch counters tick.
    The query reaches v1 as a slot misfit: nine words in every one of
    1,200 docs make 9 terms x 10 blocks, which fit bucket 128 but need
    18 of its 16 slots."""
    import json
    import urllib.request

    from elasticsearch_tpu.rest import native_http
    if not native_http.available():
        pytest.skip("native http front unavailable")
    monkeypatch.setenv("ESTPU_FASTPATH_MESH", "1")
    n = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "64,128",
                            "fast_max_k": 200}},
    }), data_path=str(tmp_path / "data"))
    try:
        port = n.start(0)
        if not isinstance(n._http, native_http.NativeHttpFront):
            pytest.skip("native front slot unavailable")
        rng = np.random.default_rng(42)
        common = VOCAB[:9]
        lines = []
        for i in range(1200):
            lines.append(json.dumps({"index": {"_index": "books",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"title": " ".join(
                common + list(rng.choice(VOCAB, rng.integers(3, 10))))}))
        data = ("\n".join(lines) + "\n").encode()
        r = urllib.request.Request(
            f"http://127.0.0.1:{port}/_bulk", data=data, method="POST",
            headers={"Content-Type": "application/x-ndjson"})
        urllib.request.urlopen(r).read()
        urllib.request.urlopen(urllib.request.Request(
            f"http://127.0.0.1:{port}/books/_refresh",
            method="POST")).read()
        fp = n._http.fastpath
        fp.refresh_registration()
        assert fp._reg is not None
        assert fp.mesh_backend is n.search_service.mesh_executor
        assert fp._reg["rmesh"] is not None, \
            "registration must bind a replica mesh"
        body = {"query": {"match": {"title": " ".join(common)}},
                "size": 20, "_source": False}
        before = n.search_service.mesh_executor.counters.get(
            "dispatch.replica", 0)
        rq = urllib.request.Request(
            f"http://127.0.0.1:{port}/books/_search",
            data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        fast = json.loads(urllib.request.urlopen(rq).read())
        assert fp.stats.get("mesh_cohorts", 0) >= 1, fp.stats
        assert n.search_service.mesh_executor.counters[
            "dispatch.replica"] > before
        status, slow = n.rest_controller.dispatch(
            "POST", "/books/_search", None, dict(body))
        assert status == 200
        assert fast["hits"]["total"] == slow["hits"]["total"]
        fh = [(h["_id"], h["_score"]) for h in fast["hits"]["hits"]]
        sh = [(h["_id"], h["_score"]) for h in slow["hits"]["hits"]]
        assert len(fh) == len(sh)
        for (fi, fs), (si, ss) in zip(fh, sh):
            # the native-front parity contract (test_native_http):
            # float32 noise between tree-order and dense summation
            assert fs == pytest.approx(ss, rel=2e-3), (fi, si)
    finally:
        n.close()


# ------------------------------------------------------------ telemetry


def test_kernels_mesh_surface(node, monkeypatch):
    """GET /_kernels gains a `mesh` section: dispatch/fallback counters
    and per-DEVICE HBM residency of the cached mesh corpora — 8 chips,
    each holding its own shard's slabs."""
    rng = seed(node, "tele8", n_shards=8, n_docs=80, seed=3)
    for body in pinned_mix(rng)[:1] + pinned_mix(rng)[-1:]:
        do(node, "POST", "/tele8/_search", body=dict(body))
    r = do(node, "GET", "/_kernels")
    mesh = r["mesh"]
    assert mesh["devices"] == 8
    assert mesh["counters"].get("dispatch.shard", 0) >= 1
    assert mesh["counters"].get("dispatch.knn", 0) >= 1
    res = mesh["residency"]
    assert len(res) == 8, f"expected 8 devices resident, got {res.keys()}"
    for dev, classes in res.items():
        assert classes.get("postings", 0) > 0, (dev, classes)
        assert classes.get("vectors", 0) > 0, (dev, classes)
    # node metrics mirror: search.mesh.dispatch{axis} counted
    stats = do(node, "GET", "/_nodes/stats")
    metrics = next(iter(stats["nodes"].values()))["telemetry"]["metrics"]
    rows = metrics.get("search.mesh.dispatch", [])
    assert any(row["labels"].get("axis") == "shard" and row["value"] >= 1
               for row in rows), rows


def test_mesh_profile_attribution(node):
    """`profile: true` rides the mesh: the response carries a
    `[index][_mesh]` profile entry whose device record attributes the
    launch per chip (mesh_shape + device list), and the mesh still
    serves the query (the gate no longer bounces profiled searches)."""
    seed(node, "prof8", n_shards=8, n_docs=80, seed=3)
    svc = node.search_service
    before = svc.mesh_executor.mesh_searches
    r = do(node, "POST", "/prof8/_search", body={
        "query": {"match": {"title": "amber dune"}},
        "size": 10, "profile": True})
    assert svc.mesh_executor.mesh_searches == before + 1
    shards = r["profile"]["shards"]
    mesh_entries = [s for s in shards if s["id"].endswith("[_mesh]")]
    assert len(mesh_entries) == 1, [s["id"] for s in shards]
    launches = mesh_entries[0]["device"]["launches"]
    assert launches[0]["kernel"] == "plan_topk_mesh"
    assert launches[0]["mesh_shape"] == {"shard": 8}
    assert len(launches[0]["device"]) == 8
    assert launches[0]["readback_bytes"] > 0
    # the pinned per-shard invariant holds for the mesh entry too
    q0 = mesh_entries[0]["searches"][0]["query"][0]
    bd = q0["breakdown"]
    assert bd["device_time_in_nanos"] + bd["host_time_in_nanos"] \
        == q0["time_in_nanos"]
