"""Host spans on the profiler's clock and the serving path's queue-wait
histograms (telemetry/tracing.py ``host_span``, search/fastpath.py,
search/batching.py, rest/native_http.py, native/src/estpu_http.cpp).

A profile taken around one fast-path ``match`` and one top-level ``knn``
must hold the documented span names on host thread lines, nested as the
code nests them; the always-on histograms in ``GET /_nodes/stats`` count
every request once; a fast-path ``took`` is timed from the front's
arrival stamp, so it holds the request's wait in the C++ queue."""

import glob
import json
import threading
import time

import numpy as np
import pytest

from elasticsearch_tpu.common.settings import Settings
from elasticsearch_tpu.node import Node
from elasticsearch_tpu.rest import native_http
from elasticsearch_tpu.telemetry.tracing import host_span

pytestmark = pytest.mark.skipif(not native_http.available(),
                                reason="native http front unavailable")

WORDS = ["alpha", "beta", "gamma", "delta", "fox", "dog", "cat", "bird"]
HISTOGRAMS = ("fastpath.queue_wait", "fastpath.inflight",
              "http.fallback.queue_wait", "knn.queue_wait", "knn.rerank")


def req(port, method, path, body=None, ndjson=False):
    import urllib.request
    data = None if body is None else (
        body.encode() if isinstance(body, str) else json.dumps(body).encode())
    r = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/x-ndjson" if ndjson
                 else "application/json"})
    with urllib.request.urlopen(r) as resp:
        return json.loads(resp.read())


def match(text):
    return {"query": {"match": {"title": text}}, "size": 5,
            "_source": False}


def knn(vec):
    return {"knn": {"field": "v", "query_vector": list(vec), "k": 3,
                    "num_candidates": 10}, "size": 3, "_source": False}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    node = Node(settings=Settings.from_dict({
        "http": {"native": {"fast_nb_buckets": "64,128",
                            "fast_max_k": 200}},
    }), data_path=str(tmp_path_factory.mktemp("spans") / "data"))
    port = node.start(0)
    req(port, "PUT", "/books", {"mappings": {"properties": {
        "title": {"type": "text"},
        "v": {"type": "dense_vector", "dims": 4,
              "similarity": "cosine"}}}})
    rng = np.random.default_rng(7)
    lines = []
    for i in range(120):
        lines.append(json.dumps({"index": {"_index": "books",
                                           "_id": str(i)}}))
        lines.append(json.dumps({
            "title": " ".join(rng.choice(WORDS, 5)),
            "v": np.round(rng.standard_normal(4), 3).tolist()}))
    req(port, "POST", "/_bulk", "\n".join(lines) + "\n", ndjson=True)
    req(port, "POST", "/books/_refresh")
    node._http.fastpath.refresh_registration()
    assert node._http.fastpath._reg is not None
    # every shape warm before anything is measured
    req(port, "POST", "/books/_search", match("fox dog"))
    req(port, "POST", "/books/_search", knn([1, 0, 0, 0]))
    yield node, port
    node.close()


def fast_count(node):
    return node._http.stats()["fast"]


def histograms(port):
    stats = req(port, "GET", "/_nodes/stats")
    metrics = next(iter(stats["nodes"].values()))["telemetry"]["metrics"]
    return {h: (metrics[h]["count"], metrics[h]["sum"], metrics[h]["max"])
            if h in metrics else (0, 0.0, None) for h in HISTOGRAMS}


def test_host_span_outside_a_profile_is_a_plain_context_manager():
    with host_span("outer"):
        with host_span("inner"):
            pass


def _host_lines(log_dir):
    from jax.profiler import ProfileData
    path = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.append([(e.name, int(e.start_ns),
                         int(e.start_ns + e.duration_ns))
                        for e in line.events])
    return out


def _within(line, inner, outer):
    """Every ``inner`` event of the thread line lies inside an
    ``outer`` event of the same line."""
    outs = [(a, b) for n, a, b in line if n == outer]
    ins = [(a, b) for n, a, b in line if n == inner]
    return bool(ins) and all(any(a <= s and e <= b for a, b in outs)
                             for s, e in ins)


def test_profile_holds_the_serving_spans_nested(served, tmp_path):
    import jax
    node, port = served
    before = fast_count(node)
    with jax.profiler.trace(str(tmp_path)):
        fast = req(port, "POST", "/books/_search", match("fox gamma"))
        vec = req(port, "POST", "/books/_search", knn([0, 1, 0, 0]))
    assert fast_count(node) == before + 1
    assert fast["hits"]["hits"] and len(vec["hits"]["hits"]) == 3
    lines = _host_lines(tmp_path)
    names = {n for line in lines for n, _, _ in line}
    for name in ("http.serve", "http.parse", "http.encode", "rest.search",
                 "fastpath.route", "fastpath.stream_wait", "fastpath.pack",
                 "fastpath.respond", "knn.flush_wait", "knn.slot_wait",
                 "knn.rerank", "launch:knn_nominate_batch",
                 "readback:search.batching.knn_cohort"):
        assert name in names, name
    assert any(n.startswith("launch:bm25_") for n in names)
    assert any(n.startswith("readback:search.fastpath.") for n in names)

    def line_of(name):
        return next(line for line in lines
                    if any(n == name for n, _, _ in line))
    # the fallback worker: parse and encode inside serve, the REST root
    # inside serve, the batcher's waits and the re-rank inside the root
    http = line_of("http.serve")
    assert _within(http, "http.parse", "http.serve")
    assert _within(http, "http.encode", "http.serve")
    assert _within(http, "rest.search", "http.serve")
    for name in ("knn.flush_wait", "knn.slot_wait", "knn.rerank",
                 "launch:knn_nominate_batch"):
        assert _within(http, name, "rest.search"), name
    # the drain thread waits for a stream inside its routing pass
    assert _within(line_of("fastpath.route"), "fastpath.stream_wait",
                   "fastpath.route")
    # a launch stream packs, launches, reads back, then responds
    stream = line_of("fastpath.pack")
    order = [n for n, _, _ in sorted(stream, key=lambda e: e[1])
             if n.startswith(("fastpath.", "launch:bm25_",
                              "readback:search.fastpath."))]
    assert order[0] == "fastpath.pack"
    assert order[1].startswith("launch:bm25_")
    assert order[2].startswith("readback:search.fastpath.")
    assert order[3] == "fastpath.respond"


def test_queue_wait_histograms_count_each_request_once(served):
    node, port = served
    h0 = histograms(port)
    n_fast, n_knn = 5, 4
    before = fast_count(node)
    for i in range(n_fast):
        req(port, "POST", "/books/_search",
            match(" ".join(WORDS[i:i + 2])))
    assert fast_count(node) == before + n_fast
    for i in range(n_knn):
        req(port, "POST", "/books/_search", knn(np.eye(4)[i]))
    h1 = histograms(port)
    delta = {h: h1[h][0] - h0[h][0] for h in HISTOGRAMS}
    assert delta["fastpath.queue_wait"] == n_fast
    assert delta["knn.queue_wait"] == n_knn
    assert delta["knn.rerank"] == n_knn
    # every fallback request is counted when a worker takes it: the kNN
    # searches and the second stats read itself
    assert delta["http.fallback.queue_wait"] == n_knn + 1
    assert 1 <= delta["fastpath.inflight"] <= n_fast
    for h in HISTOGRAMS:
        assert h1[h][1] - h0[h][1] >= 0.0, h


def test_fast_took_holds_the_wait_in_the_native_queue(served):
    """A request held in the C++ queue while every launch stream is busy
    reports that wait in ``took``: the clock starts at the front's
    arrival stamp, not when the drain thread picks it up."""
    node, port = served
    fp = node._http.fastpath
    h0 = histograms(port)
    one = req(port, "POST", "/books/_search", match("cat bird"))
    h1 = histograms(port)
    assert h1["fastpath.queue_wait"][0] == h0["fastpath.queue_wait"][0] + 1
    wait_ms = h1["fastpath.queue_wait"][1] - h0["fastpath.queue_wait"][1]
    assert one["took"] >= int(wait_ms)

    def wait_for(parsed, queued):
        deadline = time.monotonic() + 30
        while (fast_count(node) != parsed
               or fp.lib.es_fast_pending(fp.front.h) != queued):
            assert time.monotonic() < deadline
            time.sleep(0.01)

    def send(key, text):
        got[key] = req(port, "POST", "/books/_search", match(text))

    held = 0.3
    got = {}
    c0 = fast_count(node)
    for _ in range(fp.n_streams):
        fp._sem.acquire()
    try:
        first = threading.Thread(target=send, args=("a", "fox cat"))
        first.start()
        # the drain thread took "a" and now blocks on a stream
        wait_for(c0 + 1, 0)
        time.sleep(0.05)
        second = threading.Thread(target=send, args=("b", "dog bird"))
        second.start()
        wait_for(c0 + 2, 1)
        time.sleep(held)
    finally:
        for _ in range(fp.n_streams):
            fp._sem.release()
    first.join(30)
    second.join(30)
    assert not first.is_alive() and not second.is_alive()
    # "b" sat in the native queue for the whole hold
    assert got["b"]["took"] >= held * 1000
    assert got["a"]["took"] >= held * 1000


def test_flight_recorder_holds_fast_path_launches_and_readbacks(served):
    node, port = served
    req(port, "POST", "/books/_search", match("alpha beta"))
    fr = req(port, "GET", "/_flight_recorder?kind=readback&size=4096")
    sites = {e["site"] for e in fr["events"]}
    assert any(s.startswith("search.fastpath.") for s in sites), sites
    launches = req(port, "GET", "/_flight_recorder?kind=launch&size=4096")
    bm25 = [e for e in launches["events"]
            if e["kernel"].startswith("bm25_")]
    assert bm25, launches["events"][:3]
    assert all(e["capacity"] == node._http.fastpath.q_batch
               and 1 <= e["cohort"] <= e["capacity"]
               and e["queue_wait_ns"] >= 0 for e in bm25)
    assert sum(launches["aggregates"]["fill_histogram_pct"].values()) >= \
        len(bm25)


def test_kernels_reports_the_knn_batcher(served):
    node, port = served
    k0 = req(port, "GET", "/_kernels")["knn"]
    req(port, "POST", "/books/_search", knn([0, 0, 1, 0]))
    k1 = req(port, "GET", "/_kernels")["knn"]
    assert k1["knn_launches"] == k0["knn_launches"] + 1
    assert k1["knn_batched_queries"] == k0["knn_batched_queries"] + 1
    assert k1["knn_avg_batch"] > 0
