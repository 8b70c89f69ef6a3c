"""Cluster-wide telemetry: metrics registry, tracer, trace propagation
through transport headers, failover-path visibility, coordinator
slowlog, and profile-context carry across DeterministicTaskQueue task
boundaries.

Chaos tests ride the same seeded harness as test_search_failover.py:
every schedule (and therefore every metric count and span tree) is a
pure function of its seed.
"""

import pytest

from elasticsearch_tpu.cluster.node import ClusterNode
from elasticsearch_tpu.cluster.search_action import (
    FETCH_PHASE_ACTION,
    QUERY_PHASE_ACTION,
)
from elasticsearch_tpu.search import profile
from elasticsearch_tpu.telemetry import Telemetry
from elasticsearch_tpu.telemetry.metrics import Histogram, MetricsRegistry
from elasticsearch_tpu.telemetry.tracing import Tracer
from elasticsearch_tpu.testing.deterministic import (
    DeterministicTaskQueue,
    DisruptableTransport,
    SimNetwork,
)
from elasticsearch_tpu.testing.faults import (
    ERROR,
    FaultInjectingTransport,
    FaultInjector,
    FaultRule,
)
from elasticsearch_tpu.transport.transport import DiscoveryNode


# --------------------------------------------------------------- registry

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_metrics_registry_counter_gauge_histogram():
    clock = FakeClock()
    reg = MetricsRegistry(clock=clock)
    reg.inc("search.requests")
    reg.inc("search.requests", 2)
    reg.set_gauge("open_contexts", 7)
    reg.observe("latency", 3.0)
    reg.observe("latency", 400.0)
    with reg.timer("latency"):
        clock.t += 0.5        # 500 ms on the injected clock
    d = reg.to_dict()
    assert d["search.requests"] == {"type": "counter", "value": 3}
    assert d["open_contexts"] == {"type": "gauge", "value": 7}
    h = d["latency"]
    assert h["type"] == "histogram" and h["count"] == 3
    assert h["min"] == 3.0 and h["max"] == 500.0
    # cumulative Prometheus-style buckets: le_N counts everything <= N
    assert h["buckets"]["le_5"] == 1       # 3 ms
    assert h["buckets"]["le_500"] == 3     # 3 + 400 + 500 ms
    assert h["buckets"]["le_inf"] == h["count"]
    assert h["sum"] == pytest.approx(903.0)


def test_metrics_labeled_series_render_as_list():
    reg = MetricsRegistry()
    reg.inc("transport.requests.sent", action="a/one")
    reg.inc("transport.requests.sent", action="a/two")
    reg.inc("transport.requests.sent", action="a/one")
    d = reg.to_dict()["transport.requests.sent"]
    assert isinstance(d, list) and len(d) == 2
    assert {s["labels"]["action"]: s["value"] for s in d} == \
        {"a/one": 2, "a/two": 1}
    assert reg.get_value("transport.requests.sent", action="a/one") == 2


def test_histogram_overflow_bucket():
    h = Histogram(buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 100.0):
        h.observe(v)
    assert h.counts == [1, 1, 1]      # disjoint internal tallies
    d = h.to_dict()["buckets"]
    assert d == {"le_1": 1, "le_10": 2, "le_inf": 3}  # cumulative wire


# ----------------------------------------------------------------- tracer

def test_tracer_span_tree_and_ring():
    clock = FakeClock()
    tr = Tracer(clock=clock, node="n1", max_traces=2)
    root = tr.start_span("search")
    clock.t += 0.01
    child = tr.start_span("query", parent=root)
    assert child.trace_id == root.trace_id
    assert tr.open_spans() and len(tr.open_spans()) == 2
    child.finish(outcome="ok")
    root.finish()
    t = tr.trace(root.trace_id)
    assert [s["name"] for s in t["spans"]] == ["search", "query"]
    assert t["tree"][0]["name"] == "search"
    assert [c["name"] for c in t["tree"][0]["children"]] == ["query"]
    assert not tr.open_spans()
    # ring eviction: only the 2 newest root traces survive
    for _ in range(3):
        tr.start_span("s").finish()
    assert tr.trace(root.trace_id) is None
    assert len(tr.recent_traces()) == 2


def test_tracer_joins_remote_trace_ids():
    tr = Tracer(node="n2")
    span = tr.start_span("shard_query", trace_id="n1-t000001",
                         parent_span_id="n1-s000003")
    span.finish()
    t = tr.trace("n1-t000001")
    assert t["spans"][0]["parent_id"] == "n1-s000003"


def test_stage_sink_folds_profile_stages_into_histograms():
    tele = Telemetry(node="x")
    assert not profile.active()
    with profile.stage_sink(tele.stage_sink()):
        assert profile.active()
        profile.record("launch", 2_000_000)      # 2 ms
        profile.record("readback", 500_000)
    d = tele.metrics.to_dict()
    assert d["search.stage.launch"]["count"] == 1
    assert d["search.stage.launch"]["sum"] == pytest.approx(2.0)
    assert d["search.stage.readback"]["count"] == 1
    # profiling() still works independently and stacks with the sink
    with profile.profiling() as rec:
        with profile.stage_sink(tele.stage_sink()):
            profile.record("topk", 1_000_000)
    assert rec["topk"] == 1_000_000
    assert tele.metrics.to_dict()["search.stage.topk"]["count"] == 1


# ------------------------------------------------------------ sim cluster

class ChaosCluster:
    """Sim cluster + shared FaultInjector (same harness as
    test_search_failover.py)."""

    def __init__(self, n_nodes, tmp_path, seed=0):
        self.seed = seed
        self.queue = DeterministicTaskQueue(seed=seed)
        self.network = SimNetwork(self.queue)
        self.injector = FaultInjector(seed=seed, scheduler=self.queue)
        self.nodes = [DiscoveryNode(node_id=f"dn-{i}", name=f"dn{i}")
                      for i in range(n_nodes)]
        self.cluster_nodes = {}
        for node in self.nodes:
            transport = FaultInjectingTransport(
                DisruptableTransport(node, self.network), self.injector)
            cn = ClusterNode(
                transport, self.queue,
                data_path=str(tmp_path / node.name),
                seed_nodes=self.nodes,
                initial_master_nodes=[n.name for n in self.nodes],
                rng=self.queue.random)
            self.cluster_nodes[node.node_id] = cn
        for cn in self.cluster_nodes.values():
            cn.start()

    def run_for(self, seconds):
        self.queue.run_for(seconds)

    def master(self) -> ClusterNode:
        masters = [c for c in self.cluster_nodes.values()
                   if c.is_master()]
        assert len(masters) == 1, f"seed={self.seed}"
        return masters[0]

    def stabilise(self, seconds=60):
        self.run_for(seconds)
        return self.master()

    def call(self, fn, *args, timeout=60, **kwargs):
        box = {}

        def on_done(result, err=None):
            box["result"] = result
            box["err"] = err

        fn(*args, **kwargs, on_done=on_done)
        waited = 0.0
        while "result" not in box and "err" not in box and waited < timeout:
            self.run_for(1.0)
            waited += 1.0
        assert "result" in box or "err" in box, \
            f"seed={self.seed}: call never completed"
        if box.get("err") is not None:
            raise box["err"] if isinstance(box["err"], BaseException) \
                else RuntimeError(box["err"])
        return box["result"]

    def coordinator_excluding(self, *node_ids) -> ClusterNode:
        return next(c for c in self.cluster_nodes.values()
                    if c.local_node.node_id not in node_ids)


def _setup(cluster, index="logs", shards=2, replicas=1, n=20,
           settings=None):
    master = cluster.stabilise()
    cluster.call(master.create_index, index,
                 number_of_shards=shards, number_of_replicas=replicas,
                 settings=settings)
    cluster.run_for(60)
    items = [{"op": "index", "id": f"doc-{i}",
              "source": {"body": f"quick brown fox number {i}", "n": i}}
             for i in range(n)]
    resp = cluster.call(master.bulk, index, items)
    assert resp["errors"] == [], f"seed={cluster.seed}: {resp}"
    cluster.call(master.refresh)
    cluster.run_for(5)
    return master


SORTED_BODY = {"query": {"match": {"body": "fox"}},
               "sort": [{"n": "desc"}], "size": 5}


def _span_structure(tracer, trace_id):
    """Structural view of a trace: (name, parent-name, key tags),
    sorted — timing-free, so it must be identical on seed replay."""
    t = tracer.trace(trace_id)
    by_id = {s["span_id"]: s for s in t["spans"]}
    out = []
    for s in t["spans"]:
        parent = by_id.get(s["parent_id"])
        tags = s["tags"]
        out.append((s["name"], parent["name"] if parent else None,
                    tags.get("node"), tags.get("attempt"),
                    tags.get("outcome"), tags.get("error_type"),
                    tags.get("retryable"), tags.get("will_retry")))
    return sorted(map(repr, out))


@pytest.mark.chaos(seed=11)
def test_injected_failure_increments_retry_metrics_and_spans(
        tmp_path, chaos_seed):
    """Acceptance: a two-shard search with one injected replica failure
    yields search.retries >= 1, a failover to another copy, and a trace
    whose per-shard attempt spans show the failed AND succeeding
    copies."""
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    _setup(cluster)
    coord = cluster.coordinator_excluding("dn-0")
    cluster.injector.add_rule(FaultRule(
        action=QUERY_PHASE_ACTION, node="dn-0", mode=ERROR))
    resp = cluster.call(coord.search, "logs", SORTED_BODY)
    assert resp["_shards"]["failed"] == 0, f"seed={chaos_seed}: {resp}"

    m = coord.telemetry.metrics
    assert m.get_value("search.retries") >= 1, f"seed={chaos_seed}"
    assert m.get_value("search.failovers") >= 1, f"seed={chaos_seed}"
    assert m.get_value("search.backoff_seconds") > 0, f"seed={chaos_seed}"
    assert m.get_value("search.requests") == 1
    # _nodes/stats telemetry shape (the ClusterNode side of the surface)
    tel = coord.telemetry.to_dict()
    assert tel["metrics"]["search.retries"]["value"] >= 1
    assert tel["traces"]["open_spans"] == 0

    traces = coord.telemetry.tracer.recent_traces()
    search_traces = [t for t in traces if t["root"] == "search"]
    assert search_traces, f"seed={chaos_seed}: {traces}"
    trace = coord.telemetry.tracer.trace(search_traces[0]["trace_id"])
    attempts = [s for s in trace["spans"]
                if s["name"].startswith("shard[logs]")]
    failed = [s for s in attempts if s["tags"]["outcome"] == "failed"]
    ok = [s for s in attempts if s["tags"]["outcome"] == "ok"]
    assert failed and ok, f"seed={chaos_seed}: {attempts}"
    f = failed[0]["tags"]
    assert f["node"] == "dn-0" and f["retryable"] is True \
        and f["will_retry"] is True and f["error_type"], \
        f"seed={chaos_seed}: {f}"
    # the retried attempt landed on a DIFFERENT copy
    shard_of = lambda s: s["name"]  # noqa: E731
    retried_ok = [s for s in ok
                  if any(shard_of(s) == shard_of(fs) for fs in failed)]
    assert retried_ok and retried_ok[0]["tags"]["node"] != "dn-0", \
        f"seed={chaos_seed}: {ok}"
    assert retried_ok[0]["tags"]["attempt"] == 2


@pytest.mark.chaos(seed=11)
def test_same_seed_identical_span_structure(tmp_path, chaos_seed):
    """Acceptance: identical span structure on seed replay."""
    def run(path):
        cluster = ChaosCluster(3, path, seed=chaos_seed)
        _setup(cluster)
        coord = cluster.coordinator_excluding("dn-0")
        cluster.injector.add_rule(FaultRule(
            action=QUERY_PHASE_ACTION, node="dn-0", mode=ERROR))
        cluster.call(coord.search, "logs", SORTED_BODY)
        tr = coord.telemetry.tracer
        tid = next(t["trace_id"] for t in tr.recent_traces()
                   if t["root"] == "search")
        return _span_structure(tr, tid), coord.local_node.node_id

    s_a, n_a = run(tmp_path / "a")
    s_b, n_b = run(tmp_path / "b")
    assert n_a == n_b
    assert s_a == s_b, f"seed={chaos_seed}: span structure diverged"


@pytest.mark.chaos(seed=29)
def test_transport_metrics_count_requests_and_headers_propagate(
        tmp_path, chaos_seed):
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    _setup(cluster)
    coord = cluster.coordinator_excluding("dn-0")
    cluster.call(coord.search, "logs", SORTED_BODY)
    m = coord.telemetry.metrics
    sent = m.get_value("transport.requests.sent",
                       action=QUERY_PHASE_ACTION)
    assert sent >= 1, m.to_dict().get("transport.requests.sent")
    # per-action latency histogram exists for the query RPC
    lat = [s for s in m.to_dict()["transport.latency"]
           if s["labels"]["action"] == QUERY_PHASE_ACTION]
    assert lat and lat[0]["count"] >= 1
    # a remote data node recorded handler-side spans joined to a
    # coordinator-minted trace (context crossed the wire via headers)
    coord_id = coord.local_node.node_id
    remote = [cn for nid, cn in cluster.cluster_nodes.items()
              if nid != coord_id]
    joined = []
    for cn in remote:
        for tid, spans in cn.telemetry.tracer._traces.items():
            if tid.startswith(coord.local_node.name):
                joined.extend(s["name"] for s in spans)
    assert "shard_query" in joined or "shard_fetch" in joined, \
        f"seed={chaos_seed}: no remote spans joined the trace: {joined}"


@pytest.mark.chaos(seed=37)
def test_coordinator_slowlog_fires_from_index_settings(
        tmp_path, chaos_seed):
    """Satellite: the distributed coordinator applies the same
    index.search.slowlog.threshold.* checks as the single-node path and
    keeps the shared slowlog_recent entry shape."""
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    _setup(cluster, settings={
        "index.search.slowlog.threshold.query.warn": "0ms"})
    coord = cluster.coordinator_excluding("dn-0")
    cluster.call(coord.search, "logs", SORTED_BODY)
    recent = coord.search_service.slowlog_recent
    assert recent, f"seed={chaos_seed}: coordinator slowlog silent"
    entry = recent[-1]
    # the shared shape, plus the optional observability cross-links
    # (PR-8: trace.id ties slowlog -> _traces; slowest_stage appears
    # when the request was profiled; the flight-recorder summary and
    # client X-Opaque-Id ride along when present)
    assert {"index", "took_ms", "level", "source"} <= set(entry)
    assert set(entry) <= {"index", "took_ms", "level", "source",
                          "trace.id", "slowest_stage", "x_opaque_id",
                          "cohort_fill_pct", "readbacks", "regime",
                          "search.class"}
    assert entry["trace.id"].startswith(coord.local_node.name)
    assert entry["index"] == "logs" and entry["level"] == "warn"
    assert "fox" in entry["source"]


@pytest.mark.chaos(seed=41)
def test_profile_recorder_crosses_task_boundaries(tmp_path, chaos_seed):
    """Satellite: `profile: true`-style stage recording survives
    DeterministicTaskQueue scheduling — shard-side stages recorded in a
    data-node handler task land in the recorder installed around the
    coordinator call."""
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    _setup(cluster)
    coord = cluster.coordinator_excluding("dn-0")
    with profile.profiling() as rec:
        cluster.call(coord.search, "logs", SORTED_BODY)
    stages = set(rec) & set(profile.DEVICE_STAGES + profile.HOST_STAGES)
    assert stages, f"seed={chaos_seed}: shard-side stages lost: {rec}"


@pytest.mark.chaos(seed=43)
def test_fetch_failure_visible_on_trace(tmp_path, chaos_seed):
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    _setup(cluster)
    coord = cluster.coordinator_excluding("dn-2")
    cluster.injector.add_rule(FaultRule(
        action=FETCH_PHASE_ACTION, node="dn-2", mode=ERROR))
    resp = cluster.call(coord.search, "logs", SORTED_BODY)
    assert resp["_shards"]["failed"] == 0, f"seed={chaos_seed}"
    tr = coord.telemetry.tracer
    tid = next(t["trace_id"] for t in tr.recent_traces()
               if t["root"] == "search")
    fetches = [s for s in tr.trace(tid)["spans"]
               if s["name"].startswith("fetch[")]
    outcomes = {s["tags"]["outcome"] for s in fetches}
    # the failed fetch RPC and its retry on another copy both visible
    if cluster.injector.injected_count(FETCH_PHASE_ACTION, "dn-2"):
        assert "failed" in outcomes and "ok" in outcomes, \
            f"seed={chaos_seed}: {fetches}"


@pytest.mark.chaos(seed=53)
def test_malformed_request_closes_root_span(tmp_path, chaos_seed):
    """A parse error raised before the fan-out still routes through the
    completion seam: search.failed counts it and no span stays open."""
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    master = _setup(cluster, n=5)
    with pytest.raises(ValueError):
        cluster.call(master.search, "logs", {"size": "ten"})
    m = master.telemetry.metrics
    assert m.get_value("search.failed") >= 1
    assert m.get_value("search.requests") >= 1
    assert not master.telemetry.tracer.open_spans()


@pytest.mark.chaos(seed=47)
def test_partial_results_metric_on_budget_expiry(tmp_path, chaos_seed):
    from elasticsearch_tpu.testing.faults import DELAY
    cluster = ChaosCluster(3, tmp_path, seed=chaos_seed)
    master = _setup(cluster, index="two", shards=2, replicas=0, n=20)
    n0 = cluster.master().state.routing_table.index("two") \
        .shard(0).primary.current_node_id
    cluster.injector.add_rule(FaultRule(
        action=QUERY_PHASE_ACTION, node=n0, mode=DELAY,
        delay=(10.0, 10.0)))
    resp = cluster.call(
        master.search, "two",
        {"query": {"match": {"body": "fox"}}, "sort": [{"n": "desc"}],
         "size": 20, "timeout": "2s"})
    assert resp["timed_out"] is True, f"seed={chaos_seed}"
    m = master.telemetry.metrics
    assert m.get_value("search.partial_results") >= 1
    assert m.get_value("search.timed_out") >= 1
    # no span may stay open after a budget-expired search
    assert not master.telemetry.tracer.open_spans()
