"""One run of one cell, driven by the files that BENCHMARK.json names.

Everything that belongs to one configuration, traffic mix, body kind,
field type or metric sits in a file of its own, found by name:

- ``configs/<config>.json``  the deployment: docs, index, fields, limits;
- ``traffic/<traffic>.json`` the mix: loop, rate or connections, body
  kind and its parameters, the size of the checked sample;
- ``fields/<type>.py``       how a field's data is made from the seed;
- ``bodies/<kind>.py``       queries, their JSON, the reference, the
  control;
- ``metrics/<name>.py``      ``read(ctx)`` of one metric;
- ``work/<kernel>.py``       the algorithm's operations and bytes.

A run: make the data from the seed, start a default ``Node`` in this
process, mount the data (mount.py), warm up, then drive ``POST
/<index>/_search`` over loopback HTTP with the benchmark's own C load
generator for the window. After the window: read the counters and the
device's peak memory, free the node, compare a sample of the answers
with the reference, and reduce the metrics.

A configuration's ``settings.number_of_shards`` N splits its ``docs``
into N equal primary shards, each made from streams of its own and
mounted as one segment; shard ``s``'s doc ``d`` has the ``_id``
``s * docs / N + d``. The data is a list of N dicts, field -> what the
field's builder made.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import types
import urllib.request
import zlib
from typing import Optional

import numpy as np

from benchmark import loadgen, oracle
from benchmark import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
REGISTER_TIMEOUT_S = 900.0
# the fixed multiset of inter-arrival gaps every seed shares (its order
# is the seed's): runs of different seeds then offer the same load
_ARRIVALS_SEED = 20260415


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec
def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


def cell(name: str, overrides: Optional[dict] = None) -> tuple:
    """(workload entry, configuration file, traffic file, metric entries
    that apply to this cell). ``overrides`` replace keys of the
    configuration, the workload entry (``chips``) or the traffic file
    (the CPU rehearsal's sizes)."""
    spec = load_json(ROOT, "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(by_name)}")
    w = dict(by_name[name])
    conf_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT, conf_entry["file"])
    traffic = load_json(HERE, "traffic", w["traffic"] + ".json")
    for key, val in (overrides or {}).items():
        (config if key in config else w if key in w else traffic)[key] = val
    n = shards(config)
    if n > w["chips"]:
        raise SystemExit(f"{name}: {w['config']} has {n} primary shards, "
                         f"more than the cell's {w['chips']} chip(s); "
                         "each shard needs a chip of its own")
    if n > 1 and config["fast_path"]:
        raise SystemExit(f"{name}: the fast path serves single-shard "
                         f"indices only; {w['config']} has {n} shards, so "
                         "its fast_path must be false")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]
    metrics = {"end_to_end": [m for m in spec["end_to_end"] if applies(m)],
               "per_layer": [m for m in spec["per_layer"] if applies(m)]}
    return w, config, traffic, metrics


def rng(seed: int, stream: str):
    """An independent generator per (seed, stream)."""
    return np.random.default_rng(
        [seed & ((1 << 64) - 1), zlib.crc32(stream.encode())])


def module(kind: str, name: str):
    return importlib.import_module(f"benchmark.{kind}.{name}")


def metric_reader(name: str):
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------- the node
def http(port: int, method: str, path: str, body=None) -> dict:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return json.loads(resp.read())


#: histograms of ``GET /_nodes/stats`` ``telemetry.metrics`` whose count
#: and sum (ms) the counters carry
HISTOGRAMS = ("fastpath.queue_wait", "fastpath.inflight",
              "http.fallback.queue_wait", "knn.queue_wait", "knn.rerank")


def counters(node, port: int) -> dict:
    """Public counters (``GET /_kernels``, ``GET /_nodes/stats``) and the
    in-process ones. A key the node does not serve reads 0."""
    from benchmark import mount
    k = http(port, "GET", "/_kernels")
    t = k["totals"]
    serving = (k.get("serving") or {}).get("counters", {})
    out = {"first_executions": t["count"] + t["cache_hits"]}
    for key in ("cohorts", "fast_queries", "bounced", "errors"):
        out[key] = serving.get(key, 0)
    mesh = k.get("mesh") or {}
    mc = mesh.get("counters") or {}
    out["mesh_searches"] = mesh.get("mesh_searches", 0)
    out["mesh.dispatch.shard"] = mc.get("dispatch.shard", 0)
    out["mesh.fallback"] = sum(v for key, v in mc.items()
                               if key.startswith("fallback."))
    node_stats = next(iter(http(port, "GET", "/_nodes/stats")["nodes"]
                           .values()))
    hists = node_stats.get("telemetry", {}).get("metrics", {})
    for name in HISTOGRAMS:
        h = hists.get(name, {})
        out[name + ".count"] = h.get("count", 0)
        out[name + ".sum"] = h.get("sum", 0.0)
    out.update(mount.counters(node))
    return out


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, so that only the first run of a cell there compiles. The
    program takes a cache directory that is already set. The CPU
    rehearsal keeps none."""
    import jax
    if jax.default_backend() == "cpu":
        return
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def shards(config: dict) -> int:
    return int(config["settings"].get("number_of_shards", 1))


def shard_docs(config: dict) -> int:
    """Docs of each primary shard: ``docs`` split evenly."""
    n, s = int(config["docs"]), shards(config)
    if n % s:
        raise ValueError(f"{n} docs do not split evenly into {s} shards")
    return n // s


def make_data(config: dict, seed: int) -> list:
    """One dict per primary shard, field -> what its builder made from
    the stream ``field:<f>`` (shard 0) or ``field:<f>:shard<s>``."""
    t = time.monotonic()
    n = shard_docs(config)
    data = [{f: module("fields", spec["type"]).build(
                 rng(seed, f"field:{f}" + (f":shard{s}" if s else "")),
                 n, spec)
             for f, spec in config["fields"].items()}
            for s in range(shards(config))]
    log(f"data: {len(data)} x {n} docs, fields {sorted(data[0])} in "
        f"{time.monotonic() - t:.1f} s")
    return data


@contextlib.contextmanager
def serving(config: dict, data: list, body, params: dict):
    """A default ``Node`` in this process serving the configuration's
    index over loopback HTTP, mounted and warm; yields (node, port)."""
    from elasticsearch_tpu.node import Node
    from benchmark import mount
    index = config["index"]
    n = shard_docs(config)
    with tempfile.TemporaryDirectory() as tmp:
        node = Node(data_path=tmp)
        try:
            port = node.start(0)
            http(port, "PUT", f"/{index}", {
                "settings": {"index": config["settings"]},
                "mappings": {"properties": {
                    f: module("fields", spec["type"]).mapping(spec)
                    for f, spec in config["fields"].items()}}})
            t = time.monotonic()
            mount.mount(node, index, [
                mount.segment(f"{index}{s}", n, config["fields"], d,
                              id_base=s * n)
                for s, d in enumerate(data)])
            if config["fast_path"]:
                mount.wait_fast_path(node, index, REGISTER_TIMEOUT_S)
            if hasattr(body, "warm"):
                body.warm(node, index, params)
            log(f"mount and warm-up: {time.monotonic() - t:.1f} s")
            yield node, port
        finally:
            node.close()


def device_info(chips: int) -> dict:
    """The device as JAX reports it; ``memory_peak_bytes`` is the peak of
    the fullest of the cell's ``chips`` devices."""
    import jax
    devs = jax.devices()
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs[:chips]]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


# -------------------------------------------------------------- the load
def schedule(traffic: dict, seconds: float, order):
    """Due times of an open loop: ``rate * seconds`` requests whose
    inter-arrival gaps are one fixed exponential multiset, put in the
    order that the generator ``order`` draws. None for a closed loop."""
    if traffic["loop"] != "open":
        return None
    n = max(1, int(round(traffic["rate"] * seconds)))
    gaps = np.random.default_rng(_ARRIVALS_SEED).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = order.permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def loads(traffic: dict, seconds: float, seed: int) -> tuple:
    """The warm-up's and the window's (due times or None, request count):
    a closed loop gets bodies for ``max_rate`` requests a second."""
    out = []
    for stream, secs in (("warm", traffic["warm_seconds"]),
                         ("window", seconds)):
        due = schedule(traffic, secs, rng(seed, stream + ":arrivals"))
        out.append((due, len(due) if due is not None
                    else int(traffic["max_rate"] * secs)))
    return tuple(out)


def drive(port: int, index: str, body_mod, params: dict, qs,
          traffic: dict, seconds: float, keep: np.ndarray, due):
    bodies = [body_mod.encode(q, params) for q in qs]
    return loadgen.run(
        port, f"/{index}/_search", bodies, due, traffic["connections"],
        seconds, traffic["drain_seconds"], keep, traffic["keep_bytes"])


def inputs(traffic: dict, body, data, params: dict, seed: int,
           seconds: float) -> types.SimpleNamespace:
    """What the seed makes of a run's load: the warm-up's and the
    window's due times and queries, and the checked sample. One draw of
    queries covers both, so that no query repeats in a run. The sample
    is drawn among the requests due in the window (a closed loop: among
    its first ``check_within``)."""
    (warm_due, warm_n), (due, n) = loads(traffic, seconds, seed)
    qs = body.queries(data, params, rng(seed, "queries"), warm_n + n)
    pool = n if due is not None else min(n, traffic["check_within"])
    sample = np.sort(rng(seed, "sample").choice(
        pool, min(pool, traffic["check_sample"]), replace=False))
    return types.SimpleNamespace(warm_due=warm_due, warm_qs=qs[:warm_n],
                                 due=due, qs=qs[warm_n:], sample=sample)


# ----------------------------------------------------------------- a run
def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, overrides: Optional[dict] = None) -> dict:
    use_compile_cache()
    w, config, traffic, metric_specs = cell(name, overrides)
    body = module("bodies", traffic["body"])
    params = traffic["params"]
    index = config["index"]
    data = make_data(config, seed)
    got = inputs(traffic, body, data, params, seed, seconds)
    due, qs, sample = got.due, got.qs, got.sample
    keep = np.zeros(len(qs), np.uint8)
    keep[sample] = 1

    with serving(config, data, body, params) as (node, port):
        drive(port, index, body, params, got.warm_qs, traffic,
              traffic["warm_seconds"], np.zeros(len(got.warm_qs), np.uint8),
              got.warm_due)
        before = counters(node, port)
        setup_s = time.monotonic() - t_start
        with (trace_mod.capture(TRACE_DIR) if trace
              else contextlib.nullcontext()) as mark:
            res = drive(port, index, body, params, qs, traffic, seconds,
                        keep, due)
        after = counters(node, port)
        device = device_info(w["chips"])
    log(f"window: {res.sent} sent, "
        f"{int(np.sum(res.status == 200))} answered 200")

    red = None
    if trace:
        planes = trace_mod.load(TRACE_DIR)
        lo = res.t0_ns + trace_mod.clock_offset(planes, mark["mono_ns"])
        red = trace_mod.reduce(planes, lo, lo + int(seconds * 1e9))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    checks = judge(body, data, params, qs, sample, res, config, traffic,
                   due, seconds)
    ctx = context(res, due, seconds, setup_s, before, after, red, data,
                  params, body, qs, device["kind"], w)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_specs[kind]:
        v = metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": ctx.attempted,
              "failed": ctx.failed, "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result


def in_window(res, due, seconds: float) -> np.ndarray:
    """Indices of the requests the window holds: every one due in it
    (open), or every one sent in it (closed)."""
    if due is not None:
        return np.arange(len(due))
    return np.nonzero((res.send_s >= 0) & (res.send_s < seconds))[0]


def context(res, due, seconds, setup_s, before, after, red, data, params,
            body, qs, device_kind, workload) -> types.SimpleNamespace:
    idx = in_window(res, due, seconds)
    ok = res.shaped[idx]
    end = float(max(res.done_s.max(), res.send_s.max(),
                    seconds if due is None else due[-1]))
    start = due[idx] if due is not None else res.send_s[idx]
    done = np.where(ok, res.done_s[idx], end)    # a failure: missing
    completed = np.nonzero(res.shaped & (res.done_s >= 0)
                           & (res.done_s <= seconds))[0]
    ctx = types.SimpleNamespace(
        loop="open" if due is not None else "closed",
        seconds=seconds, setup_s=setup_s, attempted=int(len(idx)),
        failed=int(np.sum(~ok)), latency_s=done - start,
        late_s=(res.send_s[idx] - due[idx]) if due is not None else None,
        completed=int(len(completed)),
        before=before, after=after, trace=red, data=data, params=params,
        body=body, device_kind=device_kind, workload=workload,
        queries_done=[qs[i] for i in idx[ok]],
        queries_completed=[qs[i] for i in completed])
    ctx.delta = lambda key: ctx.after[key] - ctx.before[key]
    return ctx


def answers(res, sample) -> tuple:
    """(indices, [(ids, scores)]) of the sampled requests whose answer is
    in, and how many sampled requests went out and brought no search
    answer back."""
    have, got, lost = [], [], 0
    for i in map(int, sample):
        if res.send_s[i] < 0:
            continue            # a closed loop never sent it
        raw = res.kept.get(i)
        if not res.shaped[i] or raw is None:
            lost += 1
            continue
        hits = json.loads(raw)["hits"]["hits"]
        have.append(i)
        got.append(([h["_id"] for h in hits], [h["_score"] for h in hits]))
    return have, got, lost


def judge(body, data, params, qs, sample, res, config, traffic, due,
          seconds: float) -> dict:
    """The numbers that decide ``correct``, each beside its limit: the
    worst score and rank gaps of the sampled answers against the
    reference, and the requests of the window that failed or never
    came."""
    limits = config["limits"][traffic["body"]]
    have, got, lost = answers(res, sample)
    t = time.monotonic()
    gaps = [oracle.judge(ids, scores, ref, params["size"])
            for (ids, scores), ref in
            zip(got, body.reference(data, [qs[i] for i in have], params))]
    log(f"reference: {len(gaps)} sampled answers compared in "
        f"{time.monotonic() - t:.1f} s")
    idx = in_window(res, due, seconds)
    failed = max(int(np.sum(~res.shaped[idx])), lost)
    return {
        "score_gap": {"value": max((g["score_gap"] for g in gaps),
                                   default=1.0),
                      "limit": limits["score_gap"]},
        "rank_gap": {"value": max((g["rank_gap"] for g in gaps),
                                  default=1.0),
                     "limit": limits["rank_gap"]},
        "failed": {"value": failed, "limit": 0},
    }
