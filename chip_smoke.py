"""Drive the REST search path once on the chip and check its answers.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the sharded-index path on four chips

One chip: BASELINE config 1 (with the fields of configs 2 and 4) — one
MS MARCO-passage-shaped index of 2,000,000 docs over a 100k Zipf
vocabulary, a skewed ``keyword`` field and a 768-d cosine
``dense_vector`` field, generated from ``--seed``. The full segment is
mounted the way ``bench.py`` mounts it (indexing 2M docs through
``_bulk`` takes longer than the run may); a 50k-doc slice of the same
corpus is indexed through ``_bulk`` + ``_refresh`` into a second index,
so the indexing → refresh → device-upload path runs too. A ``Node`` with
default settings (native C++ front, ``FastPathServer``) then serves
``match`` top-1000, ``bool`` + ``term`` filter and ``knn`` queries over
real HTTP, and every answer is compared with an exact float64/float32
host oracle computed from the same corpus.

Four chips (``--chips 4``): only the sharded-index path — a 4-shard
index at the same per-shard size, served through ``MeshSearchBackend``
over HTTP and compared with the same oracle (ES default per-shard IDF).

The script fails (exit 1, no result line) when there is no TPU, when
any check fails, and when any part of the main path fell back: no fast
path, a fast-path error, no match query on the v2m kernel, a
Pallas kernel in interpret mode, or a mesh ``fallback.error``. The last
line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import tempfile
import time
import traceback
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

VOCAB = 100_000
DIMS = 768
N_TAGS = 500
HOT_TAGS = 20          # bool filters draw from the most common tags
K = 1000               # match / bool page size
KNN_K, KNN_CANDIDATES = 10, 100
BULK_DOCS = 50_000
RECALL_FLOOR_F32 = 0.999
# bf16 rounding bound of one cosine score on unit vectors, in the
# (1 + cos) / 2 score space: both operands round with unit roundoff
# u = 2^-8, so |cos~ - cos| <= 2u + u^2, plus f32 accumulation over 768
# terms (< 5e-5), halved by the transform
KNN_BF16_DELTA = (2 * 2.0 ** -8 + 2.0 ** -16 + 5e-5) / 2


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """Collects failed checks; a run passes only with none."""

    def __init__(self):
        self.failures: list = []

    def check(self, ok, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            log(f"CHECK FAILED: {what}")
        return bool(ok)


# ------------------------------------------------------------------ HTTP
def http(port: int, method: str, path: str, body=None,
         ndjson: bool = False):
    data = None
    if body is not None:
        data = body if isinstance(body, bytes) else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data, method=method,
        headers={"Content-Type": "application/x-ndjson" if ndjson
                 else "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def compile_line() -> str:
    """First executions so far, split as the compile tracker classifies
    them: cold compiles, and loads its key store attributes to the
    persistent compile cache."""
    from elasticsearch_tpu.telemetry.engine import TRACKER
    t = TRACKER.totals()
    return (f"{t['count']} compiled ({t['ms'] / 1000.0:.1f} s summed), "
            f"{t['cache_hits']} loaded from the compile cache")


def search_all(port: int, index: str, bodies: list,
               clients: int = 1) -> list:
    """Searches from ``clients`` concurrent clients. The fast path's
    cohort shapes are compiled at registration, so its queries go 16 at
    a time; Python-path queries go one at a time, because every new
    (cohort width, selection bucket) pair there is a compile of about a
    minute on TPU."""
    with ThreadPoolExecutor(max_workers=clients) as ex:
        return list(ex.map(
            lambda b: http(port, "POST", f"/{index}/_search", b), bodies))


def concurrently(*calls) -> list:
    """Run ``(fn, *args)`` calls in parallel threads; their results."""
    with ThreadPoolExecutor(max_workers=len(calls)) as ex:
        futs = [ex.submit(fn, *a) for fn, *a in calls]
        return [f.result() for f in futs]


def queries_in(rng, dfs: list, n: int, count: int, top: int,
               ratio: int) -> list:
    """``count`` queries of make_queries' distribution whose selection
    (the largest over ``dfs``, one df vector per shard) spans (hi /
    ratio, hi] postings blocks, hi = ``top`` scaled from 2M docs to
    ``n`` — a bucket or two, so the Python path compiles few shapes."""
    from elasticsearch_tpu.bench.corpus import BLOCK, make_queries
    hi = 64
    while hi < top * n // 2_000_000:
        hi *= 2
    lo = hi // ratio
    out: list = []
    for _ in range(50):
        for q in make_queries(rng, dfs[0], n, 4 * count, max_blocks=hi):
            nb = max(int(((df[q] + BLOCK - 1) // BLOCK).sum())
                     for df in dfs)
            if lo < nb <= hi:
                out.append(q)
        if len(out) >= count:
            return out[:count]
    raise RuntimeError(f"no {count} queries span ({lo}, {hi}] blocks")


# ---------------------------------------------------------------- oracle
def topk(scores: np.ndarray, k: int) -> np.ndarray:
    """Exact top-k docids of positive scores, (score desc, docid asc)."""
    pos = np.nonzero(scores > 0)[0]
    if len(pos) > 4 * k:
        kth = np.partition(scores[pos], len(pos) - k)[len(pos) - k]
        pos = pos[scores[pos] >= kth]
    return pos[np.lexsort((pos, -scores[pos]))][:k]


def hits_of(resp) -> tuple:
    hits = resp["hits"]["hits"]
    return ([h["_id"] for h in hits],
            np.asarray([h["_score"] for h in hits], np.float64))


def check_ranked(smoke: Smoke, label: str, docs: np.ndarray,
                 scores: np.ndarray, truth: np.ndarray) -> tuple:
    """Top-10 agreement (tie-aware: rank i holds a doc with the oracle's
    rank-i score) and returned scores vs the oracle; returns the strict
    set recall against ``truth`` and the tie-aware one (a returned doc
    scoring the oracle's last included score counts as a hit)."""
    m = min(10, len(truth))
    ok = len(docs) >= m and np.allclose(
        scores[docs[:m]], scores[truth[:m]], rtol=1e-5, atol=1e-6)
    smoke.check(ok, f"{label}: top-10 disagrees with the oracle")
    n = max(1, len(truth))
    strict = len(set(docs.tolist()) & set(truth.tolist())) / n
    if not len(truth):
        return strict, strict
    tied = min(n, int((scores[docs[:n]] >= scores[truth[-1]]).sum())) / n
    return strict, tied


def check_bm25(smoke: Smoke, label: str, resps: list, oracle: list,
               floor: float, parse_id) -> float:
    """oracle[i] = (scores, truth) for query i; ``parse_id`` maps a
    hit `_id` to the oracle's doc key. Returns the mean recall@K."""
    recalls, tie_recalls = [], []
    for i, (resp, (scores, truth)) in enumerate(zip(resps, oracle)):
        ids, got = hits_of(resp)
        docs = np.asarray([parse_id(x) for x in ids], np.int64)
        if not smoke.check(
                len(docs) == 0 or (docs.min() >= 0
                                   and docs.max() < len(scores)),
                f"{label} q{i}: hit id outside the index"):
            continue
        smoke.check(np.allclose(got, scores[docs], rtol=1e-4, atol=1e-5),
                    f"{label} q{i}: returned scores differ from the oracle")
        strict, tied = check_ranked(smoke, f"{label} q{i}", docs, scores,
                                    truth)
        recalls.append(strict)
        tie_recalls.append(tied)
    mean = float(np.mean(recalls)) if recalls else 0.0
    log(f"{label}: {len(resps)} queries, recall@{K} mean {mean:.6f} "
        f"min {min(recalls, default=0.0):.6f} (floor {floor}); "
        f"tie-aware mean {np.mean(tie_recalls or [0.0]):.6f}")
    smoke.check(mean >= floor, f"{label}: recall@{K} {mean:.6f} < {floor}")
    return mean


def text_of(q) -> str:
    from elasticsearch_tpu.bench.corpus import term_name
    return " ".join(term_name(t) for t in q)


def match_body(q) -> dict:
    return {"query": {"match": {"title": text_of(q)}}, "size": K,
            "_source": False}


def bool_body(q, tag: str) -> dict:
    return {"query": {"bool": {"must": [{"match": {"title": text_of(q)}}],
                               "filter": [{"term": {"tag": tag}}]}},
            "size": K, "_source": False}


MAPPING = {"title": {"type": "text"}, "tag": {"type": "keyword"}}


def create_index(port: int, name: str, shards: int, vectors: bool):
    props = dict(MAPPING)
    if vectors:
        props["vec"] = {"type": "dense_vector", "dims": DIMS,
                        "similarity": "cosine"}
    http(port, "PUT", f"/{name}",
         {"settings": {"index": {"number_of_shards": shards,
                                 "number_of_replicas": 0}},
          "mappings": {"properties": props}})


def mount(node, name: str, segments: list) -> None:
    """Swap the index's shards onto prebuilt segments (bench.py's
    mount): the engines then serve them like any refreshed segment."""
    idx = node.indices_service.get(name)
    for eng, seg in zip(idx.shards, segments):
        with eng._lock:
            eng._segments = [seg]
            eng._epoch += 1


def make_tags(rng, n: int) -> np.ndarray:
    """Skewed keyword values: tag c000 is the most common."""
    return np.minimum(rng.random(n) ** 2 * N_TAGS,
                      N_TAGS - 1).astype(np.int32)


TAG_VALUES = [f"c{i:03d}" for i in range(N_TAGS)]


# -------------------------------------------------------------- one chip
def bulk_slice(port: int, corpus, tags: np.ndarray, n: int) -> float:
    """Index the corpus's first ``n`` docs through ``_bulk`` and refresh;
    returns the seconds it took."""
    from elasticsearch_tpu.bench.corpus import doc_texts
    texts = doc_texts(corpus, n)
    create_index(port, "passages_bulk", 1, vectors=False)
    t0 = time.perf_counter()
    for lo in range(0, n, 5000):
        lines = []
        for i in range(lo, min(n, lo + 5000)):
            lines.append(json.dumps({"index": {"_index": "passages_bulk",
                                               "_id": str(i)}}))
            lines.append(json.dumps({"title": texts[i],
                                     "tag": TAG_VALUES[tags[i]]}))
        resp = http(port, "POST", "/_bulk",
                    ("\n".join(lines) + "\n").encode(), ndjson=True)
        if resp.get("errors"):
            raise RuntimeError(f"_bulk reported errors: {str(resp)[:500]}")
    http(port, "POST", "/passages_bulk/_refresh")
    return time.perf_counter() - t0


def knn_oracle(vecs: np.ndarray, queries: np.ndarray) -> np.ndarray:
    """Exact cosine scores, (1 + cos) / 2, [n_docs, n_queries] float32."""
    norms = np.linalg.norm(vecs, axis=1)
    qn = queries / np.linalg.norm(queries, axis=1, keepdims=True)
    sims = vecs @ qn.T
    sims /= np.where(norms > 0, norms, 1.0)[:, None]
    return (1.0 + sims) / 2.0


def check_knn(smoke: Smoke, resps: list, ex: np.ndarray) -> None:
    """Recall@k of each kNN answer must meet its certificate: a true
    top-k doc d is certainly nominated by the bf16 scan when fewer than
    num_candidates other docs score within 2*delta of it exactly."""
    recalls, certs = [], []
    for j, resp in enumerate(resps):
        s = ex[:, j]
        truth = topk(s, KNN_K)
        ids, got = hits_of(resp)
        docs = np.asarray([int(x) for x in ids], np.int64)
        cert = np.mean([int((s >= s[d] - 2 * KNN_BF16_DELTA).sum())
                        <= KNN_CANDIDATES for d in truth])
        recall, _ = check_ranked(smoke, f"knn q{j}", docs, s, truth)
        smoke.check(np.allclose(got, s[docs], rtol=1e-5, atol=1e-6),
                    f"knn q{j}: returned scores differ from the oracle")
        smoke.check(recall >= cert,
                    f"knn q{j}: recall@{KNN_K} {recall} below its "
                    f"certificate {cert}")
        recalls.append(recall)
        certs.append(cert)
    log(f"knn: {len(resps)} queries, recall@{KNN_K} mean "
        f"{np.mean(recalls):.6f}, certificate mean {np.mean(certs):.6f} "
        f"(bf16 delta {KNN_BF16_DELTA:.6f}, num_candidates "
        f"{KNN_CANDIDATES})")


def run_one_chip(args, smoke: Smoke) -> None:
    from elasticsearch_tpu.bench.corpus import (bm25_exact, build_corpus,
                                                make_queries,
                                                mount_segment)
    from elasticsearch_tpu.node import Node
    from elasticsearch_tpu.ops import merge
    from elasticsearch_tpu.telemetry.engine import TRACKER

    n = args.docs
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    corpus = build_corpus(rng, n, VOCAB)
    tags = make_tags(rng, n)
    vecs = rng.standard_normal((n, DIMS), dtype=np.float32)
    log(f"corpus: {n} docs, {corpus['n_postings']} postings, "
        f"{corpus['block_docids'].shape[0]} blocks, {DIMS}-d vectors "
        f"(built in {time.perf_counter() - t0:.1f} s)")
    seg = mount_segment("passages0", corpus, [str(i) for i in range(n)],
                        tags, TAG_VALUES, vecs)

    qrng = np.random.default_rng(args.seed + 1)
    match_q = make_queries(qrng, corpus["df"], n, args.queries)
    bool_q = queries_in(qrng, [corpus["df"]], n, args.queries, 4096, 4)
    bool_tags = qrng.integers(0, HOT_TAGS, args.queries)
    n_bulk = min(BULK_DOCS, n // 2)
    slice_df = np.bincount(np.repeat(
        np.arange(VOCAB), np.diff(corpus["group_start"]))[
            corpus["doc_ids"] < n_bulk], minlength=VOCAB)
    slice_q = queries_in(qrng, [slice_df], n_bulk,
                         max(8, args.queries // 4), 1024 * 40, 4)
    knn_q = qrng.standard_normal((args.queries, DIMS), dtype=np.float32)

    with tempfile.TemporaryDirectory() as tmp:
        node = Node(data_path=tmp)
        try:
            port = node.start(0)
            fp = getattr(node._http, "fastpath", None)
            smoke.check(fp is not None,
                        f"no native front / FastPathServer "
                        f"({type(node._http).__name__} serves)")
            if fp is None:
                return
            create_index(port, "passages", 1, vectors=True)
            t_mount = time.perf_counter()
            mount(node, "passages", [seg])
            deadline = t_mount + 900
            while fp._reg is None and time.perf_counter() < deadline:
                time.sleep(0.25)
            t_reg = time.perf_counter() - t_mount
            if not smoke.check(fp._reg is not None,
                               f"fast path never registered ({t_reg:.0f} s)"):
                return
            log(f"startup: fastpath registered {t_reg:.1f} s after the "
                f"mount; warm ladder {fp.warm_seconds:.1f} s; first "
                f"executions: {compile_line()}")
            log(f"fast path: nb_buckets={list(fp.nb_buckets)} "
                f"ess_buckets={list(fp.ESS_BUCKETS)} "
                f"streams={fp.n_streams} "
                f"rail={np.dtype(fp._weight_dtype()).name}")

            bulk_s = bulk_slice(port, corpus, tags, n_bulk)
            log(f"bulk slice: {n_bulk} docs through _bulk + _refresh "
                f"into passages_bulk in {bulk_s:.1f} s")

            rail64 = np.dtype(fp._weight_dtype()) == np.float64
            floor = 1.0 if rail64 else RECALL_FLOOR_F32
            log(f"recall floor {floor} "
                f"({'float64' if rail64 else 'float32'} rail)")

            t_q = time.perf_counter()
            match_r = search_all(port, "passages",
                                 [match_body(q) for q in match_q], 16)
            match_v2m = sum(v for k, v in
                            fp.serving_stats()["dispatch"].items()
                            if k.startswith("v2m:"))
            # one client per index: at most one plan-path query per
            # index in flight, so the two indices' compiles overlap
            bool_r, slice_r = concurrently(
                (search_all, port, "passages",
                 [bool_body(q, TAG_VALUES[t])
                  for q, t in zip(bool_q, bool_tags)]),
                (search_all, port, "passages_bulk",
                 [match_body(q) for q in slice_q]))
            knn_r = search_all(port, "passages", [
                {"knn": {"field": "vec", "query_vector": v.tolist(),
                         "k": KNN_K, "num_candidates": KNN_CANDIDATES},
                 "size": KNN_K, "_source": False} for v in knn_q])
            log(f"queries: {len(match_r)} match, {len(bool_r)} bool+term, "
                f"{len(knn_r)} knn, {len(slice_r)} match on the bulk "
                f"slice, served in {time.perf_counter() - t_q:.1f} s; "
                f"first executions so far: {compile_line()}")

            stats = dict(fp.stats)
            mesh_counters = dict(node.search_service.mesh_executor.counters)
            cache = TRACKER.persistent_stats()
            log(f"fast path stats: fast_queries={stats['fast_queries']} "
                f"bounced={stats['bounced']} errors={stats['errors']} "
                f"cohorts={stats['cohorts']} "
                f"dispatch={json.dumps(fp.dispatch, sort_keys=True)}")
            log(f"compile cache: dir={cache.get('jax_cache_dir')} "
                f"hits={cache.get('hits', 0)} "
                f"misses={cache.get('misses', 0)} "
                f"(key store enabled={cache['enabled']})")
            log(f"mesh counters: {json.dumps(mesh_counters, sort_keys=True)}")

            smoke.check(stats["fast_queries"] > 0,
                        "the fast path served no query")
            smoke.check(stats["errors"] == 0,
                        f"fast path counted {stats['errors']} errors")
            smoke.check(not merge._interpret(),
                        "the Pallas merge runs in interpret mode")
            smoke.check(match_v2m > 0,
                        f"no match query of {len(match_q)} ran the v2m "
                        f"(Pallas merge) kernel")
            smoke.check(mesh_counters.get("fallback.error", 0) == 0,
                        "the mesh counted fallback.error")

            t_o = time.perf_counter()
            sid = int
            match_o = []
            for q in match_q:
                s = bm25_exact(corpus, q)
                match_o.append((s, topk(s, K)))
            check_bm25(smoke, "match", match_r, match_o, floor, sid)
            bool_o = []
            for q, t in zip(bool_q, bool_tags):
                s = bm25_exact(corpus, q)
                s = np.where(tags == t, s, 0.0)
                bool_o.append((s, topk(s, K)))
            check_bm25(smoke, "bool+term", bool_r, bool_o, floor, sid)
            slice_o = []
            for q in slice_q:
                s = bm25_exact(corpus, q, n_docs=n_bulk)
                slice_o.append((s, topk(s, K)))
            check_bm25(smoke, "bulk-slice match", slice_r, slice_o, floor,
                       sid)
            check_knn(smoke, knn_r, knn_oracle(vecs, knn_q))
            log(f"oracle: computed and compared in "
                f"{time.perf_counter() - t_o:.1f} s")
        finally:
            node.close()


# ------------------------------------------------------------ four chips
def run_four_chips(args, smoke: Smoke) -> None:
    import jax

    from elasticsearch_tpu.bench.corpus import (bm25_exact, build_corpus,
                                                mount_segment)
    from elasticsearch_tpu.node import Node

    shards, n = 4, args.docs
    t0 = time.perf_counter()

    def shard(s):
        rng = np.random.default_rng([args.seed, s])
        corpus = build_corpus(rng, n, VOCAB)
        tag = make_tags(rng, n)
        return corpus, tag, mount_segment(
            f"passages{s}", corpus, [f"{s}-{i}" for i in range(n)], tag,
            TAG_VALUES)

    corpora, tags, segs = zip(*concurrently(
        *[(shard, s) for s in range(shards)]))
    log(f"corpus: {shards} shards x {n} docs "
        f"(built in {time.perf_counter() - t0:.1f} s)")
    # every query's largest per-shard selection lands in one block
    # bucket ((1024, 2048] at 2M docs) and every filter names the same
    # tag: the mesh
    # program compiles once per (bucket, clause shape), about a minute
    # each on TPU
    qrng = np.random.default_rng(args.seed + 1)
    dfs = [c["df"] for c in corpora]
    match_q = queries_in(qrng, dfs, n, args.queries, 2048, 2)
    bool_q = queries_in(qrng, dfs, n, args.queries, 2048, 2)
    bool_tags = [0] * args.queries

    with tempfile.TemporaryDirectory() as tmp:
        node = Node(data_path=tmp)
        try:
            port = node.start(0)
            create_index(port, "passages", shards, vectors=False)
            mount(node, "passages", segs)
            t_q = time.perf_counter()
            # one client per query kind: the two mesh programs compile
            # side by side
            match_r, bool_r = concurrently(
                (search_all, port, "passages",
                 [match_body(q) for q in match_q]),
                (search_all, port, "passages",
                 [bool_body(q, TAG_VALUES[t])
                  for q, t in zip(bool_q, bool_tags)]))
            log(f"queries: {len(match_r)} match, {len(bool_r)} bool+term "
                f"over HTTP in {time.perf_counter() - t_q:.1f} s "
                f"(first launches compile)")
            log(f"first executions: {compile_line()}")
            mesh = node.search_service.mesh_executor
            counters = dict(mesh.counters)
            residency = mesh.residency()
            log(f"mesh counters: {json.dumps(counters, sort_keys=True)}")
            log(f"mesh residency (bytes per device): "
                f"{json.dumps(residency, sort_keys=True)}")
            smoke.check(len(residency) == shards and len(jax.devices()) >=
                        shards, f"the slab spans {len(residency)} devices, "
                                f"not {shards}")
            smoke.check(counters.get("dispatch.shard", 0)
                        >= len(match_r) + len(bool_r),
                        "not every query was dispatched on the mesh")
            smoke.check(counters.get("fallback.error", 0) == 0,
                        "the mesh counted fallback.error")
            rail = "float64" if jax.config.jax_enable_x64 else "float32"
            floor = 1.0 if rail == "float64" else RECALL_FLOOR_F32
            log(f"recall floor {floor} ({rail} rail)")

            def merged(q, tag=None):
                """Per-shard exact BM25 (each shard's own statistics),
                merged by (score desc, shard, docid) — keyed by
                shard * n + docid."""
                full = np.concatenate([
                    np.where(tags[s] == tag, bm25_exact(corpora[s], q), 0.0)
                    if tag is not None else bm25_exact(corpora[s], q)
                    for s in range(shards)])
                return full, topk(full, K)

            def gid(x):
                s, d = x.split("-")
                return int(s) * n + int(d)

            check_bm25(smoke, "mesh match", match_r,
                       [merged(q) for q in match_q], floor, gid)
            check_bm25(smoke, "mesh bool+term", bool_r,
                       [merged(q, t) for q, t in zip(bool_q, bool_tags)],
                       floor, gid)
        finally:
            node.close()


# ------------------------------------------------------------------ main
def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--docs", type=int, default=2_000_000,
                    help="docs per shard")
    ap.add_argument("--queries", type=int, default=32,
                    help="queries of each kind")
    return ap.parse_args(argv)


def run(args) -> Smoke:
    """Every phase of the chosen path, on whatever backend JAX has;
    the platform itself is one of the checks."""
    import jax
    smoke = Smoke()
    devs = jax.devices()
    log(f"device: platform={devs[0].platform} "
        f"kind={devs[0].device_kind} count={len(devs)}")
    smoke.check(devs[0].platform == "tpu",
                f"platform is {devs[0].platform}, not tpu")
    (run_four_chips if args.chips == 4 else run_one_chip)(args, smoke)
    return smoke


def main(argv=None) -> int:
    args = parse_args(argv)
    # the serving engine's step log (registration, warm compiles) on
    # stderr, as `python -m elasticsearch_tpu` shows it
    logging.basicConfig(level=logging.WARNING, format="%(name)s: %(message)s")
    logging.getLogger("elasticsearch_tpu.fastpath").setLevel(logging.INFO)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    smoke = run(args)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed",
              file=sys.stderr)
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:                 # noqa: BLE001 — reported, exit 1
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the server's native threads are stopped by node.close(); _exit
    # skips interpreter teardown, which must not turn a verdict into an
    # abort
    os._exit(code)
