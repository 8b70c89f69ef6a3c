"""Benchmark v2: BM25 top-1000 through the REST serving path vs a C++
block-max MaxScore CPU baseline.

BASELINE.md headline config: `match` query BM25, top-1000, single shard,
single chip. Corpus is synthetic MS MARCO-passage-like (Zipf terms,
~40-term docs; real MS MARCO is unobtainable in a zero-egress image —
disclosed). 256 queries with 1-8 terms (term-count diversity).

What's measured (VERDICT round-1 items 1 & 4):
- **Headline**: QPS through the PRODUCT serving path — REST dispatch →
  SearchService → plan compiler → fused sorted-top-k kernel, with
  concurrent clients sharing launches via continuous batching
  (search/batching.py). Not a standalone kernel loop.
- **Baseline**: the C++ block-max MaxScore DAAT scorer
  (native/src/estpu_native.cpp) — a Lucene-class skipping scorer, NOT
  numpy scatter (r01's weakness #2).
- **Recall**: recall@1000 against an exact dense scorer over the FULL
  query set (r01 checked one query).
- p50/p99 disclosed for the serving path; raw-kernel and secondary
  configs (bool+filters, kNN, RRF) in the metric text.

Prints ONE JSON line; diagnostics to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

# float64 scoring rail for the serving kernels (ops/fastpath._score_dtype):
# at 2M docs the float32 representation is the recall floor — boundary
# docs whose f64 scores differ by <2^-24 relative collapse to equal f32
# (measured 0.9995 f32 vs 1.0 f64, ~2% per-launch cost; the C++ baseline
# accumulates in double too). Ranking runs in f64, reported scores stay
# f32. Must be set before the first jax import in the process; the full
# test suite passes under x64.
os.environ.setdefault("JAX_ENABLE_X64", "1")

BLOCK = 128
N_DOCS = int(os.environ.get("BENCH_DOCS", 2_000_000))
VOCAB = int(os.environ.get("BENCH_VOCAB", 100_000))
AVG_LEN = 40
N_QUERIES = int(os.environ.get("BENCH_QUERIES", 256))
K = 1000
K1, B = 1.2, 0.75
# 320 keep-alive connections: the slow-launch serving config is 8
# overlapped streams x 32-query cohorts = 256 queries in flight; fewer
# clients than that underfills cohorts (r04 averaged 18.8/32 at 192)
CLIENTS = int(os.environ.get("BENCH_CLIENTS", 320))


def log(*args):
    print(*args, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Incremental metric emission (VERDICT r4 item 1: a bench that dies
# mid-run must still have PARSED a headline). Every section refreshes
# the ONE JSON line; the driver takes the last parsed line on stdout,
# so a timeout kill after the REST section still records the serving
# number. A TERM/INT handler re-prints the latest payload and exits so
# even a kill during a blocking section flushes a parseable line.
# ---------------------------------------------------------------------------

_T_START = time.time()
_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 3300))
_LAST_PAYLOAD = {}


def remaining_budget() -> float:
    return _BUDGET_S - (time.time() - _T_START)


def emit(metric_text: str, value: float, vs_baseline: float,
         engine=None, overload=None, tasks=None, cpu=None,
         serving=None, skipped=None, aggs=None, multichip=None,
         lint=None, recovery=None, health=None, upgrade=None,
         cursors=None, tenants=None, snapshots=None, macro=None):
    _LAST_PAYLOAD.clear()
    _LAST_PAYLOAD.update({
        "metric": metric_text,
        "value": round(float(value), 2),
        "unit": "qps",
        "vs_baseline": round(float(vs_baseline), 2)
        if np.isfinite(vs_baseline) else 0.0,
    })
    if cpu:
        # CPU-side rows (corpus stats, truth/baseline timings) — banked
        # BEFORE the first device touch, so a run cut short still
        # records its host-side results
        _LAST_PAYLOAD["cpu"] = cpu
    if serving:
        # serving-path forensics: per-nb-bucket dispatch counts, warm-up
        # seconds (and seconds saved via the persistent compile cache),
        # cohort/batch histograms — attributes qps movement to each
        # serving lever (impact selection / cache / batching)
        _LAST_PAYLOAD["serving"] = serving
    if skipped:
        # sections that did not run this round, with reasons — an rc=124
        # or device outage leaves a parseable record per section
        _LAST_PAYLOAD["skipped"] = skipped
    if multichip:
        # multi-chip serving scaling rows (ISSUE 9): qps at 1/2/4/8
        # devices for sharded-corpus and replica-parallel modes, on CPU
        # virtual devices (behaviour and parity, not device speed)
        _LAST_PAYLOAD["multichip_serving"] = multichip
    if aggs:
        # aggregation-reduction rider (round-7): host vs device wall
        # time per agg family (metric moments / histogram scatter-add /
        # per-bucket sub-metric columns), sketch sizes and merge error,
        # and the incremental partial-reduce counts — host rows bank
        # CPU-side BEFORE any backend touch (PR-6 convention)
        _LAST_PAYLOAD["aggs"] = aggs
    if tasks:
        # task-management rider (transport/tasks.py): peak concurrent
        # registered tasks + cancellations observed on the serving node.
        # The standard workload must show cancelled == 0 — a nonzero
        # count here means something started killing healthy requests
        _LAST_PAYLOAD["tasks"] = tasks
    if engine:
        # engine observability rider (telemetry/engine.py): compile
        # table + HBM peak, so the perf trajectory records compile-time
        # regressions (a shape-discipline break shows as compile counts
        # growing round over round) alongside latency
        _LAST_PAYLOAD["engine"] = engine
    if overload:
        # backpressure rider: breaker trip counts + peak in-flight
        # indexing bytes on the serving node. The standard workload must
        # show tripped == 0 everywhere — a nonzero count here means a
        # limit regression started shedding healthy traffic
        _LAST_PAYLOAD["overload"] = overload
    if lint:
        # estpu-lint rider: rules_run / violations /
        # baselined over the whole package, banked before the first
        # device touch — the perf trajectory records contract drift
        # (a growing baseline or a live violation) next to the qps it
        # would eventually cost
        _LAST_PAYLOAD["lint"] = lint
    if recovery:
        # shard-relocation rider (cluster/data_node.py staged recovery
        # in the deterministic sim): virtual relocation wall-clock,
        # bytes moved, phase-2 ops replayed, HBM re-upload stage time,
        # and search availability during the move — a recovery-path
        # regression shows here round over round before it ever costs
        # a production drain
        _LAST_PAYLOAD["recovery"] = recovery
    if health:
        # health rider (health/ + telemetry/history.py, deterministic
        # sim): merged indicator statuses through a seeded breaker
        # squeeze (healthy -> red -> recovered), watchdog stall stats,
        # and the history ring's residency — the round records its
        # diagnostic surface's verdicts next to the qps they guard
        _LAST_PAYLOAD["health"] = health
    if upgrade:
        # rolling-upgrade rider (cluster/node.py shutdown plane in the
        # deterministic sim): per-node bounce wall-clock, delayed vs
        # reallocated shard counts, searches served through each
        # bounce, and the zero-acked-loss verdict — a regression in
        # graceful restart shows here before it costs a real upgrade
        _LAST_PAYLOAD["upgrade"] = upgrade
    if cursors:
        # cursor-plane rider (search/cursors.py in the deterministic
        # sim): scroll pages drained through a mid-stream node kill,
        # PIT lease transfers across a primary move, async backlog —
        # the exactly-once verdicts ride next to the qps they protect
        _LAST_PAYLOAD["cursors"] = cursors
    if tenants:
        # tenant-accounting rider (telemetry/tenants.py, deterministic
        # sim): per-tenant qps/p50/p99 + SLO burn for a mixed
        # interactive-vs-hog workload, the seeded rejection burst, and
        # the noisy_neighbor verdict that must name the hog — a
        # regression in attribution (hog unnamed, or the quiet tenant
        # charged) shows here round over round
        _LAST_PAYLOAD["tenants"] = tenants
    if snapshots:
        # snapshot/restore rider (repositories/blobstore.py + the
        # cluster snapshot plane, deterministic sim): virtual snapshot
        # wall-clock + bytes uploaded, the incremental second pass's
        # delta bytes (must stay near zero for an unchanged index),
        # restore-through-staged-recovery wall-clock, and searches
        # served while the snapshot ran — a repo-format or dedup
        # regression shows here before it costs a real backup window
        _LAST_PAYLOAD["snapshots"] = snapshots
    if macro:
        # macro-workload rider (bench/macro.py, deterministic sim): a
        # Rally-style open-loop mix — interactive/bulk/aggs/scroll/
        # async, tenant-tagged — through an injected reroute relocation
        # AND a node bounce; per-class qps/p50/p99 + SLO burn, the
        # workload_slo verdict mid-chaos, the disruption timeline, and
        # the zero-acked-write-loss verdict. A class-attribution or
        # survival regression shows here round over round
        _LAST_PAYLOAD["macro"] = macro
    print(json.dumps(_LAST_PAYLOAD), flush=True)


def _tasks_snapshot(node) -> dict:
    """Task-manager peaks of the serving node for the BENCH json
    `tasks` key."""
    try:
        s = node.task_manager.stats()
        return {"peak_concurrent": s["peak_concurrent"],
                "started": s["started"],
                "cancelled": s["cancelled"]}
    except Exception:   # noqa: BLE001 — stats must never kill the bench
        return {}


def _overload_snapshot(node) -> dict:
    """Breaker trips + indexing-pressure peaks of the serving node for
    the BENCH json `overload` key."""
    out = {}
    try:
        breakers = node.breaker_service.stats()
        out["breaker_tripped"] = {name: s["tripped"]
                                  for name, s in breakers.items()}
        out["breaker_tripped_total"] = sum(out["breaker_tripped"].values())
        ip = node.indexing_pressure.stats()["memory"]
        out["indexing_peak_all_in_bytes"] = \
            ip["total"]["peak_all_in_bytes"]
        out["indexing_rejections"] = (
            ip["total"]["coordinating_rejections"]
            + ip["total"]["primary_rejections"]
            + ip["total"]["replica_rejections"])
    except Exception:   # noqa: BLE001 — stats must never kill the bench
        pass
    return out


def _flight_snapshot(node) -> dict:
    """Flight-recorder rollup of the serving node for the BENCH json
    `serving.flight` key: cohort fill p50/p99, readbacks by call site,
    regime seconds/flips — all CPU-side counters banked as row
    metadata (r04/r05 hygiene: no device work, no extra readbacks).
    Also times the record path itself so the round documents that the
    always-on recorder stays inside its 5% overhead budget."""
    out = {}
    try:
        fl = node.telemetry.flight
        agg = fl.aggregates()
        out["fill_pct"] = fl.fill_percentiles()
        out["launches"] = agg["launches"]
        out["readbacks"] = agg["readbacks"]
        out["readback_by_site"] = agg["readback_by_site"]
        out["regime"] = {"current": agg["regime"]["current"],
                         "flips": agg["regime"]["flips"],
                         "seconds": agg["regime"]["seconds"]}
        out["ring"] = agg["ring"]
        # record-path micro-cost: a launch event is two dict builds +
        # a deque append; measure it on a scratch recorder (same class,
        # same capacity) so the live ring stays untouched and overhead
        # claims in COMPONENTS.md stay honest (ns/event, vs ~1e6 ns
        # launches — the <5% budget is satisfied by orders of magnitude)
        import timeit
        probe = type(fl)(capacity=agg["ring"]["capacity"])
        n = 2000
        t = timeit.timeit(
            lambda: probe.record_launch("bench.overhead_probe", (8, 128),
                                        dispatch_ns=1000, cohort=4,
                                        capacity=8), number=n)
        out["record_overhead_ns"] = round(t / n * 1e9)
    except Exception:   # noqa: BLE001 — stats must never kill the bench
        pass
    return out


def _engine_snapshot(parts: dict) -> dict:
    """Compile-tracker rollup + per-kernel compile table (+ the REST
    node's HBM peak once the serving section ran) for the BENCH json."""
    out = {}
    try:
        from elasticsearch_tpu.telemetry.engine import TRACKER
        out["compile"] = TRACKER.totals()
        out["kernels"] = {
            name: {"compiles": e["compiles"],
                   "shapes_seen": e["shapes_seen"],
                   "cum_ms": e["cum_ms"]}
            for name, e in TRACKER.to_dict().items()}
    except Exception:   # noqa: BLE001 — stats must never kill the bench
        pass
    if parts.get("hbm_peak_bytes"):
        out["hbm_peak_bytes"] = parts["hbm_peak_bytes"]
    return out


def _term_handler(signum, frame):
    log(f"bench: signal {signum} at t+{time.time()-_T_START:.0f}s — "
        f"flushing last metric")
    if _LAST_PAYLOAD:
        print(json.dumps(_LAST_PAYLOAD), flush=True)
    os._exit(1)


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def build_corpus(rng):
    from elasticsearch_tpu.bench.corpus import build_corpus as _build
    t0 = time.time()
    corpus = _build(rng, N_DOCS, VOCAB, AVG_LEN,
                    float(os.environ.get("BENCH_BURST", 0.35)))
    log(f"built {corpus['block_docids'].shape[0]} blocks "
        f"({corpus['n_postings']} postings) in {time.time() - t0:.1f}s")
    return corpus


def idf(df_t, n):
    return np.log(1.0 + (n - df_t + 0.5) / (df_t + 0.5))


def make_queries(rng, df):
    """N_QUERIES queries, 1-8 terms each, drawn across df bands (rare →
    common) — the term-count/selectivity diversity of a real query log;
    trimmed to BENCH_MAX_BLOCKS blocks (each pow2 bucket is one compile)."""
    from elasticsearch_tpu.bench.corpus import make_queries as _make
    return _make(rng, df, N_DOCS, N_QUERIES,
                 int(os.environ.get("BENCH_MAX_BLOCKS", 4096)))


# ---------------------------------------------------------------------------
# CPU: exact truth + C++ block-max MaxScore baseline
# ---------------------------------------------------------------------------

def cpu_exact_truth(corpus, queries):
    """Exact dense scoring (numpy float64) → per-query top-K id sets —
    the recall truth for BOTH the baseline and the TPU path."""
    lens = corpus["lens"]
    norm = K1 * (1.0 - B + B * lens / lens.mean())
    gs, d_all, tf_all, df = (corpus["group_start"], corpus["doc_ids"],
                             corpus["tf"], corpus["df"])
    t0 = time.time()
    truth = []
    for q in queries:
        scores = np.zeros(N_DOCS, np.float64)
        for t in q:
            lo, hi = int(gs[t]), int(gs[t + 1])
            d = d_all[lo:hi]
            f = tf_all[lo:hi]
            scores[d] += idf(df[t], N_DOCS) * f / (f + norm[d])
        top = np.argpartition(-scores, min(4 * K, N_DOCS - 1))[: 4 * K]
        top = top[scores[top] > 0]
        order = top[np.lexsort((top, -scores[top]))][:K]
        truth.append(set(order.tolist()))
    log(f"exact truth over {len(queries)} queries in {time.time()-t0:.1f}s")
    return truth


def run_cpu_maxscore(corpus, queries, truth, cpu_rows=None):
    from elasticsearch_tpu import native

    if not native.available():
        log("native library unavailable — no C++ baseline")
        return None, 0.0
    lens = corpus["lens"]
    norm = K1 * (1.0 - B + B * lens / lens.mean())
    bd, bt, tbs, nb, df = (corpus["block_docids"], corpus["block_tfs"],
                           corpus["tbs"], corpus["nb"], corpus["df"])
    t0 = time.time()
    # per-posting saturation tf/(tf+norm) in the block layout + block max
    sat = np.where(bt > 0, bt / (bt + norm[bd]), 0.0).astype(np.float32)
    block_max = sat.max(axis=1)
    sat_flat = sat.reshape(-1)
    docids_flat = bd.reshape(-1)
    log(f"sat/block-max precompute {time.time()-t0:.1f}s")
    if cpu_rows is not None:
        cpu_rows["sat_blockmax_precompute_s"] = round(time.time() - t0, 1)

    def args_for(q):
        post_off = np.asarray([int(tbs[t]) * BLOCK for t in q], np.int64)
        post_len = np.asarray([int(df[t]) for t in q], np.int64)
        blk_off = np.asarray([int(tbs[t]) for t in q], np.int64)
        blk_len = np.asarray([int(nb[t]) for t in q], np.int64)
        idfs = np.asarray([idf(df[t], N_DOCS) for t in q], np.float32)
        return post_off, post_len, blk_off, blk_len, idfs

    lat = []
    recalls = []
    for qi, q in enumerate(queries):
        a = args_for(q)
        best = float("inf")
        res = None
        for _ in range(2):
            t0 = time.time()
            res = native.maxscore_topk(docids_flat, sat_flat, block_max,
                                       *a, K)
            best = min(best, time.time() - t0)
        lat.append(best)
        _, docs = res
        tset = truth[qi]
        recalls.append(len(set(docs.tolist()) & tset) / max(1, len(tset)))
    qps = len(lat) / sum(lat)
    log(f"CPU block-max MaxScore: {qps:.1f} qps, "
        f"p50 {np.median(lat)*1000:.2f} ms, "
        f"recall {np.mean(recalls):.4f} (self-check vs exact)")
    return qps, float(np.mean(recalls))


# ---------------------------------------------------------------------------
# TPU raw kernel (timed before ANY device->host readback: an earlier
# runtime slowed every launch after the first readback, and the
# sustained probe below still measures that factor)
# ---------------------------------------------------------------------------

def pad_pow2(values, pad_value, floor=64):
    bucket = floor
    while bucket < len(values):
        bucket *= 2
    return values + [pad_value] * (bucket - len(values))


def select_blocks(q, corpus, zero_block, floor):
    tbs, nb, df = corpus["tbs"], corpus["nb"], corpus["df"]
    ids, ws = [], []
    for t in q:
        start, cnt = int(tbs[t]), int(nb[t])
        ids.extend(range(start, start + cnt))
        ws.extend([idf(df[t], N_DOCS)] * cnt)
    return (np.asarray(pad_pow2(ids, zero_block, floor), np.int32),
            np.asarray(pad_pow2(ws, 0.0, floor), np.float32))


def run_tpu_kernel(corpus, queries):
    import jax

    from elasticsearch_tpu.ops.bm25 import (bm25_sorted_topk,
                                            bm25_sorted_topk_batch)

    # persistent compile cache: serving shapes compile once per machine
    from elasticsearch_tpu.search.fastpath import enable_compile_cache
    enable_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev}")
    t0 = time.time()
    d_docids = jax.device_put(corpus["block_docids"], dev)
    d_tfs = jax.device_put(corpus["block_tfs"], dev)
    d_lens = jax.device_put(corpus["lens"], dev)
    d_live = jax.device_put(np.ones(N_DOCS, bool), dev)
    jax.block_until_ready((d_docids, d_tfs, d_lens, d_live))
    log(f"HBM upload {time.time() - t0:.1f}s")
    zero_block = corpus["block_docids"].shape[0] - 1
    avg = np.float32(corpus["lens"].mean())

    @jax.jit
    def score_topk(bdd, btt, lens_d, live_d, sel, ws):
        return bm25_sorted_topk(bdd, btt, sel, ws, lens_d, live_d,
                                avg, K1, B, K)

    FLOOR = int(os.environ.get("BENCH_NB_FLOOR", 2048))
    selections = [select_blocks(q, corpus, zero_block, FLOOR)
                  for q in queries]
    for sel, ws in selections[:40]:     # warm each bucket
        score_topk(d_docids, d_tfs, d_lens, d_live, sel, ws)[0].block_until_ready()
    lat = []
    for sel, ws in selections:
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            vals, ids = score_topk(d_docids, d_tfs, d_lens, d_live, sel, ws)
            vals.block_until_ready()
            best = min(best, time.time() - t0)
        lat.append(best)
    kernel_qps = len(lat) / sum(lat)
    log(f"raw kernel: {kernel_qps:.1f} qps (best-of-3), "
        f"p50 {np.median(lat)*1000:.2f} ms")


    # batch-32 launch shape (the continuous-batching ceiling)
    by_bucket = {}
    for s, w in selections:
        by_bucket.setdefault(len(s), []).append((s, w))

    @jax.jit
    def batch_topk(bdd, btt, lens_d, live_d, sels, wss):
        return bm25_sorted_topk_batch(bdd, btt, sels, wss, lens_d, live_d,
                                      avg, K1, B, K)

    BATCH = 32
    batches = []
    for plans in by_bucket.values():
        full = (plans * (BATCH // len(plans) + 1))[:BATCH]
        batches.append((np.stack([s for s, _ in full]),
                        np.stack([w for _, w in full])))
    for sel_b, ws_b in batches:
        batch_topk(d_docids, d_tfs, d_lens, d_live, sel_b,
                   ws_b)[0].block_until_ready()
    t0 = time.time()
    reps = 3
    for _ in range(reps):
        for sel_b, ws_b in batches:
            batch_topk(d_docids, d_tfs, d_lens, d_live, sel_b,
                       ws_b)[0].block_until_ready()
    batch_qps = BATCH * len(batches) * reps / (time.time() - t0)
    log(f"raw kernel batch-{BATCH}: {batch_qps:.1f} qps")
    def sustained_then_probe(n_launches=int(os.environ.get(
            "BENCH_SUSTAINED", 2000))):
        """(sustained_qps, checksum, degrade). Bounds the pre-readback
        capacity claim (VERDICT r3 item 10): n_launches batch launches
        whose outputs FOLD INTO AN ON-DEVICE ACCUMULATOR — the work
        can't be elided and is validated by a checksum read back ONCE
        at the end. The probe then re-times the identical launch after
        that readback to quantify any post-readback slowdown (an
        attached TPU: ~1)."""
        import jax
        import jax.numpy as jnp
        sel_b, ws_b = batches[0]
        acc = None
        t0 = time.time()
        done_launches = 0
        for i in range(n_launches):
            out = batch_topk(d_docids, d_tfs, d_lens, d_live, sel_b,
                             ws_b)[0]
            acc = out if acc is None else acc + out
            done_launches += 1
            # a device that executes these launches synchronously and
            # slowly would stall the bench on 2000 of them: sync after
            # the first 10, then every 100 under a wall guard.
            if done_launches == 10 or done_launches % 100 == 0:
                jax.block_until_ready(acc)
                if time.time() - t0 > 60:
                    log(f"sustained section wall-capped at "
                        f"{done_launches} launches")
                    break
        jax.block_until_ready(acc)
        n_launches = done_launches
        wall = time.time() - t0
        pre_per_launch = wall / n_launches
        sus_qps = n_launches * BATCH / wall
        checksum = float(np.asarray(jnp.sum(
            jnp.where(jnp.isfinite(acc), acc, 0.0))))  # THE readback
        log(f"sustained pre-readback: {n_launches} batch-{BATCH} "
            f"launches in {wall:.2f}s = {sus_qps:.0f} qps "
            f"({pre_per_launch*1000:.2f} ms/launch), on-device "
            f"checksum {checksum:.6g} read back once")
        best_post = float("inf")
        for _ in range(3):
            t0 = time.time()
            batch_topk(d_docids, d_tfs, d_lens, d_live, sel_b,
                       ws_b)[0].block_until_ready()
            best_post = min(best_post, time.time() - t0)
        degrade = best_post / max(pre_per_launch, 1e-9)
        log(f"post-readback launch slowdown: "
            f"{pre_per_launch*1000:.2f} ms -> {best_post*1000:.2f} ms "
            f"per identical launch (x{degrade:.0f})")
        return sus_qps, checksum, degrade

    return kernel_qps, batch_qps, dict(d_docids=d_docids, d_tfs=d_tfs,
                                       d_lens=d_lens, d_live=d_live,
                                       avg=avg, zero_block=zero_block,
                                       probe=sustained_then_probe)


def run_secondary(corpus, queries, rng, h):
    """bool+filters / kNN / RRF raw-kernel configs (BASELINE.md 2,4,5)."""
    import jax
    import jax.numpy as jnp

    from elasticsearch_tpu.ops.bm25 import bm25_sorted_topk
    from elasticsearch_tpu.ops.plan import match_count_sorted

    out = {}
    tbs, nb, df = corpus["tbs"], corpus["nb"], corpus["df"]
    N_FILTERS = 2
    avg = h["avg"]

    @jax.jit
    def bool_topk(bdd, btt, lens_d, live_d, sel, ws, fsel, fclause):
        cnt = match_count_sorted(bdd, btt, fsel, fclause, live_d)
        live = (cnt == N_FILTERS) & live_d
        return bm25_sorted_topk(bdd, btt, sel, ws, lens_d, live,
                                avg, K1, B, K)

    eligible = np.nonzero(df > N_DOCS // 20)[0]
    plans = []
    for q in queries[:16]:
        sel, ws = select_blocks(q, corpus, h["zero_block"], 2048)
        f_ids, f_cl = [], []
        for ci, t in enumerate(rng.choice(eligible, size=N_FILTERS,
                                          replace=False)):
            start, cnt = int(tbs[int(t)]), int(nb[int(t)])
            f_ids.extend(range(start, start + cnt))
            f_cl.extend([ci] * cnt)
        plans.append((sel, ws,
                      np.asarray(pad_pow2(f_ids, h["zero_block"], 2048),
                                 np.int32),
                      np.asarray(pad_pow2(f_cl, 0, 2048), np.int32)))
    for p in plans:
        bool_topk(h["d_docids"], h["d_tfs"], h["d_lens"], h["d_live"],
                  *p)[0].block_until_ready()
    t0 = time.time()
    for p in plans:
        bool_topk(h["d_docids"], h["d_tfs"], h["d_lens"], h["d_live"],
                  *p)[0].block_until_ready()
    out["bool+filters"] = len(plans) / (time.time() - t0)

    n_vec = int(os.environ.get("BENCH_VECS", 1_000_000))
    dim = int(os.environ.get("BENCH_DIMS", 256))
    vecs = rng.standard_normal((n_vec, dim), dtype=np.float32)
    vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
    d_vecs = jax.device_put(vecs.astype(np.dtype("bfloat16")),
                            jax.devices()[0])

    @jax.jit
    def knn_topk(vs, q):
        sims = (vs @ q.astype(vs.dtype)).astype(jnp.float32)
        return jax.lax.top_k(sims, K)

    qvecs = [vecs[rng.integers(n_vec)] + 0.1 * rng.standard_normal(dim)
             for _ in range(16)]
    qvecs = [(q / np.linalg.norm(q)).astype(np.float32) for q in qvecs]
    knn_topk(d_vecs, qvecs[0])[0].block_until_ready()
    t0 = time.time()
    for q in qvecs:
        knn_topk(d_vecs, q)[0].block_until_ready()
    out["knn"] = len(qvecs) / (time.time() - t0)
    out["knn_desc"] = f"{n_vec // 1_000_000}M×{dim}d"

    @jax.jit
    def hybrid_rrf(bdd, btt, lens_d, live_d, sel, ws, vs, qv):
        bvals, bids = bm25_sorted_topk(bdd, btt, sel, ws, lens_d, live_d,
                                       avg, K1, B, K)
        sims = (vs @ qv.astype(vs.dtype)).astype(jnp.float32)
        kvals, kids = jax.lax.top_k(sims, K)
        rr = jnp.zeros(lens_d.shape[0], jnp.float32)
        ranks = jnp.arange(K, dtype=jnp.float32)
        rr = rr.at[jnp.clip(bids, 0, lens_d.shape[0] - 1)].add(
            jnp.where(jnp.isfinite(bvals), 1.0 / (61.0 + ranks), 0.0),
            mode="drop")
        rr = rr.at[kids].add(1.0 / (61.0 + ranks), mode="drop")
        return jax.lax.top_k(rr, K)

    base = [select_blocks(q, corpus, h["zero_block"], 2048)
            for q in queries[:16]]
    hplans = [(s, w, qvecs[i % len(qvecs)]) for i, (s, w) in enumerate(base)]
    for sel, ws, qv in hplans:
        hybrid_rrf(h["d_docids"], h["d_tfs"], h["d_lens"], h["d_live"],
                   sel, ws, d_vecs, qv)[0].block_until_ready()
    t0 = time.time()
    for sel, ws, qv in hplans:
        hybrid_rrf(h["d_docids"], h["d_tfs"], h["d_lens"], h["d_live"],
                   sel, ws, d_vecs, qv)[0].block_until_ready()
    out["rrf_hybrid"] = len(hplans) / (time.time() - t0)
    for cfg in ("bool+filters", "knn", "rrf_hybrid"):
        log(f"secondary [{cfg}]: {out[cfg]:.1f} qps")
    del d_vecs
    return out


# ---------------------------------------------------------------------------
# REST serving path: node + real index (segment mounted from the corpus),
# concurrent clients through dispatch(), continuous batching
# ---------------------------------------------------------------------------

def build_rest_node(corpus, tmpdir):
    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.segment import PostingsField, Segment, StoredFields
    from elasticsearch_tpu.node import Node

    t0 = time.time()
    t_step = time.time()

    def step(name):
        nonlocal t_step
        log(f"  node-build step [{name}] {time.time()-t_step:.1f}s")
        t_step = time.time()
    bd, bt, lens = corpus["block_docids"], corpus["block_tfs"], corpus["lens"]
    # the segment's block arrays EXCLUDE the bench's extra zero row — the
    # device layer appends its own reserved block
    bd = bd[:-1]
    bt = bt[:-1]
    ln = lens[bd]
    ln[bt == 0] = np.inf
    block_min_len = np.where(np.isfinite(ln.min(axis=1)), ln.min(axis=1),
                             0.0).astype(np.float32)
    del ln
    pf = PostingsField(
        field="title",
        terms=[f"t{i:06d}" for i in range(VOCAB)],
        doc_freq=corpus["df"].astype(np.int32),
        total_term_freq=corpus["df"].astype(np.int64),  # approx; unused here
        term_block_start=corpus["tbs"][:-1].astype(np.int32),
        term_block_count=corpus["nb"].astype(np.int32),
        block_docids=bd, block_tfs=bt,
        block_max_tf=bt.max(axis=1).astype(np.float32),
        block_min_len=block_min_len,
        field_lengths=lens,
        sum_total_term_freq=int(lens.sum()),
        sum_doc_freq=int(corpus["df"].sum()),
        doc_count=N_DOCS)
    stored = StoredFields(offsets=np.zeros(N_DOCS + 1, np.int64), data=b"",
                          ids=[str(i) for i in range(N_DOCS)])
    # keyword + numeric doc values for the agg / script_score product
    # rows; optional dense vectors for the hybrid RRF row
    from elasticsearch_tpu.index.segment import (KeywordDocValues,
                                                 NumericDocValues,
                                                 VectorValues)
    rng2 = np.random.default_rng(99)
    n_cats = int(os.environ.get("BENCH_CATS", 500))
    cat_of = np.minimum((rng2.random(N_DOCS) ** 2 * n_cats),
                        n_cats - 1).astype(np.int32)     # skewed
    kv = KeywordDocValues(
        "cat", [f"c{i:03d}" for i in range(n_cats)], ords=cat_of,
        offsets=np.arange(N_DOCS + 1, dtype=np.int64),
        all_ords=cat_of)
    feat = rng2.random(N_DOCS).astype(np.float64)
    nv = NumericDocValues(
        "feat", values=feat, missing=np.zeros(N_DOCS, bool),
        offsets=np.arange(N_DOCS + 1, dtype=np.int64), all_values=feat)
    vectors = {}
    rrf_dims = int(os.environ.get("BENCH_RRF_DIMS", 256))
    if os.environ.get("BENCH_RRF", "1") != "0":
        vs = rng2.standard_normal((N_DOCS, rrf_dims)).astype(np.float32)
        vs /= np.linalg.norm(vs, axis=1, keepdims=True)
        vectors["vec"] = VectorValues("vec", vs,
                                      np.ones(N_DOCS, bool), rrf_dims,
                                      "cosine")
    seg = Segment("bench0", N_DOCS, postings={"title": pf},
                  numerics={"feat": nv}, keywords={"cat": kv},
                  vectors=vectors, stored=stored)
    step("segment assembly")

    node = Node(settings=Settings.from_dict({
        "http": {"native": {
            "fast_nb_buckets": os.environ.get("BENCH_FAST_BUCKETS",
                                              "1024,2048,4096"),
            "fast_streams": int(os.environ.get("BENCH_FAST_STREAMS", 6)),
            "fast_q_batch": int(os.environ.get("BENCH_FAST_QBATCH", 32)),
            "fast_max_k": K}},
    }), data_path=os.path.join(tmpdir, "node"))
    step("Node construction")
    status, _ = node.rest_controller.dispatch(
        "PUT", "/bench", None,
        {"mappings": {"properties": {"title": {"type": "text"}}}})
    assert status == 200
    eng = node.indices_service.get("bench").shards[0]
    with eng._lock:
        eng._segments = [seg]
        eng._epoch += 1
    step("index create + segment inject")
    port = node.start(0)
    step("node.start")
    log(f"REST node ready in {time.time()-t0:.1f}s (port {port})")
    # the fast path registers once its kernel shapes are compiled — this
    # is the refresh/startup precompile (VERDICT r2 item 2: the 69.7s
    # first-query stall is paid HERE, not by the first request)
    t0 = time.time()
    fp = getattr(node._http, "fastpath", None)
    if fp is not None:
        deadline = time.time() + 1200
        while fp._reg is None and time.time() < deadline:
            time.sleep(1.0)
        log(f"fastpath registered in {time.time()-t0:.1f}s "
            f"(warm compiles included)")
    else:
        log("WARNING: native front unavailable — serving via fallback")
    return node, port


def _loadgen(port, bodies_json, n_conns, total, timeout_ms=600_000,
             path=b"/bench/_search"):
    """Drive the node over REAL loopback HTTP with the C++ epoll client
    (native/src/estpu_http.cpp es_loadgen). On a 1-core host a Python
    client pool competes with the server for the GIL and measures
    itself; the C++ client costs ~µs/request."""
    import ctypes

    from elasticsearch_tpu.rest import native_http

    lib = native_http.get_lib()
    blobs = [json.dumps(b).encode() for b in bodies_json]
    blob = b"".join(blobs)
    offs = np.zeros(len(blobs) + 1, np.int64)
    np.cumsum([len(b) for b in blobs], out=offs[1:])
    lat = np.zeros(total, np.float64)
    wall = ctypes.c_double()
    done = lib.es_loadgen(
        port, path, blob,
        offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(blobs), n_conns, total, timeout_ms,
        lat.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.byref(wall))
    lat_ms = lat[:done] / 1000.0
    qps = done / wall.value if wall.value > 0 else 0.0
    return done, qps, lat_ms


def run_rest_path(corpus, queries, truth, tmpdir, emit_cb=None):
    import urllib.request

    import elasticsearch_tpu.search.batching as batching_mod
    import elasticsearch_tpu.search.plan as plan_mod

    # fallback-path knobs (anything the C++ fast parser rejects still
    # runs through the Python plan path)
    plan_mod.MIN_PLAN_BUCKET = int(os.environ.get("BENCH_REST_FLOOR", 1024))
    batching_mod._Q_BUCKETS = (1, 32)

    # surface the serving engine's own step logs (warm-compile
    # timings) in the bench stderr — the driver-run record
    import logging as _logging
    h = _logging.StreamHandler(sys.stderr)
    h.setFormatter(_logging.Formatter("  fastpath: %(message)s"))
    fplog = _logging.getLogger("elasticsearch_tpu.fastpath")
    fplog.addHandler(h)
    fplog.setLevel(_logging.INFO)
    node, port = build_rest_node(corpus, tmpdir)
    base = f"http://127.0.0.1:{port}"
    bodies = []
    for q in queries:
        text = " ".join(f"t{t:06d}" for t in q)
        bodies.append({"query": {"match": {"title": text}},
                       "size": K, "_source": False})

    def http_post(body, tries: int = 3):
        last = None
        for attempt in range(tries):
            r = urllib.request.Request(
                base + "/bench/_search",
                data=json.dumps(body).encode(), method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(r, timeout=300) as resp:
                    return json.loads(resp.read())
            except OSError as e:
                # one lost request must not kill the whole bench
                last = e
                log(f"http_post retry {attempt + 1}: {e!r}")
        raise last

    # ---- first-query latency post-registration (the cold-start number:
    # kernel shapes compiled at registration, so this must be fast)
    t0 = time.time()
    http_post(bodies[0])
    log(f"first REST query (post-registration) {time.time()-t0:.2f}s")

    # ---- recall over the FULL query set through real HTTP.
    # CONCURRENT posts (32 workers): a serial pass rides cohorts of
    # ONE; concurrency lets the continuous batcher
    # fill cohorts, which is the serving path's real shape anyway.
    from concurrent.futures import ThreadPoolExecutor

    def recall_pass(label):
        t0 = time.time()
        def one(args):
            qi, body = args
            try:
                resp = http_post(body)
            except OSError:
                return None        # lost request; disclosed below
            ids = {int(h["_id"]) for h in resp["hits"]["hits"]}
            tset = truth[qi]
            return len(ids & tset) / max(1, len(tset))
        with ThreadPoolExecutor(max_workers=32) as ex:
            recalls = [x for x in ex.map(one, enumerate(bodies))]
        lost = sum(1 for x in recalls if x is None)
        kept = [x for x in recalls if x is not None]
        r = float(np.mean(kept)) if kept else 0.0
        log(f"REST recall@{K} {label} over {len(kept)}/{len(bodies)} "
            f"queries: {r:.4f} ({time.time()-t0:.1f}s"
            + (f"; {lost} lost" if lost else "") + ")")
        return r

    def _serving_snapshot():
        """The BENCH json `serving` section: per-nb-bucket dispatch
        counts, warm-up seconds (+ persistent-compile-cache savings),
        cohort/batch histograms — attributes qps movement to the
        serving levers (impact selection / compile cache / batching)."""
        out = {}
        try:
            fpx = getattr(node._http, "fastpath", None)
            if fpx is not None:
                out.update(fpx.serving_stats())
            out["plan_batcher"] = node.search_service.plan_batcher.stats()
            from elasticsearch_tpu.telemetry.engine import TRACKER
            out["persistent_cache"] = TRACKER.persistent_stats()
            out["flight"] = _flight_snapshot(node)
        except Exception as e:   # noqa: BLE001 — stats never kill a run
            log(f"serving snapshot failed: {e!r}")
        return out

    rest_recall = recall_pass("cold")
    # the cold pass warmed the θ cache — measure the θ-warm essential
    # lane's recall too (the certificate guarantees exactness relative
    # to the same float32 scoring; refires fall back to the full kernel)
    warm_recall = recall_pass("θ-warm")
    fp0 = getattr(node._http, "fastpath", None)
    ess_stats = dict(fp0.stats) if fp0 is not None else {}
    log(f"θ-warm lane stats: ess_queries "
        f"{ess_stats.get('ess_queries', 0)}, refires "
        f"{ess_stats.get('ess_refires', 0)}")

    # ---- throughput: C++ loadgen, CLIENTS keep-alive connections.
    # Snapshot the fast-path stats AROUND the measured phase only — the
    # sequential recall pass runs cohort-1 launches and would dilute the
    # continuous-batching average
    reps = int(os.environ.get("BENCH_REST_REPS", 12))
    _loadgen(port, bodies, CLIENTS, len(bodies) * 2)   # warm caches
    fp = getattr(node._http, "fastpath", None)
    stats0 = node._http.stats() if hasattr(node._http, "stats") else {}
    fstats0 = dict(fp.stats) if fp is not None else {}
    done, best_qps, lat_ms = _loadgen(port, bodies, CLIENTS,
                                      len(bodies) * reps)
    p50 = float(np.median(lat_ms)) if len(lat_ms) else 0.0
    p99 = float(np.percentile(lat_ms, 99)) if len(lat_ms) else 0.0
    stats1 = node._http.stats() if hasattr(node._http, "stats") else {}
    fstats1 = dict(fp.stats) if fp is not None else {}
    fast_served = stats1.get("fast", 0) - stats0.get("fast", 0)
    avg_batch = ((fstats1.get("fast_queries", 0)
                  - fstats0.get("fast_queries", 0))
                 / max(1, (fstats1.get("cohorts", 0)
                           - fstats0.get("cohorts", 0))))
    log(f"REST serving: {best_qps:.1f} qps over HTTP with {CLIENTS} "
        f"connections ({done} reqs, p50 {p50:.2f} ms, p99 {p99:.2f} ms, "
        f"fast-served {fast_served}, avg cohort {avg_batch:.1f})")
    if fp is not None:
        # lane routing forensics for the round analysis: how much of
        # the serving phase rode the theta-warm essential lane
        delta = {k: fstats1.get(k, 0) - fstats0.get(k, 0)
                 for k in ("fast_queries", "ess_queries", "ess_refires",
                           "v2_queries", "cohorts")}
        log(f"serving-phase lanes: {delta}")
    if emit_cb is not None:
        # the HEADLINE is measured — freshen the metric line NOW so any
        # later kill still leaves the serving number parsed
        emit_cb(rest_qps=best_qps, p50=p50, p99=p99,
                rest_recall=rest_recall, warm_recall=warm_recall,
                avg_batch=avg_batch, serving=_serving_snapshot())

    # ---- bool+filters over HTTP (filters from a small hot pool — the
    # cached-filter-mask + cohort-sharing path)
    bool_qps = 0.0
    try:
        frng = np.random.default_rng(777)
        eligible = np.nonzero(corpus["df"] > N_DOCS // 20)[0]
        pool = frng.choice(eligible, size=min(8, len(eligible)),
                           replace=False)
        fbodies = []
        for q in queries[:64]:
            f1, f2 = frng.choice(pool, size=2, replace=False)
            fbodies.append({
                "query": {"bool": {
                    "must": [{"match": {"title": " ".join(
                        f"t{t:06d}" for t in q)}}],
                    "filter": [{"match": {"title": f"t{int(f1):06d}"}},
                               {"match": {"title": f"t{int(f2):06d}"}}]}},
                "size": K, "_source": False})
        _loadgen(port, fbodies, CLIENTS, len(fbodies))   # warm masks
        done_b, bool_qps, lat_b = _loadgen(port, fbodies, CLIENTS,
                                           len(fbodies) * 8)
        log(f"REST bool+filters over HTTP: {bool_qps:.1f} qps "
            f"({done_b} reqs, p50 {np.median(lat_b):.2f} ms)")
    except Exception as e:
        log(f"REST bool+filters failed: {e!r}")
    if emit_cb is not None:
        emit_cb(rest_bool_qps=bool_qps)

    # ---- product rows for the remaining BASELINE configs + aggs:
    # these bodies are NOT C++-fast-parseable, so they measure the full
    # Python serving path (REST dispatch → query DSL → device kernels).
    # Budget-gated: the headline is already emitted, these only enrich
    # the metric text.
    extra = {}
    if os.environ.get("BENCH_PRODUCT_ROWS", "1") == "0" \
            or remaining_budget() < 180:
        if remaining_budget() < 180:
            log(f"skipping product rows (budget: "
                f"{remaining_budget():.0f}s left)")
        if emit_cb is not None:
            emit_cb(hbm_peak_bytes=node.indices_service.device_cache
                    .hbm_stats().get("peak_bytes", 0),
                    overload=_overload_snapshot(node),
                    tasks=_tasks_snapshot(node),
                    serving=_serving_snapshot())
        node.close()
        return (best_qps, p50, p99, rest_recall, warm_recall, avg_batch,
                bool_qps, extra)

    def _row(name, bodies, conns, reps, check=None):
        try:
            # validate ONE response before measuring — a row that 400s
            # would otherwise 'benchmark' error responses
            probe = http_post(bodies[0])
            if "error" in probe:
                raise RuntimeError(f"probe error: {probe['error']}")
            if check is not None:
                check(probe)
            _loadgen(port, bodies, conns, len(bodies))          # warm
            done_x, qps_x, lat_x = _loadgen(port, bodies, conns,
                                            len(bodies) * reps)
            p50x = float(np.median(lat_x)) if len(lat_x) else 0.0
            log(f"REST {name}: {qps_x:.1f} qps ({done_x} reqs, "
                f"p50 {p50x:.2f} ms)")
            extra[name] = qps_x
        except Exception as e:
            log(f"REST {name} failed: {e!r}")
            extra[name] = 0.0
        if emit_cb is not None:
            emit_cb(extra=dict(extra))

    def qtext(q):
        return " ".join(f"t{t:06d}" for t in q)

    # terms aggregation at corpus scale (device ord-major collector)
    _row("match+terms-agg", [
        {"query": {"match": {"title": qtext(q)}}, "size": 0,
         "aggs": {"cats": {"terms": {"field": "cat"}}}}
        for q in queries[:32]], min(CLIENTS, 64), 4,
        check=lambda r: (r["aggregations"]["cats"]["buckets"][0]
                         ["doc_count"] > 0))
    # BASELINE config 3: script_score re-rank (vectorized expression)
    _row("script_score", [
        {"query": {"script_score": {
            "query": {"match": {"title": qtext(q)}},
            "script": {"source":
                       "doc['feat'].value * 0.5 + _score"}}},
         "size": K, "_source": False}
        for q in queries[:32]], min(CLIENTS, 64), 4)
    # BASELINE config 5: hybrid BM25 + kNN with RRF fusion
    if os.environ.get("BENCH_RRF", "1") != "0":
        dims = int(os.environ.get("BENCH_RRF_DIMS", 256))
        vrng = np.random.default_rng(7)
        rbodies = []
        for q in queries[:32]:
            qv = vrng.standard_normal(dims)
            qv /= np.linalg.norm(qv)
            rbodies.append({
                "query": {"match": {"title": qtext(q)}},
                "knn": {"field": "vec",
                        "query_vector": [round(float(x), 4)
                                         for x in qv],
                        "k": K, "num_candidates": int(1.5 * K)},
                "rank": {"rrf": {}}, "size": K, "_source": False})
        _row("rrf_hybrid", rbodies, min(CLIENTS, 64), 4,
             check=lambda r: len(r["hits"]["hits"]) > 0)

    if emit_cb is not None:
        # HBM peak of the serving node's device cache + backpressure
        # snapshot, recorded into the BENCH json before the node goes
        # away (overload.breaker_tripped must stay all-zero on the
        # standard workload)
        emit_cb(hbm_peak_bytes=node.indices_service.device_cache
                .hbm_stats().get("peak_bytes", 0),
                overload=_overload_snapshot(node),
                tasks=_tasks_snapshot(node),
                serving=_serving_snapshot())
    node.close()
    return (best_qps, p50, p99, rest_recall, warm_recall, avg_batch,
            bool_qps, extra)


# ---------------------------------------------------------------------------
# BASELINE config 4 at spec scale: dense kNN 8M×768 through the product
# path. 8M×768×f32 ≈ 23 GiB exceeds single-chip HBM (16 GiB), so the
# DEVICE slab is bfloat16 (11.5 GiB) and only NOMINATES candidates; the
# top num_candidates are re-ranked exactly in float32 from the host copy
# (search/queries.py KnnQuery._exact_rerank), making the final ranking
# f32-exact up to candidate coverage — measured below as recall vs a
# full f32 oracle. CPU analogue: numpy f32 brute force (the reference
# implements this config as script-scored brute force too —
# x-pack/plugin/vectors/.../query/ScoreScriptUtils.java:112-170).
# ---------------------------------------------------------------------------

def run_knn_at_scale():
    import tempfile
    import urllib.request

    from elasticsearch_tpu.common.settings import Settings
    from elasticsearch_tpu.index.segment import (Segment, StoredFields,
                                                 VectorValues)
    from elasticsearch_tpu.node import Node

    n = int(os.environ.get("BENCH_KNN_DOCS",
                           8_000_000 if N_DOCS >= 2_000_000 else N_DOCS))
    dims = int(os.environ.get("BENCH_KNN_DIMS", 768))
    nq = 16
    t0 = time.time()
    rng = np.random.default_rng(4242)
    vs = np.empty((n, dims), np.float32)
    step = 500_000
    for i in range(0, n, step):
        j = min(n, i + step)
        chunk = rng.standard_normal((j - i, dims)).astype(np.float32)
        chunk /= np.linalg.norm(chunk, axis=1, keepdims=True)
        vs[i:j] = chunk
    qvs = []
    for _ in range(nq):
        q = vs[rng.integers(n)] + 0.25 * rng.standard_normal(
            dims).astype(np.float32)
        qvs.append((q / np.linalg.norm(q)).astype(np.float32))
    log(f"kNN slab {n}x{dims} f32 built in {time.time()-t0:.1f}s "
        f"({vs.nbytes/2**30:.1f} GiB host)")

    # CPU analogue + f32 oracle (same pass): exact top-K per query
    t0 = time.time()
    lat = []
    oracle = []
    for q in qvs:
        tq = time.time()
        sims = vs @ q
        top = np.argpartition(-sims, K - 1)[:K]
        lat.append(time.time() - tq)
        oracle.append(set(top.tolist()))
    cpu_qps = len(lat) / sum(lat)
    log(f"kNN CPU f32 brute force: {cpu_qps:.2f} qps "
        f"(p50 {np.median(lat)*1000:.0f} ms)")

    with tempfile.TemporaryDirectory() as td:
        node = Node(settings=Settings.EMPTY, data_path=td + "/n")
        try:
            st, _ = node.rest_controller.dispatch(
                "PUT", "/knnbench", None, {"mappings": {"properties": {
                    "vec": {"type": "dense_vector", "dims": dims}}}})
            assert st == 200
            seg = Segment(
                "knn0", n, postings={}, numerics={}, keywords={},
                vectors={"vec": VectorValues("vec", vs,
                                             np.ones(n, bool), dims,
                                             "cosine")},
                stored=StoredFields(
                    offsets=np.zeros(n + 1, np.int64), data=b"",
                    ids=[str(i) for i in range(n)]))
            eng = node.indices_service.get("knnbench").shards[0]
            with eng._lock:
                eng._segments = [seg]
                eng._epoch += 1
            port = node.start(0)
            bodies = [{"knn": {"field": "vec",
                               "query_vector": [float(x) for x in q],
                               "k": K,
                               "num_candidates": int(os.environ.get(
                                   "BENCH_KNN_CANDIDATES", 3 * K))},
                       "size": K, "_source": False}
                      for q in qvs]
            base = f"http://127.0.0.1:{port}"

            def post(body):
                r = urllib.request.Request(
                    base + "/knnbench/_search",
                    data=json.dumps(body).encode(), method="POST",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(r, timeout=1800) as resp:
                    return json.loads(resp.read())
            t0 = time.time()
            # device upload + compile ride the first query; the 11.5
            # GiB slab upload can outlive one HTTP timeout — the retry
            # hits the server-side caches and completes
            try:
                post(bodies[0])
            except OSError:
                log("kNN first query timed out once; retrying against "
                    "the warmed caches")
                post(bodies[0])
            log(f"kNN first query (upload+compile) {time.time()-t0:.1f}s")
            recalls = []
            for qi, body in enumerate(bodies):
                ids = {int(h["_id"])
                       for h in post(body)["hits"]["hits"]}
                recalls.append(len(ids & oracle[qi]) / K)
            knn_recall = float(np.mean(recalls))
            done_k, knn_qps, lat_k = _loadgen(
                port, bodies, int(os.environ.get("BENCH_KNN_CONNS", 8)),
                len(bodies) * 4, timeout_ms=1_200_000,
                path=b"/knnbench/_search")
            p50k = float(np.median(lat_k)) if len(lat_k) else 0.0
            log(f"kNN product path: {knn_qps:.1f} qps ({done_k} reqs, "
                f"p50 {p50k:.0f} ms), recall@{K} {knn_recall:.4f} vs "
                f"f32 oracle")
            return (f"; dense kNN {n//1_000_000}M×{dims}d THROUGH REST "
                    f"(bf16 device slab + exact f32 re-rank of top-"
                    f"{os.environ.get('BENCH_KNN_CANDIDATES', 3*K)}): "
                    f"{knn_qps:.1f} qps, recall {knn_recall:.4f} vs f32 "
                    f"oracle, vs CPU f32 brute force {cpu_qps:.2f} qps "
                    f"({knn_qps/cpu_qps:.0f}x)")
        finally:
            node.close()


def compose_metric(p):
    """The ONE metric text, assembled from whatever sections have run
    (missing sections say so instead of silently vanishing)."""
    if p.get("cpu_qps"):
        base_txt = (f"baseline = C++ block-max MaxScore DAAT, SINGLE "
                    f"core ({p['cpu_qps']:.0f} qps, self-recall "
                    f"{p.get('cpu_recall', 0):.4f}; vs_baseline is "
                    f"chip-vs-one-core)")
    else:
        base_txt = "baseline unavailable (native library did not build)"
    extra = p.get("extra", {})
    rows_txt = (f"; PRODUCT rows: match+terms-agg "
                f"{extra.get('match+terms-agg', 0):.0f} qps, script_score "
                f"re-rank {extra.get('script_score', 0):.0f} qps, "
                f"hybrid RRF (match+knn, rank.rrf) "
                f"{extra.get('rrf_hybrid', 0):.0f} qps"
                if extra else "; product rows pending")
    if p.get("rest_qps") is None:
        head = (f"PROVISIONAL (REST serving section pending — run cut "
                f"early): raw fused-batch kernel "
                f"{p.get('kernel_qps', 0):.0f} qps single / "
                f"{p.get('batch_qps', 0):.0f} qps batch-32, "
                f"{N_DOCS // 1_000_000}M-doc corpus, single chip; ")
    else:
        head = (
            f"BM25 top-{K} QPS through the REST product path — REAL "
            f"loopback HTTP against the native C++ front (epoll server, "
            f"C++ body parse + response serialization, exact fused-batch "
            f"kernel), {CLIENTS} keep-alive "
            f"connections driven by a C++ epoll loadgen, continuous "
            f"batching avg {p.get('avg_batch', 0):.0f}/launch, "
            f"{N_QUERIES} queries 1-8 terms, synthetic "
            f"{N_DOCS // 1_000_000}M-doc corpus, single chip; p50 "
            f"{p.get('p50', 0):.1f} ms, p99 {p.get('p99', 0):.1f} ms; "
            f"the identical launch measured "
            f"x{p.get('degrade', 0):.0f} slower after the first "
            f"device→host transfer (raw-kernel numbers below ran "
            f"pre-readback); recall@{K} "
            f"{p.get('rest_recall', 0):.4f} vs a float64 exact oracle "
            f"over ALL queries (θ-warm essential lane "
            f"{p.get('warm_recall', 0):.4f}); any sub-1.0 residue is "
            f"float32 score REPRESENTATION — boundary docs whose "
            f"float64 scores differ by <2^-24 relative collapse to "
            f"equal float32; Lucene also scores in float32 and would "
            f"measure the same against this oracle, while the C++ "
            f"baseline accumulates in double; ")
    return (
        head + base_txt +
        (f"; REST bool+filters w/ cached filter masks "
         f"{p['rest_bool_qps']:.0f} qps" if p.get("rest_bool_qps")
         is not None else "; bool section pending") +
        rows_txt + p.get("knn_txt", "; 8M kNN section pending") +
        (f"; sustained pre-readback capacity {p['sus_qps']:.0f} qps "
         f"over {os.environ.get('BENCH_SUSTAINED', 2000)} checksummed "
         f"batch launches (single final readback)"
         if p.get("sus_qps") else "") +
        (f"; raw kernel {p['kernel_qps']:.0f} qps single / "
         f"{p['batch_qps']:.0f} qps batch-32"
         if p.get("kernel_qps") else "") +
        p.get("sec_txt", ""))


# ---------------------------------------------------------------------------
# aggregation reduction bench (round-7): host vs device wall time per
# agg family + sketch/partial-reduce accounting. The HOST half runs
# pure numpy (no jax import) so it banks before any backend touch; the
# DEVICE half runs after the raw-kernel section.
# ---------------------------------------------------------------------------

AGGS_N = int(os.environ.get("BENCH_AGGS_DOCS", 2_000_000))
AGGS_NB = 64            # histogram bucket count (one ladder rung)
AGGS_REPS = 5


def _aggs_columns(rng):
    vals = rng.uniform(1.0, 1000.0, AGGS_N)
    missing = rng.random(AGGS_N) < 0.1
    mask = rng.random(AGGS_N) < 0.3
    interval = 1000.0 / AGGS_NB
    steps = np.floor(vals / interval).astype(np.int64)
    return vals, missing, mask, steps


def run_aggs_cpu(rng):
    """Host reduction rows + sketch/partial-reduce accounting — all
    numpy, banked before the first device touch."""
    from elasticsearch_tpu.search.agg_partials import AggReduceConsumer
    from elasticsearch_tpu.search.sketches import TDigest
    vals, missing, mask, steps = _aggs_columns(rng)
    sel = mask & ~missing
    out = {"docs": AGGS_N, "buckets": AGGS_NB}

    t0 = time.time()
    for _ in range(AGGS_REPS):
        v = vals[sel]
        _ = (len(v), v.sum(), v.min(), v.max(), (v ** 2).sum())
    out["host_metric_stats_ms"] = round(
        (time.time() - t0) / AGGS_REPS * 1000, 2)

    t0 = time.time()
    for _ in range(AGGS_REPS):
        np.unique(steps[sel], return_counts=True)
    out["host_histogram_counts_ms"] = round(
        (time.time() - t0) / AGGS_REPS * 1000, 2)

    # the per-bucket sub-metric chain the device columns replace: one
    # masked numpy pass per bucket
    t0 = time.time()
    for b in range(AGGS_NB):
        in_b = sel & (steps == b)
        v = vals[in_b]
        if len(v):
            _ = (len(v), v.sum(), v.min(), v.max(), (v ** 2).sum())
    out["host_bucket_metrics_ms"] = round((time.time() - t0) * 1000, 2)

    # sketch: build, split-merge, q-space error, size
    t0 = time.time()
    digest = TDigest.from_values(vals[sel])
    out["sketch_build_ms"] = round((time.time() - t0) * 1000, 2)
    out["sketch_centroids"] = int(digest.means.size)
    out["sketch_bytes"] = digest.nbytes()
    shards = np.array_split(vals[sel], 8)
    t0 = time.time()
    merged = TDigest.merge_all([TDigest.from_values(s) for s in shards])
    out["sketch_shard_merge_ms"] = round((time.time() - t0) * 1000, 2)
    v = vals[sel]
    out["sketch_q50_qerr_pct"] = round(abs(
        float((v <= merged.quantile(50)).mean()) * 100 - 50), 4)
    out["sketch_q99_qerr_pct"] = round(abs(
        float((v <= merged.quantile(99)).mean()) * 100 - 99), 4)

    # incremental partial reduce: 8 shard partials through the consumer
    spec = {"p": {"percentiles": {"field": "x"}},
            "s": {"stats": {"field": "x"}}}
    partials = []
    for s in shards:
        partials.append({
            "p": {"d": TDigest.from_values(s).to_wire()},
            "s": {"n": len(s), "s": float(s.sum()), "mn": float(s.min()),
                  "mx": float(s.max()), "ss": float((s ** 2).sum())}})
    from elasticsearch_tpu.utils.breaker import payload_size_bytes
    out["partial_bytes_each"] = payload_size_bytes(partials[0])
    cons = AggReduceConsumer(spec, batch_size=3)
    t0 = time.time()
    for p in partials:
        cons.consume(p)
    _acc, phases = cons.finish()
    out["partial_reduce_ms"] = round((time.time() - t0) * 1000, 2)
    out["partial_reduce_partials"] = cons.partials_consumed
    out["partial_reduce_phases"] = phases
    return out


def run_profile_cpu(corpus, queries, n=32):
    """Per-phase latency percentiles (p50/p95/p99) + ONE sampled
    ES-shaped profile tree from the host-side scoring path, exercising
    the real PR-8 machinery (search/profile.py spans +
    shard_profile_tree — stdlib-only, no jax import) — banked into the
    BENCH json `serving` section CPU-side, BEFORE any backend touch."""
    from elasticsearch_tpu.search import profile as prof
    lens = corpus["lens"]
    norm = K1 * (1.0 - B + B * lens / lens.mean())
    gs, d_all, tf_all, df = (corpus["group_start"], corpus["doc_ids"],
                             corpus["tf"], corpus["df"])
    phases = {"rewrite": [], "score": [], "topk": [], "merge": []}
    sample_rec, sample_total = {}, 0
    body = {"query": {"match": {"title": "<bench query>"}}, "size": K}
    for q in queries[:n]:
        with prof.profiling() as rec:
            t0 = time.monotonic_ns()
            with prof.span("rewrite"):
                terms = [(int(gs[t]), int(gs[t + 1]),
                          idf(df[t], N_DOCS)) for t in q]
            with prof.span("score"):
                scores = np.zeros(N_DOCS, np.float32)
                for (lo, hi, w), t in zip(terms, q):
                    d = d_all[lo:hi]
                    f = tf_all[lo:hi]
                    scores[d] += w * f / (f + norm[d])
            with prof.span("topk"):
                top = np.argpartition(-scores,
                                      min(K, N_DOCS - 1))[:K]
            with prof.span("merge"):
                top[np.lexsort((top, -scores[top]))]
            total = time.monotonic_ns() - t0
        for name in phases:
            phases[name].append(rec.get(name, 0) / 1e6)
        sample_rec, sample_total = dict(rec), total
    pct = {
        name: {"p50": round(float(np.percentile(v, 50)), 3),
               "p95": round(float(np.percentile(v, 95)), 3),
               "p99": round(float(np.percentile(v, 99)), 3)}
        for name, v in phases.items() if v}
    return {
        "profile_phase_percentiles_ms": pct,
        "profile_sample": prof.shard_profile_tree(
            "[bench][0]", body, sample_rec, sample_total),
    }


def run_recovery_cpu(n_docs=400, seed=7):
    """Shard-relocation rider (CPU-side, deterministic sim — no jax):
    a 3-node sim cluster indexes ``n_docs``, then relocates its primary
    via `_cluster/reroute` while probe searches keep running. Reports
    the relocation's VIRTUAL wall-clock (sim seconds are deterministic,
    so the number is replay-stable round over round), bytes moved, ops
    replayed in phase 2, the HBM re-upload stage time, and how many
    searches ran (and failed) during the move — banked into the BENCH
    json `recovery` section BEFORE any backend touch."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.cluster.state import SHARD_STARTED
    from elasticsearch_tpu.testing.deterministic import (
        DeterministicTaskQueue, DisruptableTransport, SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"bn-{i}", name=f"bn{i}")
                 for i in range(3)]
        cluster = {}
        for node in nodes:
            cluster[node.node_id] = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
        for cn in cluster.values():
            cn.start()

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        queue.run_for(60)
        master = next(cn for cn in cluster.values() if cn.is_master())
        call(master.create_index, "bench", number_of_shards=1,
             number_of_replicas=0)
        queue.run_for(30)
        call(master.bulk, "bench", [
            {"op": "index", "id": f"d{i}",
             "source": {"body": f"bench doc {i} term{i % 37}"}}
            for i in range(n_docs)])
        call(master.refresh)

        table = master.state.routing_table.index("bench").shard(0)
        src = table.primary.current_node_id
        tgt = next(n.node_id for n in nodes
                   if n.node_id != src)
        probes = {"ok": 0, "failed": 0}

        def probe():
            master.search(
                "bench", {"query": {"match": {"body": "bench"}},
                          "size": 0},
                on_done=lambda r, e=None: probes.__setitem__(
                    "failed" if e or r["_shards"]["failed"] else "ok",
                    probes["failed" if e or r["_shards"]["failed"]
                           else "ok"] + 1))

        def live_write(i):
            master.bulk("bench", [
                {"op": "index", "id": f"live{i}-{j}",
                 "source": {"body": f"live doc {i}-{j}"}}
                for j in range(4)])

        for i in range(8):
            queue.schedule(0.2 + i * 0.3, probe, f"probe-{i}")
            # dense early writes: the relocation's phase 1 runs in the
            # first ~100ms of virtual time, so these land between the
            # snapshot and the handoff and exercise phase-2 replay
            queue.schedule(0.01 + i * 0.02,
                           lambda _i=i: live_write(_i), f"write-{i}")
        master.reroute(commands=[{"move": {
            "index": "bench", "shard": 0,
            "from_node": src, "to_node": tgt}}])
        for _ in range(600):
            queue.run_for(0.1)
            table = master.state.routing_table.index("bench").shard(0)
            if [s.state for s in table.shards] == [SHARD_STARTED] \
                    and table.primary.current_node_id == tgt:
                break
        queue.run_for(5.0)

        tgt_dn = cluster[tgt].data_node
        rec = next(r.to_dict() for r in tgt_dn.recoveries.values()
                   if r.recovery_type == "relocation")
        device_ms = None
        tracer = cluster[tgt].telemetry.tracer
        for summary in tracer.recent_traces(limit=16):
            if summary["root"] != "recovery":
                continue
            tree = tracer.trace(summary["trace_id"]) or {}
            for span in tree.get("spans", []):
                if span.get("name") == "recovery.device":
                    device_ms = round(span.get("duration_ms", 0.0), 3)
        return {
            "relocation_ms": rec["total_time_ms"],
            "bytes_moved": rec["index_files"]["recovered_bytes"],
            "translog_ops_replayed": rec["translog"]["ops_replayed"],
            "hbm_upload_ms": device_ms,
            "hbm_segments": rec["device"]["hbm_segments"],
            "hbm_uploaded_bytes": rec["device"]["hbm_uploaded_bytes"],
            "searches_during_move": probes["ok"] + probes["failed"],
            "searches_failed": probes["failed"],
            "stage": rec["stage"],
            "host_s": round(time.time() - t_host, 1),
        }


def run_health_cpu(seed=7):
    """Health rider (CPU-side, deterministic sim — no jax): boots a
    3-node sim cluster, lays metrics-history samples, squeezes the
    request breaker into a trip storm, and drives the
    `cluster:monitor/health_report[n]` fan-out through the squeeze and
    back out — banking the merged indicator statuses, the watchdog's
    stall-tracking stats, and the history ring's residency estimate
    into the BENCH json `health` section BEFORE any backend touch.
    Replay-stable: seeded queue + virtual clock render the same
    statuses every round."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.testing.deterministic import (
        DeterministicTaskQueue, DisruptableTransport, SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode
    from elasticsearch_tpu.utils.breaker import (
        CircuitBreaker, CircuitBreakingException)

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"hn-{i}", name=f"hn{i}")
                 for i in range(3)]
        cluster = {}
        for node in nodes:
            cluster[node.node_id] = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
        for cn in cluster.values():
            cn.start()

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        queue.run_for(60)
        master = next(cn for cn in cluster.values() if cn.is_master())
        call(master.create_index, "bench", number_of_shards=2,
             number_of_replicas=1)
        queue.run_for(30)
        healthy = call(master.health_report)

        # seeded squeeze: 6 request-breaker trips inside one history
        # window turn circuit_breakers red via the ring's trip RATE
        breaker = master.breaker_service.get_breaker(
            CircuitBreaker.REQUEST)
        for _ in range(6):
            try:
                breaker.add_estimate_bytes_and_maybe_break(
                    1 << 50, "bench-squeeze")
            except CircuitBreakingException:
                pass
        queue.run_for(11)
        squeezed = call(master.health_report)
        # periodic reports keep sampling until the storm ages out of
        # the trailing window — the verdict must recover on its own
        recovered = squeezed
        for _ in range(8):
            queue.run_for(10)
            recovered = call(master.health_report)

        master_det = squeezed["indicators"]["circuit_breakers"][
            "details"]["nodes"][master.local_node.node_id]
        history = master.telemetry.history
        return {
            "status_healthy": healthy["status"],
            "status_squeezed": squeezed["status"],
            "status_recovered": recovered["status"],
            "indicators_squeezed": {
                name: ind["status"] for name, ind in
                sorted(squeezed["indicators"].items())},
            "breaker_trips_in_window": int(master_det["recent_trips"]),
            "watchdog": master.health_watchdog.stats(),
            "history_samples": len(history.samples()),
            "history_memory_bytes": history.memory_bytes(),
            "host_s": round(time.time() - t_host, 1),
        }


def run_upgrade_cpu(seed=11):
    """Rolling-upgrade rider (CPU-side, deterministic sim — no jax):
    boots a 3-node sim cluster, indexes a seed corpus, then gracefully
    bounces every node in turn — restart shutdown marker, stop, restart
    over the same data dir — with bulks and searches running through
    each bounce. Banks per-node bounce wall-clock (virtual seconds),
    delayed-vs-reallocated shard counts, searches served during each
    bounce, and the zero-acked-loss verdict into the BENCH json
    `upgrade` section BEFORE any backend touch. Replay-stable: seeded
    queue + virtual clock render the same rows every round."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.cluster.state import SHARD_STARTED
    from elasticsearch_tpu.testing.deterministic import (
        CONNECTED, DISCONNECTED, DeterministicTaskQueue,
        DisruptableTransport, SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"un-{i}", name=f"un{i}")
                 for i in range(3)]
        cluster = {}

        def boot(node):
            cn = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
            cluster[node.node_id] = cn
            cn.start()
            return cn

        for node in nodes:
            boot(node)

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        def master():
            return next(cn for cn in cluster.values()
                        if cn.is_master())

        queue.run_for(60)
        call(master().create_index, "bench", number_of_shards=2,
             number_of_replicas=2)
        queue.run_for(60)
        items = [{"op": "index", "id": f"seed-{i}",
                  "source": {"body": f"seed doc {i}"}}
                 for i in range(40)]
        call(master().bulk, "bench", items)
        acked, submitted = 40, 40

        bounces = []
        master_id = master().local_node.node_id
        order = sorted(nid for nid in cluster if nid != master_id)
        order.append(master_id)
        for step, vid in enumerate(order):
            t0 = queue.now()
            call(master().put_node_shutdown, vid, "restart",
                 allocation_delay="600s")
            cn = cluster.pop(vid)
            cn.stop()
            down = cn.local_node
            for other in nodes:
                network.set_link(down, other, DISCONNECTED)
            queue.run_for(20)
            coord = cluster[sorted(cluster)[0]]
            state = master().state
            delayed = sum(1 for s in state.routing_table.all_shards()
                          if s.delayed)
            searches = 0
            for q in ("seed", "doc", "bench"):
                r = call(coord.search, "bench",
                         {"query": {"match": {"body": q}}, "size": 5})
                if r["_shards"]["failed"] == 0:
                    searches += 1
            mid = [{"op": "index", "id": f"mid-{step}-{i}",
                    "source": {"body": f"mid doc {i}"}}
                   for i in range(5)]
            resp = call(coord.bulk, "bench", mid)
            submitted += 5
            acked += sum(1 for it in resp["items"]
                         if it and "error" not in it)
            for other in nodes:
                network.set_link(down, other, CONNECTED)
            back = boot(down)
            queue.run_for(60)
            state = master().state
            reattached = sum(
                1 for r in back.data_node.recoveries.values()
                if r.recovery_type == "existing_store")
            reallocated = sum(
                1 for r in back.data_node.recoveries.values()
                if r.recovery_type != "existing_store")
            bounces.append({
                "node": down.name,
                "was_master": vid == master_id,
                "wall_s": round(queue.now() - t0, 1),
                "delayed_shards": delayed,
                "reattached": reattached,
                "reallocated": reallocated,
                "searches_served": searches,
            })

        call(master().refresh)
        r = call(master().search, "bench",
                 {"query": {"match_all": {}}, "size": 0})
        total = r["hits"]["total"]["value"]
        started = [s for s in
                   master().state.routing_table.all_shards()
                   if s.state == SHARD_STARTED]
        for cn in cluster.values():
            cn.stop()
        return {
            "bounces": bounces,
            "acked_writes": acked,
            "docs_after": total,
            "zero_acked_loss": bool(total == acked == submitted),
            "active_shards_after": len(started),
            "host_s": round(time.time() - t_host, 1),
        }


def run_cursors_cpu(seed=13):
    """Cursor-plane rider (CPU-side, deterministic sim — no jax):
    boots a 3-node sim cluster, drains a sorted scroll to exhaustion
    while a context-owning node is killed mid-stream (the portable
    cursor fails over to another copy at the same continuation point),
    relocates a PIT-pinned primary with an explicit reroute move (the
    `pit/…` retention lease transfers at the handoff barrier), and
    pushes a small async-search backlog through submit/get/delete.
    Banks pages drained, exactly-once verdicts, failover/lease-
    transfer counts and the async backlog into the BENCH json
    `cursors` section BEFORE any backend touch. Replay-stable: seeded
    queue + virtual clock render the same rows every round."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.testing.deterministic import (
        DISCONNECTED, DeterministicTaskQueue, DisruptableTransport,
        SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"kn-{i}", name=f"kn{i}")
                 for i in range(3)]
        cluster = {}
        for node in nodes:
            cn = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
            cluster[node.node_id] = cn
            cn.start()

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        def master():
            return next(cn for cn in cluster.values()
                        if cn.is_master())

        def hit_ids(resp):
            return [h["_id"] for h in resp["hits"]["hits"]]

        queue.run_for(60)
        call(master().create_index, "bench", number_of_shards=3,
             number_of_replicas=1)
        queue.run_for(60)
        body = {"query": {"match_all": {}}, "sort": [{"n": "desc"}]}
        call(master().bulk, "bench",
             [{"op": "index", "id": f"doc-{i}",
               "source": {"body": f"cursor doc {i}", "n": i}}
              for i in range(36)])
        call(master().refresh)
        whole = hit_ids(call(master().search, "bench",
                             {**body, "size": 100}))

        # -- scroll drain with a mid-stream node kill (copy failover)
        coord = master()
        t_v0 = queue.now()
        resp = call(coord.search, "bench", {**body, "size": 7},
                    scroll=300.0)
        sid, ids, pages = resp["_scroll_id"], hit_ids(resp), 1
        while resp["hits"]["hits"]:
            if pages == 2:      # between pages: kill a context owner
                rec = coord.search_service._scrolls.get(sid, {})
                victim = next(
                    (e["node"] for _k, e in
                     sorted(rec.get("shards", {}).items())
                     if e["node"] != coord.local_node.node_id), None)
                if victim is not None:
                    down = cluster.pop(victim)
                    down.stop()
                    for other in nodes:
                        network.set_link(down.local_node, other,
                                         DISCONNECTED)
                    queue.run_for(30)
            resp = call(coord.scroll, sid, 300.0)
            ids += hit_ids(resp)
            pages += 1
        call(coord.clear_scroll, [sid])
        scroll_virtual_s = round(queue.now() - t_v0, 1)

        # -- PIT pinned through an explicit primary move (lease travels)
        call(master().create_index, "pinned", number_of_shards=1,
             number_of_replicas=0)
        queue.run_for(60)
        call(master().bulk, "pinned",
             [{"op": "index", "id": f"p-{i}",
               "source": {"body": f"pinned doc {i}", "n": i}}
              for i in range(12)])
        call(master().refresh)
        pit = call(master().open_pit, "pinned", 600.0)["id"]
        pit_body = {**body, "size": 50, "pit": {"id": pit}}
        before = hit_ids(call(master().search, "_all", pit_body))
        state = master().state
        src = state.routing_table.index("pinned").shard(0) \
            .primary.current_node_id
        tgt = next(nid for nid in sorted(cluster) if nid != src)
        call(master().reroute, commands=[{"move": {
            "index": "pinned", "shard": 0,
            "from_node": src, "to_node": tgt}}])
        queue.run_for(60)
        after = hit_ids(call(master().search, "_all", pit_body))
        call(master().close_pit, pit)
        lease_transfers = sum(cn.data_node.lease_transfers
                              for cn in cluster.values())

        # -- async-search backlog: submit a burst, then drain it
        subs = [call(master().submit_async_search, "bench",
                     {**body, "size": 5},
                     {"wait_for_completion_timeout": "0s",
                      "keep_alive": "5m"})
                for _ in range(4)]
        queue.run_for(30)
        backlog = master().async_search.open_async_search_count()
        done = sum(
            1 for s in subs
            if call(master().get_async_search, s["id"],
                    {})["is_running"] is False)
        for s in subs:
            call(master().delete_async_search, s["id"])
        queue.run_for(10)

        out = {
            "docs": len(whole),
            "pages_drained": pages,
            "scroll_exactly_once": bool(ids == whole),
            "scroll_virtual_s": scroll_virtual_s,
            "cursor_failovers": coord.search_service.cursor_failovers,
            "lease_transfers": lease_transfers,
            "pit_stable_across_move": bool(before == after and
                                           len(before) == 12),
            "async_backlog": backlog,
            "async_completed": done,
            "async_open_after_delete":
                master().async_search.open_async_search_count(),
            "host_s": round(time.time() - t_host, 1),
        }
        for cn in cluster.values():
            cn.stop()
        return out


def run_tenants_cpu(seed=19):
    """Tenant-accounting rider (CPU-side, deterministic sim — no jax):
    boots a 3-node sim cluster and runs a mixed two-tenant workload —
    an `interactive` searcher with a tight latency objective against a
    `hog` that bulks, drains scrolls, and finally slams into a shrunk
    indexing-pressure limit (a seeded rejection burst). Banks per-
    tenant qps/p50/p99 + SLO-violation counts from the merged
    `_tenants/stats` fan-out and the `noisy_neighbor` verdict (which
    must name the hog) into the BENCH json `tenants` section BEFORE
    any backend touch. Replay-stable: seeded queue + virtual clock
    render the same rows every round."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.testing.deterministic import (
        DeterministicTaskQueue, DisruptableTransport, SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"tt-{i}", name=f"tt{i}")
                 for i in range(3)]
        cluster = {}
        for node in nodes:
            cn = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
            cluster[node.node_id] = cn
            cn.start()
        # per-tenant latency objectives (virtual ms): interactive is
        # held to a tight SLO, the hog gets a loose one
        for cn in cluster.values():
            cn.telemetry.tenants.slo_objectives = {
                "interactive": 25.0, "hog": 400.0}

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        queue.run_for(60)
        master = next(cn for cn in cluster.values() if cn.is_master())
        # index-default tagging: bulks carry no body, so each index
        # names its tenant (precedence: header > body > index default)
        call(master.create_index, "inter", number_of_shards=2,
             number_of_replicas=1,
             settings={"index.tenant.default": "interactive"})
        call(master.create_index, "hoggy", number_of_shards=2,
             number_of_replicas=1,
             settings={"index.tenant.default": "hog"})
        queue.run_for(30)
        call(master.bulk, "inter",
             [{"op": "index", "id": f"i-{i}",
               "source": {"body": f"interactive doc {i}", "n": i}}
              for i in range(30)])
        # baseline report: lays the history-ring sample the final
        # report's windowed deltas anchor against (the ring samples on
        # report boundaries, not on a background task)
        call(master.health_report)
        t0_virtual = queue.now()

        # mixed workload: every round the interactive tenant runs a
        # tagged search; the hog bulks a batch and periodically drains
        # a scroll over its whole index
        for rnd in range(12):
            call(master.search, "inter",
                 {"tenant": "interactive",
                  "query": {"match": {"body": "interactive"}},
                  "size": 5})
            call(master.bulk, "hoggy",
                 [{"op": "index", "id": f"h-{rnd}-{i}",
                   "source": {"body": f"hog doc {rnd} {i}", "n": i}}
                  for i in range(20)])
            if rnd % 3 == 2:
                page = call(master.search, "hoggy",
                            {"tenant": "hog",
                             "query": {"match_all": {}}, "size": 25},
                            scroll=60.0)
                while page["hits"]["hits"]:
                    page = call(master.scroll, page["_scroll_id"], 60.0)
        workload_virtual_s = max(queue.now() - t0_virtual, 1e-9)

        # seeded rejection burst: shrink the coordinating node's
        # indexing-pressure budget so the hog's bulks shed with 429s —
        # the shed_load dimension the noisy_neighbor indicator reads
        saved_limit = master.indexing_pressure.limit
        master.indexing_pressure.limit = 64
        rejected = 0
        for i in range(8):
            try:
                call(master.bulk, "hoggy",
                     [{"op": "index", "id": f"burst-{i}",
                       "source": {"body": "x" * 256}}])
            except RuntimeError:
                rejected += 1
        master.indexing_pressure.limit = saved_limit
        queue.run_for(11)   # let the history ring sample the burst

        report = call(master.health_report)
        noisy = report["indicators"]["noisy_neighbor"]
        merged = call(master.tenants_stats)

        def row(tenant):
            t = merged["tenants"].get(tenant, {})
            search = t.get("search", {})
            lat = search.get("latency", {})
            slo = t.get("slo", {})
            return {
                "searches": search.get("count", 0),
                "qps_virtual": round(
                    search.get("count", 0) / workload_virtual_s, 2),
                "p50_ms": lat.get("p50_ms", 0.0),
                "p99_ms": lat.get("p99_ms", 0.0),
                "indexing_bytes": t.get("indexing", {}).get("bytes", 0),
                "rejections": t.get("indexing", {}).get("rejections", 0),
                "slo_violations": slo.get("violations", 0),
                "slo_burn_pct": slo.get("budget_burn_pct", 0.0),
            }

        out = {
            "tenants_live": merged["cardinality"]["live"],
            "interactive": row("interactive"),
            "hog": row("hog"),
            "rejected_bursts": rejected,
            "noisy_status": noisy["status"],
            "noisy_named": sorted({
                r for d in noisy.get("diagnosis", [])
                for r in d.get("affected_resources", [])}),
            "host_s": round(time.time() - t_host, 1),
        }
        for cn in cluster.values():
            cn.stop()
        return out


def run_snapshots_cpu(n_docs=300, seed=23):
    """Snapshot/restore rider (CPU-side, deterministic sim — no jax):
    a 3-node sim cluster indexes ``n_docs`` into a 2-shard index, takes
    a distributed snapshot into an fs repository while probe searches
    keep running, takes a SECOND snapshot of the unchanged index (the
    incremental pass — its uploaded bytes must stay ~zero), indexes a
    delta and snapshots a third time, then restores the first snapshot
    under rename through the staged recovery protocol. All clocks are
    VIRTUAL (sim seconds), so every number is replay-stable round over
    round — banked into the BENCH json `snapshots` section BEFORE any
    backend touch."""
    import tempfile

    from elasticsearch_tpu.cluster.node import ClusterNode
    from elasticsearch_tpu.cluster.state import SHARD_STARTED
    from elasticsearch_tpu.testing.deterministic import (
        DeterministicTaskQueue, DisruptableTransport, SimNetwork)
    from elasticsearch_tpu.transport.transport import DiscoveryNode

    t_host = time.time()
    with tempfile.TemporaryDirectory() as tmp:
        queue = DeterministicTaskQueue(seed=seed)
        network = SimNetwork(queue)
        nodes = [DiscoveryNode(node_id=f"sn-{i}", name=f"sn{i}")
                 for i in range(3)]
        cluster = {}
        for node in nodes:
            cluster[node.node_id] = ClusterNode(
                DisruptableTransport(node, network), queue,
                data_path=os.path.join(tmp, node.name),
                seed_nodes=nodes,
                initial_master_nodes=[n.name for n in nodes],
                rng=queue.random)
        for cn in cluster.values():
            cn.start()

        def call(fn, *args, **kwargs):
            box = {}
            fn(*args, **kwargs,
               on_done=lambda r, e=None: box.update(r=r, e=e))
            for _ in range(120):
                if box:
                    break
                queue.run_for(1.0)
            if box.get("e") is not None:
                raise RuntimeError(box["e"])
            return box.get("r")

        queue.run_for(60)
        master = next(cn for cn in cluster.values() if cn.is_master())
        call(master.create_index, "bench", number_of_shards=2,
             number_of_replicas=0)
        queue.run_for(30)
        call(master.bulk, "bench", [
            {"op": "index", "id": f"d{i}",
             "source": {"body": f"bench doc {i} term{i % 37}"}}
            for i in range(n_docs)])
        call(master.refresh)
        call(master.put_repository, "bench-backup",
             {"type": "fs",
              "settings": {"location": os.path.join(tmp, "repo")}})

        probes = {"ok": 0, "failed": 0}

        def probe():
            master.search(
                "bench", {"query": {"match": {"body": "bench"}},
                          "size": 0},
                on_done=lambda r, e=None: probes.__setitem__(
                    "failed" if e or r["_shards"]["failed"] else "ok",
                    probes["failed" if e or r["_shards"]["failed"]
                           else "ok"] + 1))

        # probes land inside the snapshot window: per-shard uploads run
        # over several virtual network hops, so the first ~2s of sim
        # time IS the snapshot — writes stay unblocked throughout
        for i in range(8):
            queue.schedule(0.05 + i * 0.25, probe, f"snap-probe-{i}")
        snap1 = call(master.create_snapshot, "bench-backup", "snap-1",
                     {"indices": "bench"})["snapshot"]
        st1 = call(master.snapshot_status, "bench-backup",
                   "snap-1")["stats"]
        # incremental pass over the unchanged index: every segment blob
        # dedups by content hash, so uploaded bytes must stay ~zero
        call(master.create_snapshot, "bench-backup", "snap-2",
             {"indices": "bench"})
        st2 = call(master.snapshot_status, "bench-backup",
                   "snap-2")["stats"]
        call(master.bulk, "bench", [
            {"op": "index", "id": f"x{i}",
             "source": {"body": f"delta doc {i} extra{i % 11}"}}
            for i in range(50)])
        call(master.refresh)
        call(master.create_snapshot, "bench-backup", "snap-3",
             {"indices": "bench"})
        st3 = call(master.snapshot_status, "bench-backup",
                   "snap-3")["stats"]

        t_restore = queue.now()
        call(master.restore_snapshot, "bench-backup", "snap-1",
             {"indices": "bench", "rename_pattern": "bench",
              "rename_replacement": "bench_restored"})
        restore_ms = None
        for _ in range(600):
            queue.run_for(0.1)
            table = master.state.routing_table.index("bench_restored")
            if table is not None and all(
                    s.state == SHARD_STARTED
                    for sid in range(2)
                    for s in table.shard(sid).shards):
                restore_ms = round((queue.now() - t_restore) * 1000)
                break
        queue.run_for(5.0)
        restore_recs = [
            r.to_dict() for cn in cluster.values()
            for r in cn.data_node.recoveries.values()
            if r.recovery_type == "snapshot"]
        restored = call(master.search, "bench_restored",
                        {"query": {"match_all": {}}, "size": 0})
        out = {
            "snapshot_ms": snap1["end_time_in_millis"]
            - snap1["start_time_in_millis"],
            "snapshot_uploaded_bytes": st1["uploaded_bytes"],
            "snapshot_files": st1["file_count"],
            "incremental_delta_bytes": st2["uploaded_bytes"],
            "incremental_skipped_bytes": st2["skipped_bytes"],
            "third_uploaded_bytes": st3["uploaded_bytes"],
            "restore_ms": restore_ms,
            "restore_shard_ms": max((r["total_time_ms"]
                                     for r in restore_recs),
                                    default=None),
            "restore_shards": len(restore_recs),
            "restored_docs": restored["hits"]["total"]["value"],
            "searches_during_snapshot": probes["ok"] + probes["failed"],
            "searches_failed": probes["failed"],
            "host_s": round(time.time() - t_host, 1),
        }
        for cn in cluster.values():
            cn.stop()
        return out


def run_macro_cpu(seed=29, smoke=False):
    """Macro-workload rider (CPU-side, deterministic sim — no jax):
    the Rally-style open-loop mix from ``bench/macro.py`` — tenant-
    tagged interactive/bulk/aggs/scroll/async arrivals against a
    3-node sim cluster — through an injected ``_cluster/reroute``
    relocation AND a node stop/restart. Banks per-class qps/p50/p99 +
    SLO burn from the merged ``/_workload/stats`` fan-out, the
    ``workload_slo`` verdict probed mid-chaos, the disruption
    timeline, and the zero-acked-write-loss verdict into the BENCH
    json ``macro`` section BEFORE any backend touch. Replay-stable:
    all virtual clocks; the full transcript is folded to its sha256."""
    from elasticsearch_tpu.bench.macro import run_macro

    t_host = time.time()
    out = run_macro(seed=seed, smoke=smoke)
    out.pop("transcript", None)
    out["host_s"] = round(time.time() - t_host, 1)
    return out


# ---------------------------------------------------------------------------
# Multi-chip serving rows (ISSUE 9): qps at 1/2/4/8 devices for the two
# mesh serving modes — sharded-corpus (one SPMD fan-out/merge program per
# query, parallel/mesh_executor.py) and replica-parallel (continuous-
# batching cohorts split their query axis over the mesh). EVERY row runs
# in a SUBPROCESS pinned to a CPU virtual-device mesh
# (--xla_force_host_platform_device_count).
# ---------------------------------------------------------------------------

_MC_QUERY_VOCAB = ["amber", "basalt", "cedar", "dune", "ember", "fjord",
                   "granite", "harbor", "islet", "juniper", "krill",
                   "lagoon"]


def _multichip_row(n_devices: int, mode: str) -> None:
    """Subprocess entry (``bench.py --multichip-row N MODE``): ONE
    scaling row, incrementally re-printed as JSON (the dryrun
    convention — a kill mid-row still leaves a parseable record)."""
    out = {"mode": mode, "requested_devices": n_devices}

    def bank(**kw):
        out.update(kw)
        print(json.dumps({"multichip_row": out}), flush=True)

    bank()
    import jax

    devices = len(jax.devices())
    bank(devices=devices)
    if mode == "sharded_corpus":
        _multichip_row_sharded(bank, devices, n_devices)
    else:
        _multichip_row_replica(bank, devices)


def _multichip_row_sharded(bank, devices: int, n_devices: int) -> None:
    """REST `_search` qps through the product path: index with one
    shard per device, pinned query mix (bm25 / bool+filter / knn),
    mesh vs per-shard loop, with a parity check."""
    import tempfile

    from elasticsearch_tpu.node import Node

    shards = max(1, min(n_devices, devices))
    docs = int(os.environ.get("BENCH_MULTICHIP_DOCS", 3000))
    n_q = int(os.environ.get("BENCH_MULTICHIP_QUERIES", 48))
    rng = np.random.default_rng(11)
    bodies = []
    for i in range(n_q):
        kind = i % 3
        if kind == 0:
            bodies.append({"query": {"match": {"title": " ".join(
                rng.choice(_MC_QUERY_VOCAB, 2))}}, "size": 10})
        elif kind == 1:
            bodies.append({"query": {"bool": {
                "must": [{"match": {"title": str(
                    rng.choice(_MC_QUERY_VOCAB))}}],
                "filter": [{"term": {"tag": str(
                    rng.choice(["x", "y"]))}}]}}, "size": 10})
        else:
            bodies.append({"knn": {
                "field": "vec",
                "query_vector": rng.standard_normal(16).tolist(),
                "k": 10, "num_candidates": 64},
                "_source": False, "size": 10})
    with tempfile.TemporaryDirectory() as tmp:
        node = Node(data_path=tmp)
        try:
            rc = node.rest_controller
            status, _ = rc.dispatch("PUT", "/mc", None, {
                "settings": {"index": {"number_of_shards": shards}},
                "mappings": {"properties": {
                    "title": {"type": "text"},
                    "tag": {"type": "keyword"},
                    "vec": {"type": "dense_vector", "dims": 16,
                            "similarity": "cosine"}}}})
            assert status == 200, status
            for i in range(docs):
                rc.dispatch("PUT", f"/mc/_doc/{i}", None, {
                    "title": " ".join(rng.choice(_MC_QUERY_VOCAB,
                                                 rng.integers(2, 8))),
                    "tag": str(rng.choice(["x", "y"])),
                    "vec": rng.standard_normal(16).astype(
                        np.float32).tolist()})
            rc.dispatch("POST", "/mc/_refresh", None, None)
            rc.dispatch("POST", "/mc/_forcemerge", None, None)
            bank(shards=shards, docs=docs, build_ok=True)

            def measure():
                for b in bodies[:6]:        # warm compiles out of band
                    rc.dispatch("POST", "/mc/_search", None, dict(b))
                t0 = time.time()
                hits = []
                for b in bodies:
                    st, r = rc.dispatch("POST", "/mc/_search", None,
                                        dict(b))
                    assert st == 200, (st, r)
                    hits.append([(h["_id"], h["_score"])
                                 for h in r["hits"]["hits"]])
                return round(n_q / (time.time() - t0), 1), hits

            svc = node.search_service
            mesh_before = svc.mesh_executor.mesh_searches
            qps_mesh, mesh_hits = measure()
            mesh_used = svc.mesh_executor.mesh_searches - mesh_before
            bank(qps_mesh=qps_mesh, mesh_searches=int(mesh_used),
                 mesh=mesh_used > 0,
                 counters=dict(svc.mesh_executor.counters))
            os.environ["ESTPU_MESH_SERVING"] = "0"
            try:
                qps_loop, loop_hits = measure()
            finally:
                del os.environ["ESTPU_MESH_SERVING"]
            bank(qps_loop=qps_loop,
                 speedup=round(qps_mesh / qps_loop, 2) if qps_loop
                 else None,
                 parity=mesh_hits == loop_hits)
        finally:
            node.close()


def _multichip_row_replica(bank, devices: int) -> None:
    """Kernel-level cohort fan-out: a 32-query plan cohort launched
    single-device vs replica-sharded over the mesh (corpus replicated,
    Q axis split) — launches/s and byte parity."""
    from __graft_entry__ import _synthetic_blocks
    from elasticsearch_tpu.ops import plan as plan_ops
    from elasticsearch_tpu.parallel.mesh_executor import MeshSearchBackend

    nd = int(os.environ.get("BENCH_MULTICHIP_ND", 65536))
    cohort = 32
    rng = np.random.default_rng(7)
    docids, tfs, zero_block = _synthetic_blocks(
        rng, nd, n_terms=16, postings_per_term=2048)
    lens = rng.integers(5, 60, size=nd).astype(np.float32)
    live = np.ones(nd, bool)
    nb = 64
    sel = np.full((cohort, nb), zero_block, np.int32)
    w = np.zeros((cohort, nb), np.float32)
    for qi in range(cohort):
        picks = rng.choice(16, size=3, replace=False)
        for j, t in enumerate(picks):
            lo = t * 16
            sel[qi, j * 16:(j + 1) * 16] = np.arange(lo, lo + 16)
            w[qi, j * 16:(j + 1) * 16] = 1.0 + 0.1 * j
    grp = np.zeros((cohort, nb), np.int32)
    sub = sel.copy()
    cst = np.zeros((cohort, nb), bool)
    gk = np.full((cohort, 4), plan_ops.SHOULD, np.int32)
    gr = np.ones((cohort, 4), np.int32)
    gc = np.full((cohort, 4), np.nan, np.float32)
    scalars = [np.zeros(cohort, np.int32)] * 3 + \
        [np.zeros(cohort, np.float32)] * 2
    backend = MeshSearchBackend()
    rmesh = backend.replica_mesh_for(cohort)
    bank(docs=nd, cohort=cohort,
         replica_devices=int(rmesh.devices.size) if rmesh is not None
         else 1)

    def launch(sharded: bool):
        st = plan_ops.FieldStream(docids, tfs, lens,
                                  np.float32(lens.mean()),
                                  sel, grp, sub, w, cst)
        args = [gk, gr, gc, live] + scalars
        if sharded:
            rep = [backend.replicated(rmesh, a)
                   for a in (docids, tfs, lens,
                             np.float32(lens.mean()))]
            st = plan_ops.FieldStream(
                *rep, *[backend.shard_rows(rmesh, a)
                        for a in (sel, grp, sub, w, cst)])
            args = [backend.shard_rows(rmesh, gk),
                    backend.shard_rows(rmesh, gr),
                    backend.shard_rows(rmesh, gc),
                    backend.replicated(rmesh, live)] + \
                [backend.shard_rows(rmesh, a) for a in scalars]
        return np.asarray(plan_ops.plan_topk_batch(
            [st], args[0], args[1], args[2], args[3], args[4], args[5],
            args[6], args[7], args[8], k=10))

    reps = int(os.environ.get("BENCH_MULTICHIP_REPS", 20))
    solo = launch(False)                      # warm
    t0 = time.time()
    for _ in range(reps):
        solo = launch(False)
    solo_qps = round(cohort * reps / (time.time() - t0), 1)
    bank(qps_solo=solo_qps)
    if rmesh is None:
        bank(skipped="fewer than 2 devices — replica fan-out n/a")
        return
    meshed = launch(True)                     # warm (sharded signature)
    t0 = time.time()
    for _ in range(reps):
        meshed = launch(True)
    mesh_qps = round(cohort * reps / (time.time() - t0), 1)
    bank(qps_mesh=mesh_qps,
         speedup=round(mesh_qps / solo_qps, 2) if solo_qps else None,
         parity=bool(np.array_equal(solo, meshed)))


def run_multichip_serving() -> dict:
    """The `multichip_serving` BENCH section: one subprocess per row,
    each on a CPU virtual-device mesh (1/2/4/8). These rows check the
    mesh modes' behaviour and parity; their qps are CPU numbers, not
    device speed. Native rows would need the chip in a child process
    while this one holds it, so the chip's mesh path is driven by
    ``chip_smoke.py --chips 4`` instead."""
    import re
    import subprocess

    section = {"rows": []}
    row_s = float(os.environ.get("BENCH_MULTICHIP_ROW_S", 420))

    def run_row(n_devices: int, mode: str, env_extra: dict,
                label: str) -> dict:
        env = {**os.environ, **env_extra}
        try:
            r = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--multichip-row", str(n_devices), mode],
                capture_output=True, text=True, timeout=row_s, env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)))
        except subprocess.TimeoutExpired as e:
            # the row's own incremental banking still surfaces partial
            # progress from the killed subprocess's stdout
            row = _last_row_json(e.stdout or "")
            row.update({"mode": mode, "backend": label,
                        "skipped": f"row subprocess exceeded "
                                   f"{row_s:.0f}s"})
            return row
        row = _last_row_json(r.stdout)
        row.setdefault("mode", mode)
        row["backend"] = label
        if not row.get("qps_mesh") and not row.get("qps_loop") \
                and not row.get("qps_solo") and "skipped" not in row:
            tail = (r.stderr or r.stdout or "").strip().splitlines()[-2:]
            row["skipped"] = ("row produced no qps: "
                              + " | ".join(tail))[:400]
        return row

    def _last_row_json(stdout: str) -> dict:
        for line in reversed((stdout or "").splitlines()):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if isinstance(parsed, dict) and "multichip_row" in parsed:
                return dict(parsed["multichip_row"])
        return {}

    if os.environ.get("BENCH_MULTICHIP", "1") == "0":
        section["skipped"] = "disabled (BENCH_MULTICHIP=0)"
        return section
    # the section's own wall-clock cap: remaining rows bank as typed
    # skips instead of eating the serving sections' budget
    sec_budget = float(os.environ.get("BENCH_MULTICHIP_BUDGET_S", 900))
    t_sec = time.time()

    def over_budget() -> bool:
        return (time.time() - t_sec > sec_budget
                or remaining_budget() < 900)

    for mode in ("sharded_corpus", "replica_parallel"):
        for d in (1, 2, 4, 8):
            if mode == "replica_parallel" and d == 1:
                continue          # solo baseline rides every row
            if over_budget():
                section["rows"].append(
                    {"mode": mode, "backend": f"cpu-virtual-{d}",
                     "skipped": "multichip section wall-clock budget"})
                continue
            # REPLACE any inherited device-count flag: each row must see
            # exactly d virtual devices, not the parent harness's count
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""))
            flags = (flags + f" --xla_force_host_platform_"
                             f"device_count={d}").strip()
            row = run_row(d, mode, {"JAX_PLATFORMS": "cpu",
                                    "XLA_FLAGS": flags},
                          label=f"cpu-virtual-{d}")
            section["rows"].append(row)
            log(f"multichip row {mode}/cpu-{d}: "
                f"{json.dumps(row)[:200]}")
    return section


def run_aggs_device(rng, aggs_rows):
    """Device reduction rows (requires a live backend): the fused
    metric-stats launch, histogram scatter-add, and per-bucket metric
    columns — wall time per launch after warm-up, vs the host rows
    already banked."""
    import jax

    from elasticsearch_tpu.ops.aggs import (
        bucket_counts,
        bucket_metric_columns,
        masked_metric_stats,
    )
    vals, missing, mask, steps = _aggs_columns(rng)
    dv = jax.device_put(vals.astype(np.float32))
    dm = jax.device_put(missing)
    dk = jax.device_put(mask)
    ids = np.clip(steps, 0, AGGS_NB - 1).astype(np.int32)
    di = jax.device_put(ids)

    masked_metric_stats(dv, dm, dk)          # warm (compile)
    t0 = time.time()
    for _ in range(AGGS_REPS):
        masked_metric_stats(dv, dm, dk)
    aggs_rows["device_metric_stats_ms"] = round(
        (time.time() - t0) / AGGS_REPS * 1000, 2)

    bucket_counts(di, dk, AGGS_NB)
    t0 = time.time()
    for _ in range(AGGS_REPS):
        bucket_counts(di, dk, AGGS_NB)
    aggs_rows["device_histogram_counts_ms"] = round(
        (time.time() - t0) / AGGS_REPS * 1000, 2)

    bucket_metric_columns(di, dk, dv, dm, AGGS_NB)
    t0 = time.time()
    for _ in range(AGGS_REPS):
        bucket_metric_columns(di, dk, dv, dm, AGGS_NB)
    aggs_rows["device_bucket_metrics_ms"] = round(
        (time.time() - t0) / AGGS_REPS * 1000, 2)

    for fam in ("metric_stats", "histogram_counts", "bucket_metrics"):
        host = aggs_rows.get(f"host_{fam}_ms")
        dev = aggs_rows.get(f"device_{fam}_ms")
        if host and dev:
            aggs_rows[f"{fam}_speedup"] = round(host / dev, 2)
    return aggs_rows


def main():
    import signal
    import tempfile

    signal.signal(signal.SIGTERM, _term_handler)
    signal.signal(signal.SIGINT, _term_handler)
    parts = {}

    def emit_now(**updates):
        parts.update(updates)
        if parts.get("rest_qps") is not None:
            value = parts["rest_qps"]
        else:
            value = parts.get("kernel_qps", 0.0)
        cpu = parts.get("cpu_qps") or 0.0
        # the serving section carries BOTH the dispatch snapshot (set
        # once the REST path runs) and the CPU-side profile rider
        # (per-phase percentiles + sampled tree, banked pre-backend)
        serving = {**(parts.get("serving") or {}),
                   **(parts.get("serving_profile") or {})} or None
        emit(compose_metric(parts), value,
             value / cpu if cpu else float("nan"),
             engine=_engine_snapshot(parts),
             overload=parts.get("overload"),
             tasks=parts.get("tasks"),
             cpu=parts.get("cpu"),
             serving=serving,
             skipped=parts.get("skipped"),
             aggs=parts.get("aggs"),
             multichip=parts.get("multichip"),
             lint=parts.get("lint"),
             recovery=parts.get("recovery"),
             health=parts.get("health"),
             upgrade=parts.get("upgrade"),
             cursors=parts.get("cursors"),
             tenants=parts.get("tenants"),
             snapshots=parts.get("snapshots"),
             macro=parts.get("macro"))

    # estpu-lint scan: static contract scan of the whole package
    # (stdlib ast, ~2s, no device). Summary rides every BENCH line.
    try:
        from elasticsearch_tpu.lint import run_lint
        t0 = time.time()
        s = run_lint().summary()
        parts["lint"] = {
            "rules_run": s["rules_run"], "files": s["files"],
            "violations": s["violations"],
            "baselined": s["baselined"],
            "allowlisted": s["allowlisted"], "ok": s["ok"],
            "scan_s": round(time.time() - t0, 1),
        }
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"lint scan failed: {e!r}")

    rng = np.random.default_rng(12345)
    t0 = time.time()
    corpus = build_corpus(rng)
    cpu_rows = {
        "docs": N_DOCS, "vocab": VOCAB, "queries": N_QUERIES,
        "postings": int(corpus["n_postings"]),
        "blocks": int(corpus["block_docids"].shape[0]),
        "corpus_build_s": round(time.time() - t0, 1),
    }
    parts["cpu"] = cpu_rows
    queries = make_queries(rng, corpus["df"])
    # corpus stats banked IMMEDIATELY — even a kill during the truth
    # pass leaves a parsed line with non-zero CPU rows
    emit_now()

    t0 = time.time()
    truth = cpu_exact_truth(corpus, queries)
    cpu_rows["exact_truth_s"] = round(time.time() - t0, 1)
    cpu_qps, cpu_recall = run_cpu_maxscore(corpus, queries, truth,
                                           cpu_rows)
    cpu_rows["baseline_qps"] = round(cpu_qps or 0.0, 1)
    cpu_rows["baseline_self_recall"] = round(cpu_recall or 0.0, 4)
    parts.update(cpu_qps=cpu_qps, cpu_recall=cpu_recall)
    # aggregation HOST rows (pure numpy — metric moments, histogram
    # unique, per-bucket chains, sketch build/merge/error, incremental
    # partial-reduce counts) bank with the other CPU rows
    try:
        t0 = time.time()
        parts["aggs"] = run_aggs_cpu(rng)
        cpu_rows["aggs_host_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"aggs host section failed: {e!r}")
    # profiling HOST rows: per-phase p50/p95/p99 + one sampled profile
    # tree through the PR-8 recorder/tree-builder (stdlib-only)
    try:
        t0 = time.time()
        parts["serving_profile"] = run_profile_cpu(corpus, queries)
        cpu_rows["profile_host_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"profile host section failed: {e!r}")
    # relocation/recovery rows (deterministic sim, no jax): replay-
    # stable virtual timings for a primary move under search load
    try:
        parts["recovery"] = run_recovery_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"recovery rider failed: {e!r}")
    # health rows (deterministic sim, no jax): indicator verdicts
    # through a seeded breaker squeeze + watchdog/history residency
    try:
        parts["health"] = run_health_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"health rider failed: {e!r}")
    # rolling-upgrade rows (deterministic sim, no jax): graceful
    # node bounces under live traffic — delayed-allocation counts,
    # reattach-vs-copy split, and the zero-acked-loss verdict
    try:
        parts["upgrade"] = run_upgrade_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"upgrade rider failed: {e!r}")
    # cursor rows (deterministic sim, no jax): scroll pages drained
    # through a mid-stream node kill, PIT lease transfers across a
    # primary move, and the async-search backlog — replay-stable
    # virtual counts
    try:
        parts["cursors"] = run_cursors_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"cursors rider failed: {e!r}")
    # tenant rows (deterministic sim, no jax): mixed two-tenant
    # workload — per-tenant qps/p50/p99, SLO burn, and the
    # noisy_neighbor verdict naming the hog
    try:
        parts["tenants"] = run_tenants_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"tenants rider failed: {e!r}")
    # snapshot rows (deterministic sim, no jax): distributed snapshot
    # wall-clock + bytes, the incremental pass's near-zero delta, and
    # restore-through-staged-recovery timing — replay-stable virtual
    # numbers
    try:
        parts["snapshots"] = run_snapshots_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"snapshots rider failed: {e!r}")
    # macro-workload rows (deterministic sim, no jax): the Rally-style
    # open-loop class mix through an injected reroute AND a node
    # bounce — per-class qps/p50/p99, SLO burn, the mid-chaos
    # workload_slo verdict, and the zero-acked-write-loss verdict
    try:
        parts["macro"] = run_macro_cpu()
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        parts.setdefault("skipped", {})["macro"] = repr(e)
        log(f"macro rider failed: {e!r}")
    # ALL CPU-side rows land before the first backend touch
    emit_now()

    # multi-chip serving rows: every row is a SUBPROCESS pinned to its
    # own CPU virtual-device mesh — none of them needs the chip, which
    # this process holds from the raw-kernel section on
    try:
        t0 = time.time()
        parts["multichip"] = run_multichip_serving()
        cpu_rows["multichip_s"] = round(time.time() - t0, 1)
    except Exception as e:  # noqa: BLE001 — the rider must not sink
        log(f"multichip serving section failed: {e!r}")
        parts.setdefault("skipped", {})["multichip_serving"] = repr(e)
    emit_now()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # the device sections measure the chip; a run without one
        # fails instead of banking CPU numbers under device names
        log(f"no TPU found (default device: {dev}); bench needs the chip")
        sys.exit(1)
    kernel_qps, batch_qps, handles = run_tpu_kernel(corpus, queries)
    parts.update(kernel_qps=kernel_qps, batch_qps=batch_qps)
    # device aggregation rows: a handful of reduction launches over the
    # synthetic columns — cheap, and the host halves already banked
    if parts.get("aggs") is not None:
        try:
            run_aggs_device(rng, parts["aggs"])
        except Exception as e:  # noqa: BLE001 — rider must not sink
            log(f"aggs device section failed: {e!r}")
            parts.setdefault("skipped", {})["aggs_device"] = repr(e)
    if os.environ.get("BENCH_SECONDARY", "1") != "0":
        try:
            sec = run_secondary(corpus, queries, rng, handles)
            parts["sec_txt"] = (
                f"; raw-kernel configs: bool+filters "
                f"{sec['bool+filters']:.0f} qps, "
                f"kNN {sec['knn_desc']} {sec['knn']:.0f} qps, "
                f"RRF hybrid {sec['rrf_hybrid']:.0f} qps")
        except Exception as e:
            log(f"secondary configs failed: {e!r}")
    # the sustained run ends in a readback that an earlier runtime
    # answered with a lasting launch slowdown — run it only once every
    # pre-readback raw section is done
    sus_qps, _checksum, degrade = handles["probe"]()
    parts.update(sus_qps=sus_qps, degrade=degrade)
    # release the raw-kernel corpus copies before the REST path re-uploads
    handles.clear()
    # PROVISIONAL emission: if the driver kills the run before the REST
    # section lands, the raw-kernel line (clearly labeled) still parses
    emit_now()

    log(f"REST serving (post-readback slowdown x{degrade:.0f}; "
        f"budget {remaining_budget():.0f}s left)")
    with tempfile.TemporaryDirectory() as tmpdir:
        (rest_qps, p50, p99, rest_recall, warm_recall, avg_batch,
         rest_bool_qps, extra) = run_rest_path(
             corpus, queries, truth, tmpdir, emit_cb=emit_now)
    # free the text corpus before the 8M×768 slab (23 GiB f32 host)
    del corpus, truth
    if os.environ.get("BENCH_KNN8M", "1") == "0":
        parts["knn_txt"] = "; 8M kNN section disabled (BENCH_KNN8M=0)"
    elif remaining_budget() < 1200:
        # the phase needs slab build (~2 min clean host) + an 11.5 GiB
        # upload that rides the FIRST query + the measured rows
        log(f"skipping 8M kNN phase (budget: "
            f"{remaining_budget():.0f}s left < 1200)")
        parts["knn_txt"] = ("; 8M kNN skipped this run (wall-clock "
                            "budget) — see BASELINE.md round-4 "
                            "validated row: 6.3 qps, recall 1.0, "
                            "35x CPU f32 brute force")
    else:
        try:
            parts["knn_txt"] = run_knn_at_scale()
        except Exception as e:
            log(f"kNN-at-scale phase failed: {e!r}")
            parts["knn_txt"] = "; 8M kNN section failed this run"
    emit_now()
    log(f"bench complete in {time.time()-_T_START:.0f}s")


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "--multichip-row":
        # subprocess row harness (run_multichip_serving spawns these)
        _multichip_row(int(sys.argv[2]), sys.argv[3])
        sys.exit(0)
    if len(sys.argv) >= 2 and sys.argv[1] == "--macro-smoke":
        # tier-1 smoke entry: the macro rider at reduced scale (tiny
        # corpus, 2 rounds), rows banked incrementally — a kill still
        # leaves a parseable {"macro": ...} or a typed skipped reason
        payload = {}
        try:
            seed = int(sys.argv[2]) if len(sys.argv) >= 3 else 29
            payload["macro"] = run_macro_cpu(seed=seed, smoke=True)
        except Exception as e:  # noqa: BLE001 — must bank a reason
            payload["skipped"] = {"macro": repr(e)}
        print(json.dumps(payload), flush=True)
        sys.exit(0)
    try:
        main()
    except SystemExit:
        raise
    except BaseException as e:
        import traceback
        print("bench: fatal error — flushing last metric",
              file=sys.stderr, flush=True)
        traceback.print_exc()
        if _LAST_PAYLOAD:
            print(json.dumps(_LAST_PAYLOAD), flush=True)
        os._exit(1)
