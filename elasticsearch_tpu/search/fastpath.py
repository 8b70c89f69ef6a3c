"""Fast-path serving engine: the Python half of the native HTTP front.

The C++ front (native/src/estpu_http.cpp) parses hot `_search` bodies and
queues (term_ids, k, filter_tids) structs; this engine drains them in
COHORTS, launches the exact batched kernel (ops/fastpath.py) on a pool of
overlapping streams, and hands (docid, score) arrays back to C++ for
response serialization. Per-REQUEST Python cost on the hot path is zero —
all Python work is per-cohort (ref: the reference's equivalent seam is the
netty event loop feeding the search threadpool,
Netty4HttpServerTransport.java + ThreadPool.java:117-181; here the
"threadpool" is a handful of launch streams because the TPU, not the host,
does the scoring).

Continuous batching emerges from backpressure: the drain thread only pulls
a new cohort when a stream is free, so under load requests accumulate in
the C++ queue and drain in full-width launches (SURVEY.md §7 hard part 5).

Eligibility (everything else falls back to the full Python path, which
serves the whole DSL): one index explicitly registered or auto-picked —
single shard, single segment, single text postings field, no security
(the fast path performs no authn/authz and must never bypass an enabled
realm chain).
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

from elasticsearch_tpu.ops.device import readback as _readback
from elasticsearch_tpu.ops.plan import unpack_ids as _unpack_ids
from elasticsearch_tpu.telemetry import flightrecorder as _flight
from elasticsearch_tpu.telemetry.tracing import host_span

logger = logging.getLogger("elasticsearch_tpu.fastpath")

MAX_TERMS = 16    # keep in sync with estpu_http.cpp
MAX_FILTERS = 8
Q_BATCH = 32      # cohort width (one compiled Q shape)

# the checkout root: the compile cache's fixed home when
# JAX_COMPILATION_CACHE_DIR is not set (a fixed path, because the path
# is part of the cache key — a directory that moves never hits)
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache so serving-kernel
    shapes compile once per machine, not once per process, and attach
    the shape-bucket key store (telemetry/engine.py
    PersistentKernelCache) that classifies warm first-executions as
    cache hits in ``GET /_kernels``.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    that directory is the cache; otherwise the cache lives at
    ``<checkout>/.jax_cache``. CPU backends skip it: serving-shape
    compiles take seconds there. Safe to call repeatedly."""
    import jax

    from elasticsearch_tpu.telemetry.engine import (PersistentKernelCache,
                                                    TRACKER)
    if jax.default_backend() == "cpu":
        return
    cur = jax.config.jax_compilation_cache_dir
    if not cur:
        cur = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cur)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          0.0)
    # the key store mirrors the executable cache at the TRACKER's
    # shape-bucket granularity, in a subdirectory of the same cache
    if TRACKER.persistent is None:
        TRACKER.attach_persistent(
            PersistentKernelCache(os.path.join(cur, "keys")))


def _mask_args(stack, mask_ids):
    """(masks, mask_ids) for a v2m or v1 launch: (None, None) when no
    query of the cohort reads a filter row, so the kernel skips the
    per-posting mask gather (ops/fastpath.py F_SLOTS)."""
    if mask_ids.any():
        return stack, mask_ids
    return None, None


class FastPathServer:
    # v2m kernel term-slot count (= MAX_TERMS: every instance gets >= 1
    # slot); a bucket's slot width is bucket // N_SLOTS blocks
    N_SLOTS = 16
    # essential-lane block buckets (the θ-cached MaxScore lane)
    ESS_BUCKETS = (256, 1024)

    def __init__(self, node, front, nb_buckets=(1024, 2048, 4096),
                 n_streams: int = 4, max_k: int = 1000,
                 q_batch: int = Q_BATCH, impact_mode: str = "certified",
                 mesh_backend=None):
        self.node = node
        # replica-axis cohort fan-out over a device mesh (opt-in:
        # ESTPU_FASTPATH_MESH=1 resolves the node's MeshSearchBackend
        # at start, or pass one explicitly). The v1 lane's cohorts then
        # shard their Q axis over the mesh with the corpus replicated —
        # same kernel, GSPMD-partitioned, byte-identical per query.
        self.mesh_backend = mesh_backend
        self.front = front           # NativeHttpFront (owns the lib)
        self.lib = front.lib
        # a query that fits a bucket's slot layout runs the v2m kernel
        # (the v1 exact kernel with its sort replaced by the linear-work
        # bitonic merge); slot misfits, θ-lane refires, the truncated
        # lane and the replica mesh run v1 at the largest bucket
        self.nb_buckets = tuple(sorted(nb_buckets))
        # impact-ordered block selection for queries whose block need
        # exceeds the largest lane bucket (previously: bounce to the
        # Python path). "certified": serve the impact-truncated top-k
        # only when the post-launch safe-termination check proves the
        # set exact (totals report relation "gte"); "always": serve
        # every truncated result (approximate, gte); "off": bounce.
        self.impact_mode = impact_mode
        # cohort width: one compiled Q shape; wider cohorts amortize the
        # per-launch floor at the cost of compile time and p50
        self.q_batch = int(q_batch)
        self.n_streams = n_streams
        self.max_k = max_k
        self._running = False
        self._drain_thread: Optional[threading.Thread] = None
        self._pool = None
        self._sem = threading.Semaphore(n_streams)
        # registered state
        self._lock = threading.Lock()
        # serializes whole registration passes (drain tick vs direct
        # calls) — without it two passes double-bump the generation and
        # in-flight requests parsed under the first bounce spuriously
        self._refresh_lock = threading.Lock()
        self._reg: Optional[dict] = None   # {index, field, epoch, dp, ...}
        self._gen = 0
        self._warm = False
        # errors: launches, warm compiles, registrations and drain
        # passes that raised (bounces for an error land here; plain
        # `bounced` also counts by-design handoffs to the Python path)
        # unmasked_cohorts: launches whose cohort carried no filter row,
        # so the kernel read no mask (ops/fastpath.py F_SLOTS);
        # posting_len_cohorts: launches that read each posting's doc
        # length as a block row (the registration's ``block_lens``)
        self.stats = {"cohorts": 0, "unmasked_cohorts": 0,
                      "posting_len_cohorts": 0,
                      "fast_queries": 0, "bounced": 0, "errors": 0,
                      # θ-cache (essential-lane admission) counters —
                      # the engine-stats `caches.theta` surface
                      "theta_hits": 0, "theta_misses": 0,
                      "theta_stores": 0}
        # per-(lane, nb-bucket) dispatch counts + cohort-width histogram
        # — which warmed shapes actually serve traffic (the nb-ladder
        # tradeoff surface: GET /_kernels `serving`, bench `serving`)
        self.dispatch: Dict[str, int] = {}
        self.cohort_hist: Dict[int, int] = {}
        # warm-up accounting (persistent-compile-cache payoff)
        self.warm_seconds = 0.0
        # cohort padding accounting: every launch pads its cohort to a
        # pow2 Q row count — the pad rows are pure device waste, and
        # their share is the profile-subsystem's serving-side padding
        # attribution (the per-request analogue lives in
        # search/batching.py device records)
        self.pad_rows = 0
        self.used_rows = 0
        # the node's flight recorder, ambient on every launch stream, and
        # its registry's histograms: per request, parse stamp to its
        # cohort's launch; per cohort, launch to the end of the readback
        tele = getattr(node, "telemetry", None)
        self.flight = tele.flight if tele is not None else None
        self._queue_wait = self._inflight = None
        if tele is not None:
            self._queue_wait = tele.metrics.histogram("fastpath.queue_wait")
            self._inflight = tele.metrics.histogram("fastpath.inflight")

    def _count_dispatch(self, lane: str, bucket: int, n: int):
        key = f"{lane}:{bucket}"
        self.dispatch[key] = self.dispatch.get(key, 0) + n

    def _count_cohort(self, n: int):
        b = 1
        while b < n:
            b *= 2
        self.cohort_hist[b] = self.cohort_hist.get(b, 0) + 1
        self.pad_rows += b - n
        self.used_rows += n

    def _count_launch(self, masks):
        self.stats["cohorts"] += 1
        if masks is None:
            self.stats["unmasked_cohorts"] += 1
        # every fast-path kernel reads the lengths as block rows
        self.stats["posting_len_cohorts"] += 1

    def serving_stats(self) -> dict:
        """Routing/dispatch telemetry of the serving front: per-lane ×
        nb-bucket dispatch counts, cohort-width histogram, padding
        waste, warm-up seconds, and the truncated-lane counters."""
        from elasticsearch_tpu.ops.fastpath import merge_payload
        padded = self.pad_rows + self.used_rows
        reg = self._reg
        return {
            "dispatch": dict(self.dispatch),
            "cohort_hist": {str(k): v
                            for k, v in sorted(self.cohort_hist.items())},
            "padding_waste_pct": round(
                100.0 * self.pad_rows / padded, 1) if padded else 0.0,
            "warm_seconds": round(self.warm_seconds, 3),
            "nb_buckets": list(self.nb_buckets),
            "ess_buckets": list(self.ESS_BUCKETS),
            "impact_mode": self.impact_mode,
            # what the v2m merge carries on this rail: "contrib" or "lane"
            "merge_payload": merge_payload(),
            # device bytes of the registration's per-posting lengths
            "posting_len_bytes": (int(reg["block_lens"].nbytes)
                                  if reg is not None else 0),
            "counters": {k: v for k, v in self.stats.items()
                         if isinstance(v, (int, float))},
        }

    def engine_cache_stats(self) -> dict:
        """θ-cache counters for the `engine.caches.theta` stats surface
        (rest/api.py nodes_stats): lane-admission hits/misses, stored
        thresholds, and the live entry count of the current
        registration (cleared with the registration on refresh)."""
        reg = self._reg
        theta = reg.get("theta") if reg is not None else None
        return {"hits": self.stats.get("theta_hits", 0),
                "misses": self.stats.get("theta_misses", 0),
                "stores": self.stats.get("theta_stores", 0),
                "entries": len(theta) if theta is not None else 0}

    # ------------------------------------------------------------ lifecycle
    def start(self):
        from concurrent.futures import ThreadPoolExecutor
        enable_compile_cache()
        if self.mesh_backend is None \
                and os.environ.get("ESTPU_FASTPATH_MESH") == "1":
            svc = getattr(self.node, "search_service", None)
            self.mesh_backend = getattr(svc, "mesh_executor", None)
        self._pool = ThreadPoolExecutor(max_workers=self.n_streams,
                                        thread_name_prefix="fast-stream")
        self._running = True
        self._drain_thread = threading.Thread(
            target=self._drain_loop, name="fastpath-drain", daemon=True)
        self._drain_thread.start()

    def stop(self) -> bool:
        """Returns True when every thread exited (the front only frees
        its process-wide slot on a clean stop)."""
        self._running = False
        clean = True
        if self._drain_thread is not None:
            self._drain_thread.join(timeout=3.0)
            clean = not self._drain_thread.is_alive()
            self._drain_thread = None
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
        return clean

    # --------------------------------------------------------- registration
    def _eligible(self) -> Optional[Tuple[str, object]]:
        """(index_name, engine) for the best fast-servable index, or None.
        The fast path must never bypass an enabled realm chain."""
        sec = getattr(self.node, "security_service", None)
        if sec is not None and sec.enabled:
            return None
        from elasticsearch_tpu.index.mapper import TextFieldType
        best = None
        for name, idx in list(self.node.indices_service.indices.items()):
            if getattr(idx, "is_closed", False) or len(idx.shards) != 1:
                continue
            eng = idx.shards[0]
            segs = eng.segments
            if len(segs) != 1:
                continue
            seg = segs[0]
            if not seg.postings or not bool(np.all(seg.live)):
                continue
            # exactly one TEXT field with the standard analyzer (the C++
            # tokenizer mirrors it — estpu_tokenize.h); keyword subfields
            # and other fields don't interfere: a fast parse only matches
            # the registered field name
            text_fields = []
            for f in seg.postings:
                ft = idx.mapper.field_type(f)
                if isinstance(ft, TextFieldType):
                    if ft.search_analyzer_name not in ("standard",
                                                      "default"):
                        text_fields = []
                        break
                    text_fields.append(f)
            if len(text_fields) != 1:
                continue
            if best is None or seg.n_docs > best[3]:
                best = (name, idx, text_fields[0], seg.n_docs)
        return (best[0], best[1], best[2]) if best else None

    def refresh_registration(self):
        """(Re)register the fast index if its segment set changed. Called
        periodically from the drain loop — registration is C++-visible
        only AFTER the kernel shapes are warm, so a cold node never
        stalls a request on a 30s XLA compile."""
        with self._refresh_lock:
            self._refresh_registration_locked()

    def _refresh_registration_locked(self):
        pick = self._eligible()
        if pick is None:
            with self._lock:
                if self._reg is not None:
                    self.lib.es_fast_unregister(self.front.h)
                    self._reg = None
            return
        name, idx, field = pick
        eng = idx.shards[0]
        seg = eng.segments[0]
        with self._lock:
            if (self._reg is not None and self._reg["index"] == name
                    and self._reg["segment"] is seg
                    and bool(np.all(seg.live))):
                return
        pf = seg.postings[field]
        dev = idx.device_cache.get(seg)
        dp = dev.postings[field]
        # register-time enforcement of the float-pack id invariant: the
        # C++ front's readback lanes carry docids as float32 casts
        from elasticsearch_tpu.ops.plan import check_packed_id_limit
        check_packed_id_limit(dev.n_docs_padded,
                              f"fastpath register [{name}]")
        self._gen += 1
        reg = {
            "index": name, "field": field, "segment": seg,
            "gen": self._gen, "dev": dev, "dp": dp,
            "k1": idx.k1, "b": idx.b,
            "idf": None, "nb": None,
            "filter_live": {},   # filt tuple -> device (live AND filters)
            "ess_bad": set(),    # query keys whose certificate failed
        }
        # per-term idf + block counts as vectors (per-cohort selection
        # assembly is vectorized numpy, no per-term Python)
        df = dp.doc_freq.astype(np.float64)
        n = float(pf.doc_count)
        reg["idf"] = np.log1p((n - df + 0.5) / (df + 0.5)).astype(
            self._weight_dtype())
        reg["nb"] = dp.term_block_count.astype(np.int64)
        reg["starts"] = dp.term_block_start.astype(np.int64)
        # --- θ-cached exact-MaxScore state (ops/fastpath.py essential
        # lane): per-term MAX possible contribution (the MaxScore upper
        # bound, from the block-max metadata), flat posting ranges for
        # the patch phase's binary search, and the θ/total cache —
        # valid for this registration's immutable segment
        from elasticsearch_tpu.index.segment import BLOCK_SIZE
        from elasticsearch_tpu.ops.plan import build_term_impacts
        k1, b = reg["k1"], reg["b"]
        starts32 = reg["starts"]
        nbv = reg["nb"]
        # per-block BM25 upper bounds + per-term impact ordering
        # (ops/plan.py): feeds BOTH the θ-lane's per-term max
        # contribution AND the budgeted impact selection of oversize
        # queries (the Lucene impact-ordered-postings analogue)
        impacts = build_term_impacts(
            starts32, nbv, pf.block_max_tf, pf.block_min_len,
            reg["idf"].astype(np.float64), float(dp.avg_len), k1, b)
        reg["impacts"] = impacts
        maxc = np.zeros(len(pf.terms), np.float64)
        nz = nbv > 0
        if nz.any():
            # a term's max contribution = its highest-impact block's
            # bound (ub_desc is impact-DESCENDING within each term)
            maxc[nz] = impacts.ub_desc[starts32[nz]]
        reg["maxc"] = maxc.astype(np.float32)
        reg["post_start"] = (starts32 * BLOCK_SIZE).astype(np.int32)
        reg["post_len"] = dp.doc_freq.astype(np.int32)
        reg["flat_docids"] = dp.block_docids.reshape(-1)
        reg["flat_tfs"] = dp.block_tfs.reshape(-1)
        # each posting's document length as a block row beside its tf,
        # the kernels' one read of lengths (HBM-accounted by the
        # device segment: its `posting_lens` slab class)
        reg["block_lens"] = dev.posting_lens(field)
        reg["theta"] = {}    # (tids, filt, k) -> (θ, exact_total)
        # replica mesh for this registration's v1 cohorts: bound once so
        # warm + serve share ONE (sharded) compile signature per bucket
        reg["rmesh"] = (self.mesh_backend.replica_mesh_for(self.q_batch)
                        if self.mesh_backend is not None else None)
        t0 = time.time()
        self._warm_shapes(reg)
        logger.info("warm shapes %.1fs", time.time() - t0)
        # only now does C++ start routing /{index}/_search to the queue
        terms_blob = b"".join(t.encode("utf-8") for t in pf.terms)
        lens = np.fromiter((len(t.encode("utf-8")) for t in pf.terms),
                           np.int64, len(pf.terms))
        offs = np.zeros(len(pf.terms) + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        ids = seg.stored.ids
        id_lens = np.fromiter((len(s.encode("utf-8")) for s in ids),
                              np.int64, len(ids))
        id_offs = np.zeros(len(ids) + 1, np.int64)
        np.cumsum(id_lens, out=id_offs[1:])
        ids_blob = b"".join(s.encode("utf-8") for s in ids)
        rc = self.lib.es_fast_register(
            self.front.h, reg["gen"], reg["index"].encode(),
            field.encode(),
            terms_blob, offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(pf.terms), ids_blob,
            id_offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(ids), 10, self.max_k)
        if rc == 0:
            # keep blob buffers alive until C++ copies... es_fast_register
            # copies synchronously, so locals may die here
            with self._lock:
                self._reg = reg
            logger.info("fastpath registered index=%s field=%s terms=%d",
                        name, field, len(pf.terms))

    def _warm_shapes(self, reg):
        """Compile every kernel program the router can reach up front
        (the 69.7s first-query stall of round 2 — VERDICT item 2 — was
        lazy compilation on the first request): v2m masked and unmasked
        at every nb bucket, v1 masked and unmasked at the largest bucket
        (slot misfits, θ-lane refires and the truncated lane run
        there), and the essential lane at every ess bucket. Compiles
        run CONCURRENTLY (XLA parallelizes across shapes — 4 serving
        shapes compile in the wall time of the slowest one) and land in
        the persistent compile cache, so a warm machine pays seconds,
        not minutes."""
        from functools import partial

        import jax.numpy as jnp

        from elasticsearch_tpu.ops.fastpath import (
            F_SLOTS, NE_SLOTS, bm25_essential_topk_batch,
            bm25_topk_total_batch, bm25_topk_total_merge_batch)
        dp, dev = reg["dp"], reg["dev"]
        masks = jnp.stack([dev.live] * F_SLOTS)
        # the persistent stack's first state (_resolve_mask_rows): row 0
        # is the live column and stays so; filter sets take rows 1..
        reg["plain_masks"] = masks
        mask_ids = np.zeros(self.q_batch, np.int32)
        wd = self._weight_dtype()

        def warm_v2m(nb, plain=False):
            if not self._running:
                return "skipped (stopping)"
            sel = np.full((self.q_batch, nb), dp.zero_block, np.int32)
            ws = np.zeros((self.q_batch, nb), wd)
            mk, mi = (None, None) if plain else (masks, mask_ids)
            bm25_topk_total_merge_batch(
                dp.block_docids, dp.block_tfs, sel, ws,
                reg["block_lens"], mk, mi, wd(dp.avg_len),
                self.N_SLOTS, reg["k1"], reg["b"],
                self.max_k).block_until_ready()
            return f"v2m NB={nb}" + (" unmasked" if plain else "")

        def warm_v1(nb, plain=False):
            if not self._running:
                return "skipped (stopping)"
            sel = np.full((self.q_batch, nb), dp.zero_block, np.int32)
            ws = np.zeros((self.q_batch, nb), wd)
            bd, bt, s_, w_, bl, mk, mi = self._v1_inputs(
                reg, sel, ws, *((None, None) if plain
                                else (masks, mask_ids)))
            bm25_topk_total_batch(
                bd, bt, s_, w_, bl, mk, mi, wd(dp.avg_len), reg["k1"],
                reg["b"], self.max_k).block_until_ready()
            return f"v1 NB={nb}" + (" unmasked" if plain else "") + (
                " (mesh)" if reg.get("rmesh") is not None else "")

        def warm_ess(nb):
            if not self._running:
                return "skipped (stopping)"
            sel = np.full((self.q_batch, nb), dp.zero_block, np.int32)
            ws = np.zeros((self.q_batch, nb), wd)
            bm25_essential_topk_batch(
                dp.block_docids, dp.block_tfs, reg["flat_docids"],
                reg["flat_tfs"], sel, ws, reg["block_lens"], dp.doc_lens,
                masks, mask_ids,
                np.zeros((self.q_batch, NE_SLOTS), np.int32),
                np.zeros((self.q_batch, NE_SLOTS), np.int32),
                np.zeros((self.q_batch, NE_SLOTS), wd),
                np.zeros(self.q_batch, wd),
                wd(dp.avg_len), reg["k1"], reg["b"],
                self.max_k).block_until_ready()
            return f"ess NB={nb}"

        # v2m and v1 each warm an unmasked program beside the masked
        # one: a cohort with no filter row launches without the mask
        # (_mask_args), one with a filter row with it
        jobs = []
        for nb in self.nb_buckets:
            jobs.append((warm_v2m, nb))
            jobs.append((partial(warm_v2m, plain=True), nb))
        jobs.append((warm_v1, self.nb_buckets[-1]))
        jobs.append((partial(warm_v1, plain=True), self.nb_buckets[-1]))
        for nb in self.ESS_BUCKETS:
            jobs.append((warm_ess, nb))
        from concurrent.futures import ThreadPoolExecutor

        # 4 workers: XLA's internal compile parallelism saturates the
        # host around there, and a stop() during warm only has to drain
        # 4 in-flight compiles (queued jobs see _running and skip)
        t0 = time.time()
        try:
            with ThreadPoolExecutor(
                    max_workers=min(4, max(1, len(jobs)))) as ex:
                futs = [ex.submit(fn, nb) for fn, nb in jobs
                        if self._running]
                for f in futs:
                    try:
                        logger.info("fastpath warm %s (t+%.1fs)",
                                    f.result(), time.time() - t0)
                    except Exception:
                        logger.exception("fastpath warm compile failed")
                        self.stats["errors"] += 1
        except RuntimeError:
            # interpreter shutdown while the drain thread was still
            # registering — nothing to warm for, just exit quietly
            if self._running:
                raise
        finally:
            # warm-ladder wall time: with the persistent compile cache
            # warm, this drops from minutes (cold XLA compiles) to the
            # executable-deserialize cost — `serving.warm_seconds`
            self.warm_seconds += time.time() - t0

    # --------------------------------------------------------------- drain
    def _drain_loop(self):
        c = ctypes
        # drain DEEP: the router groups by bucket class before chunking
        # to q_batch, so a shallow poll fragments cohorts across the
        # bucket ladder (r5 full bench averaged 19.7/32 at 2x); deep
        # polls give every bucket group a shot at full cohorts
        max_n = 8 * self.q_batch
        tokens = (c.c_uint64 * max_n)()
        gens = (c.c_int32 * max_n)()
        ks = (c.c_int32 * max_n)()
        nterms = (c.c_int32 * max_n)()
        tids = (c.c_int32 * (max_n * MAX_TERMS))()
        nfilt = (c.c_int32 * max_n)()
        ftids = (c.c_int32 * (max_n * MAX_FILTERS))()
        stamps = (c.c_int64 * max_n)()
        last_reg_check = 0.0
        while self._running:
            now = time.time()
            if now - last_reg_check > 1.0:
                last_reg_check = now
                try:
                    self.refresh_registration()
                except Exception:
                    logger.exception("fastpath registration failed")
                    self.stats["errors"] += 1
            h = self.front.h
            if h is None:
                break
            n = self.lib.es_fast_poll(h, tokens, gens, ks, nterms, tids,
                                      nfilt, ftids, stamps, max_n, 50)
            if n == 0:
                continue
            try:
                with host_span("fastpath.route"):
                    self._route_cohort(h, n, tokens, gens, ks, nterms,
                                       tids, nfilt, ftids, stamps)
            except Exception:
                # the drain thread must NEVER die: C++ keeps routing to
                # the fast queue and every client would hang
                logger.exception("fastpath drain error; bouncing batch")
                self.stats["errors"] += 1
                for i in range(n):
                    try:
                        self.lib.es_fast_bounce(h, tokens[i])
                    except Exception:
                        pass

    def _route_cohort(self, h, n, tokens, gens, ks, nterms, tids, nfilt,
                      ftids, stamps):
        # token -> when the front queued it (monotonic ns): each
        # request's queue wait and `took` start there
        arrived = {tokens[i]: stamps[i] for i in range(n)}
        reqs = []
        for i in range(n):
            reqs.append((
                tokens[i], gens[i], ks[i],
                list(tids[i * MAX_TERMS:
                          i * MAX_TERMS + nterms[i]]),
                tuple(sorted(ftids[i * MAX_FILTERS:
                                   i * MAX_FILTERS + nfilt[i]])),
            ))
        with self._lock:
            reg = self._reg
        if reg is None:
            for tok, *_ in reqs:
                self.lib.es_fast_bounce(h, tok)
            return
        # group by NB bucket only — filter sets ride per-query mask
        # rows inside one launch (ops/fastpath.py F_SLOTS). Queries with
        # a cached θ route to the essential lane: a MUCH smaller sort
        # plus per-candidate patching (exact MaxScore). Everything else
        # rides the v2m merge kernel when it fits the slot layout;
        # slot misfits run the v1 full kernel at the largest bucket.
        v2_by_bucket: Dict[int, list] = {}
        ess_by_bucket: Dict[int, list] = {}
        misfits: list = []
        trunc_items: list = []
        for tok, gen, k, term_ids, filt in reqs:
            if gen != reg["gen"]:
                # parsed under an older term dictionary (segment changed
                # between parse and drain) — term ids are meaningless now
                self.stats["bounced"] += 1
                self.lib.es_fast_bounce(h, tok)
                continue
            nb_need = int(reg["nb"][[t for t in term_ids
                                     if t >= 0]].sum()) \
                if any(t >= 0 for t in term_ids) else 0
            if nb_need > self.nb_buckets[-1] or not term_ids:
                # empty query: cheap immediate answer, no device work
                if not term_ids or all(t < 0 for t in term_ids):
                    self._respond_empty(tok, reg)
                    continue
                # oversize selection: impact-ordered truncation to the
                # largest bucket (the blocks with the highest score
                # upper bounds enter the budget; the excluded tail's
                # residual bound rides along for the post-launch
                # safe-termination check) instead of the old
                # unconditional bounce to the slow Python path. In
                # "certified" mode a k == max_k query can never certify
                # (the check needs the (k+1)-th observed score and the
                # kernel returns exactly max_k) — bounce immediately
                # rather than pay a doomed launch.
                attempt = (self.impact_mode == "always"
                           or (self.impact_mode == "certified"
                               and k < self.max_k
                               and not self._trunc_hopeless(reg)))
                trunc = self._impact_truncate(reg, term_ids) \
                    if attempt else None
                if trunc is None:
                    self.stats["bounced"] += 1
                    self.lib.es_fast_bounce(h, tok)
                else:
                    trunc_items.append((tok, k, term_ids, filt, trunc))
                continue
            ess = self._essential_split(reg, k, term_ids, filt,
                                        nb_need)
            if ess is not None:
                ess_by_bucket.setdefault(ess[0], []).append(
                    (tok, k, term_ids, filt, ess))
                continue
            b2 = self._v2_bucket(reg, term_ids)
            if b2 is not None:
                v2_by_bucket.setdefault(b2, []).append(
                    (tok, k, term_ids, filt))
                continue
            misfits.append((tok, k, term_ids, filt))
        # adaptive merge-up: a nearly-empty bucket group pays the full
        # per-launch floor for a handful of queries — fold small
        # groups into the next bigger bucket (padding costs device time
        # only when the group was too small to amortize the floor anyway)
        def merge_up(groups):
            merged: Dict[int, list] = {}
            carry: list = []
            for bucket in sorted(groups):
                cur = carry + groups[bucket]
                if len(cur) < self.q_batch // 2 \
                        and bucket != self.nb_buckets[-1] \
                        and any(b > bucket for b in groups):
                    carry = cur
                    continue
                merged.setdefault(bucket, []).extend(cur)
                carry = []
            # the max bucket can never carry (the carry condition
            # requires a bigger bucket to exist)
            assert not carry
            return merged

        # the θ-warm lane fragments worst without folding: the ess
        # ladder splits the SAME query stream three ways, and a 10-deep
        # cohort pays the identical launch floor a 32-deep one does
        # (r5 full-bench measured avg cohort 16.3/32 before this fold)
        for bucket, items in merge_up(ess_by_bucket).items():
            for chunk in self._chunk_by_slots(items):
                self._submit(self._launch_essential, "ess", reg, bucket,
                             chunk, arrived)
        for bucket, items in merge_up(v2_by_bucket).items():
            for chunk in self._chunk_by_slots(items):
                self._submit(self._launch_group_v2, "v2m", reg, bucket,
                             chunk, arrived)
        # slot misfits and the truncated lane run on the only warm v1
        # shape, the largest bucket (routing to a smaller one would
        # lazy-compile at serve time — the round-2 stall; the v1 kernel
        # is order-agnostic, so neither needs a slot layout)
        bucket = self.nb_buckets[-1]
        for chunk in self._chunk_by_slots(misfits):
            self._submit(self._launch_group, "v1", reg, bucket, chunk,
                         arrived)
        for chunk in self._chunk_by_slots(trunc_items):
            self._submit(self._launch_truncated, "trunc", reg, bucket,
                         chunk, arrived)

    def _submit(self, launch, lane, reg, bucket, chunk, arrived):
        """Hand one cohort to a launch stream: resolve its filter rows,
        count it, and wait for a free stream — backpressure: requests
        keep queueing in C++ meanwhile and drain in wider cohorts."""
        stack, rows = self._resolve_mask_rows(reg, {it[3] for it in chunk})
        self._count_dispatch(lane, bucket, len(chunk))
        self._count_cohort(len(chunk))
        with host_span("fastpath.stream_wait"):
            self._sem.acquire()
        self._pool.submit(self._stream_task, launch, reg, bucket, chunk,
                          arrived, stack, rows)

    def _stream_task(self, launch, reg, bucket, items, arrived, stack,
                     rows):
        """One launch on a stream, with the node's flight recorder
        ambient; a launch that raises bounces its cohort."""
        try:
            with _flight.activate(self.flight):
                launch(reg, bucket, items, arrived, stack, rows)
        except Exception:
            self._launch_failed(items)
        finally:
            self._sem.release()

    def _launch_cohort(self, site, items, arrived, first, kernel, *args):
        """Launch a cohort's kernel and read its packed result back: ONE
        device→host sync per cohort, through the tracked funnel. On a
        cohort's ``first`` launch (a refire's riders were counted at
        theirs) each rider's wait from its parse stamp to this launch
        feeds ``fastpath.queue_wait``; the launch to the end of the
        readback feeds ``fastpath.inflight``. Returns the host array and
        the monotonic ns at the end of the readback."""
        t_launch = time.monotonic_ns()
        waits = [t_launch - arrived[it[0]] for it in items]
        if first and self._queue_wait is not None:
            for w in waits:
                self._queue_wait.observe(w / 1e6)
        with _flight.annotate_launch(len(items), self.q_batch,
                                     queue_wait_ns=max(waits)):
            packed = kernel(*args)
        out = _readback(site, packed)
        t_done = time.monotonic_ns()
        if self._inflight is not None:
            self._inflight.observe((t_done - t_launch) / 1e6)
        return out, t_done

    def _v2_bucket(self, reg, term_ids) -> Optional[int]:
        """Smallest bucket whose slot layout fits: each term INSTANCE
        starts on a slot boundary (slot = bucket // N_SLOTS blocks), so
        the fit condition is sum(ceil(blocks_t / slot)) <= N_SLOTS."""
        nbs = reg["nb"]
        cnts = [int(nbs[t]) for t in term_ids if t >= 0]
        if not cnts or len(cnts) > self.N_SLOTS:
            return None
        for bucket in self.nb_buckets:
            slot = bucket // self.N_SLOTS
            if slot == 0:
                continue
            if sum(-(-c // slot) for c in cnts) <= self.N_SLOTS:
                return bucket
        return None

    def _launch_group_v2(self, reg, bucket, items, arrived, stack, rows):
        from elasticsearch_tpu.ops.fastpath import (
            bm25_topk_total_merge_batch)
        dp = reg["dp"]
        slot = bucket // self.N_SLOTS
        q = len(items)
        with host_span("fastpath.pack"):
            sel = np.full((self.q_batch, bucket), dp.zero_block, np.int32)
            ws = np.zeros((self.q_batch, bucket), self._weight_dtype())
            mask_ids = np.zeros(self.q_batch, np.int32)
            starts, nbs, idf = reg["starts"], reg["nb"], reg["idf"]
            no_match: list = []
            for qi, (tok, k, term_ids, filt) in enumerate(items):
                pos = 0
                for t in term_ids:
                    if t < 0:
                        continue
                    cnt = int(nbs[t])
                    s = int(starts[t])
                    sel[qi, pos:pos + cnt] = np.arange(s, s + cnt,
                                                       dtype=np.int32)
                    ws[qi, pos:pos + cnt] = idf[t]
                    pos += -(-cnt // slot) * slot
                if filt:
                    row = rows.get(filt)
                    if row is None:          # unknown filter term ⇒ no hits
                        no_match.append(tok)
                        sel[qi, :] = dp.zero_block
                        ws[qi, :] = 0.0
                        continue
                    mask_ids[qi] = row
        k_static = self.max_k
        masks, mids = _mask_args(stack, mask_ids)
        out, t_done = self._launch_cohort(
            "search.fastpath.v2_cohort", items, arrived, True,
            bm25_topk_total_merge_batch, dp.block_docids, dp.block_tfs,
            sel, ws, reg["block_lens"], masks, mids,
            self._weight_dtype()(dp.avg_len), self.N_SLOTS, reg["k1"],
            reg["b"], k_static)
        self._count_launch(masks)
        self.stats["v2_queries"] = self.stats.get("v2_queries", 0) + q
        no_match_set = set(no_match)
        with host_span("fastpath.respond"):
            for qi, (tok, k, term_ids, filt) in enumerate(items):
                if tok in no_match_set:
                    self._respond_empty(tok, reg)
                    continue
                vals = out[qi, :k_static]
                ids = _unpack_ids(out[qi, k_static:2 * k_static])
                total = int(out[qi, 2 * k_static:][0])
                nhit = int(min(k, np.isfinite(vals).sum()))
                v = vals[:nhit]
                d = ids[:nhit]
                # v2m's device top_k tie order is arbitrary (v1
                # contract): re-sort (score desc, docid asc) host-side
                order = np.lexsort((d, -v))
                took_ms = (t_done - arrived[tok]) // 1_000_000
                self._respond_hits(reg, tok,
                                   np.ascontiguousarray(v[order]),
                                   np.ascontiguousarray(d[order]),
                                   k, total, took_ms, term_ids, filt)
        self.stats["fast_queries"] += q

    def _respond_empty(self, tok, reg):
        empty = np.zeros(0, np.int32)
        h = self.front.h
        if h is None:
            return
        self.lib.es_fast_respond(
            h, tok, reg["index"].encode(),
            empty.ctypes.data_as(ctypes.c_void_p),
            empty.ctypes.data_as(ctypes.c_void_p), 0, 0, b"eq", 0)

    # -------------------------------------------------------------- launch
    def _launch_failed(self, items):
        """A launch raised: count it (``stats["errors"]`` — a chip run
        that served any query this way did not really serve it) and
        bounce the cohort to the Python path."""
        logger.exception("fastpath launch failed; bouncing cohort")
        self.stats["errors"] += 1
        h = self.front.h
        for tok, *_ in items:
            try:
                if h is not None:
                    self.lib.es_fast_bounce(h, tok)
            except Exception:
                pass

    # ------------------------------------------------- impact truncation
    # adaptive back-off: a registration whose certificate NEVER fires
    # (boundary-dense corpora refuse nearly everything the doom check
    # lets through) stops paying uncertifiable launches and bounces
    # directly until the next registration resets the counters
    TRUNC_BACKOFF_ATTEMPTS = 32

    def _trunc_hopeless(self, reg) -> bool:
        if (reg.get("trunc_attempts", 0) >= self.TRUNC_BACKOFF_ATTEMPTS
                and reg.get("trunc_certified", 0) == 0):
            self.stats["trunc_backoff"] = \
                self.stats.get("trunc_backoff", 0) + 1
            return True
        return False

    def _impact_truncate(self, reg, term_ids):
        """Budgeted impact-ordered selection for a query whose full
        block need exceeds the largest bucket. Returns (known_terms,
        per-term block arrays, miss_bound) or None when the query has
        no known terms (the caller bounces)."""
        known = [t for t in term_ids if t >= 0]
        if not known or reg.get("impacts") is None:
            return None
        from elasticsearch_tpu.ops.plan import select_blocks_impact
        per_term, miss = select_blocks_impact(
            known, self.nb_buckets[-1], reg["starts"], reg["nb"],
            reg["impacts"])
        if self.impact_mode == "certified" and miss > 0.0:
            # pre-launch doom check: certification needs miss < kth,
            # and no observed score can exceed Σ per-kept-term best
            # SELECTED bound — which is maxc for every term that kept
            # ≥1 block (greedy selection keeps a term's top-impact
            # blocks first). A selection that provably can't certify
            # bounces NOW instead of paying a doomed launch+readback
            # (the heavily-truncated multi-term case).
            obs_max = sum(float(reg["maxc"][t])
                          for t, blocks in zip(known, per_term)
                          if len(blocks))
            if miss >= obs_max:
                self.stats["trunc_doomed"] = \
                    self.stats.get("trunc_doomed", 0) + 1
                return None
        return known, per_term, miss

    def _launch_truncated(self, reg, bucket, items, arrived, stack, rows):
        """Impact-truncated cohort on the exact v1 kernel: scores are
        exact over the SELECTED blocks, so every observed score is a
        lower bound of the true score and no doc can gain more than the
        query's ``miss_bound`` (ops/plan.select_blocks_impact). The
        post-launch safe-termination check proves (when it can) that
        the observed top-k SET is the true top-k; totals always report
        relation "gte" (excluded blocks may hold unseen matches)."""
        from elasticsearch_tpu.ops.fastpath import bm25_topk_total_batch
        from elasticsearch_tpu.ops.plan import impact_safe_termination
        dp = reg["dp"]
        with host_span("fastpath.pack"):
            sel = np.full((self.q_batch, bucket), dp.zero_block, np.int32)
            ws = np.zeros((self.q_batch, bucket), self._weight_dtype())
            mask_ids = np.zeros(self.q_batch, np.int32)
            idf = reg["idf"]
            no_match: list = []
            for qi, (tok, k, term_ids, filt, trunc) in enumerate(items):
                known, per_term, _miss = trunc
                pos = 0
                for t, blocks in zip(known, per_term):
                    cnt = len(blocks)
                    sel[qi, pos:pos + cnt] = blocks
                    ws[qi, pos:pos + cnt] = idf[t]
                    pos += cnt
                if filt:
                    row = rows.get(filt)
                    if row is None:          # unknown filter term ⇒ no hits
                        no_match.append(tok)
                        sel[qi, :] = dp.zero_block
                        ws[qi, :] = 0.0
                        continue
                    mask_ids[qi] = row
        k_static = self.max_k
        bd, bt, sel_m, ws_m, bl, mk, mi = self._v1_inputs(
            reg, sel, ws, *_mask_args(stack, mask_ids))
        out, t_done = self._launch_cohort(
            "search.fastpath.truncated_cohort", items, arrived, True,
            bm25_topk_total_batch, bd, bt, sel_m, ws_m, bl, mk, mi,
            self._weight_dtype()(dp.avg_len), reg["k1"], reg["b"],
            k_static)
        self._count_launch(mk)
        if self._mesh_active(reg):
            self.stats["mesh_cohorts"] = \
                self.stats.get("mesh_cohorts", 0) + 1
            self.mesh_backend._dispatch("replica", len(items))
        h = self.front.h
        idx_b = reg["index"].encode()
        no_match_set = set(no_match)
        served = 0
        with host_span("fastpath.respond"):
            for qi, (tok, k, term_ids, filt, trunc) in enumerate(items):
                if tok in no_match_set:
                    self._respond_empty(tok, reg)
                    served += 1
                    continue
                miss = float(trunc[2])
                vals = out[qi, :k_static]
                ids = _unpack_ids(out[qi, k_static:2 * k_static])
                total = int(out[qi, 2 * k_static:][0])
                nhit = int(min(k, np.isfinite(vals).sum()))
                certified = False
                if nhit >= k:
                    kth = float(vals[k - 1])
                    if k < k_static:
                        # the (k+1)-th observed score bounds the best
                        # excluded candidate
                        nxt = (float(vals[k])
                               if np.isfinite(vals[k]) else 0.0)
                    elif total <= k:
                        # every matching doc is in the result: only
                        # entirely-unseen docs (observed 0) could displace
                        nxt = 0.0
                    else:
                        nxt = None   # k == kernel k: no (k+1)-th to bound by
                    certified = (nxt is not None
                                 and impact_safe_termination(kth, nxt, miss))
                # per-registration certificate track record (feeds the
                # _trunc_hopeless back-off; refresh resets with the reg)
                reg["trunc_attempts"] = reg.get("trunc_attempts", 0) + 1
                if certified:
                    reg["trunc_certified"] = \
                        reg.get("trunc_certified", 0) + 1
                if not certified and self.impact_mode != "always":
                    # can't prove the truncated set exact — the full Python
                    # path serves it (the pre-impact behavior for oversize)
                    self.stats["trunc_refused"] = \
                        self.stats.get("trunc_refused", 0) + 1
                    self.stats["bounced"] += 1
                    if h is not None:
                        self.lib.es_fast_bounce(h, tok)
                    continue
                v = vals[:nhit]
                d = ids[:nhit]
                order = np.lexsort((d, -v))
                v = np.ascontiguousarray(v[order])
                d = np.ascontiguousarray(d[order])
                self.stats["trunc_served"] = \
                    self.stats.get("trunc_served", 0) + 1
                if certified:
                    self.stats["trunc_certified"] = \
                        self.stats.get("trunc_certified", 0) + 1
                served += 1
                if h is None:
                    return
                self.lib.es_fast_respond(
                    h, tok, idx_b,
                    d.ctypes.data_as(ctypes.c_void_p),
                    v.ctypes.data_as(ctypes.c_void_p),
                    nhit, total, b"gte",
                    (t_done - arrived[tok]) // 1_000_000)
        self.stats["fast_queries"] += served

    # binary-search depth contract of the patch kernel (ops/fastpath)
    NE_MAX_LEN = 1 << 21

    @staticmethod
    def _weight_dtype():
        """Weights/avg ride the ranking dtype: under x64 the kernels
        rank in float64, and f32-ROUNDED idf weights would reintroduce
        the ~2^-24 boundary noise the f64 rail removes."""
        import jax
        return np.float64 if jax.config.jax_enable_x64 else np.float32

    def _chunk_by_slots(self, items):
        """Split a launch class into cohorts bounded by the cohort
        width (Q_BATCH) AND the mask-slot budget (≤ F_SLOTS-1 distinct
        filter sets per launch; row 0 is the plain live mask). Item
        layout: (tok, k, term_ids, filt, ...)."""
        from elasticsearch_tpu.ops.fastpath import F_SLOTS
        chunk: list = []
        filts: set = set()
        for item in items:
            f = item[3]
            nf = filts | ({f} if f else set())
            if chunk and (len(chunk) >= self.q_batch
                          or len(nf) > F_SLOTS - 1):
                yield chunk
                chunk = []
                filts = set()
                nf = {f} if f else set()
            chunk.append(item)
            filts = nf
        if chunk:
            yield chunk

    def _essential_split(self, reg, k, term_ids, filt,
                         nb_full=None):
        """(ess_bucket, ess_terms, ne_terms, ne_bound, θ, total) when a
        cached θ licenses the essential lane for this exact query, else
        None. Term INSTANCES partition (duplicates keep their own
        slot — a doubled term doubles both its contribution and its
        bound)."""
        from elasticsearch_tpu.ops.fastpath import NE_SLOTS
        if k != self.max_k:
            return None
        key = (tuple(term_ids), filt, k)
        hit = reg["theta"].get(key)
        if hit is None:
            self.stats["theta_misses"] = \
                self.stats.get("theta_misses", 0) + 1
            return None
        self.stats["theta_hits"] = self.stats.get("theta_hits", 0) + 1
        theta, total = hit
        if key in reg["ess_bad"]:
            # certificate already failed once for this query — the
            # essential attempt + refire would only double the work
            return None
        known = [t for t in term_ids if t >= 0]
        if len(known) < 2:
            return None
        maxc = reg["maxc"]
        inst = sorted(known, key=lambda t: float(maxc[t]))
        # a FRACTION of θ, not all of it: correctness only needs
        # Σ maxc_ne < θ (docs outside every essential list can't reach
        # the kth), and the CERTIFICATE needs ess_(C+1) + Σ maxc_ne <
        # kth. With the candidate budget at CAND=16K the overflow term
        # is usually -inf and kth == θ for a repeat query, so 0.9
        # keeps a real margin while TRIPLING lane eligibility vs the
        # old 0.5 (offline model on the bench mix: 41 -> 119 of 256
        # queries, mean essential union 2107 -> 663 blocks); failed
        # certificates memoize into ess_bad and never retry
        theta_safe = float(theta) * 0.9
        ne: list = []
        bound = 0.0
        ess: list = []
        for t in inst:
            mc = float(maxc[t])
            # a term can ride an NE slot only if the patch phase can
            # recover its per-candidate tf: a binary-searchable flat
            # range (STRICT 2^21: the patch kernel's 21 halving steps
            # only fully resolve ranges < 2^21)
            patchable = int(reg["post_len"][t]) < self.NE_MAX_LEN
            if (len(ne) < NE_SLOTS and len(inst) - len(ne) > 1
                    and bound + mc < theta_safe and patchable):
                ne.append(t)
                bound += mc
            else:
                ess.append(t)
        if not ne:
            return None
        # the certificate only closes trivially when EVERY matching doc
        # of the essential union is a candidate (overflow bound -inf);
        # past the candidate budget the bound engages and, at 0.9·θ
        # admission, almost always refires (r5 run: 78 of 100 lane
        # launches refired before this gate). Union size is bounded by
        # Σ df over essential terms.
        from elasticsearch_tpu.ops.fastpath import CAND as _CAND
        if int(reg["post_len"][ess].sum()) > int(0.9 * _CAND):
            return None
        nb_ess = int(reg["nb"][ess].sum())
        if nb_full is None:
            nb_full = int(reg["nb"][known].sum())
        if nb_ess * 5 > nb_full * 4:
            # under a 1.25x reduction the lane's fixed costs (extra
            # top-(C+1), patch pass, refire risk) outweigh the win
            return None
        for bkt in self.ESS_BUCKETS:
            if nb_ess <= bkt:
                return (bkt, ess, ne, bound, float(theta), int(total))
        return None

    def _launch_essential(self, reg, bucket, items, arrived, stack, rows):
        responded: set = set()
        try:
            self._launch_essential_inner(reg, bucket, items, arrived,
                                         stack, rows, responded)
        except Exception:
            logger.exception("essential launch failed; full-kernel "
                             "retry")
            self.stats["errors"] += 1
            # only tokens not yet answered — a mid-loop failure must
            # never double-respond/bounce consumed tokens
            left = [it for it in items if it[0] not in responded]
            try:
                if left:
                    self._refire_full(reg, left, arrived, stack, rows)
            except Exception:
                h = self.front.h
                for tok, *_ in left:
                    try:
                        if h is not None:
                            self.lib.es_fast_bounce(h, tok)
                    except Exception:
                        pass

    def _refire_full(self, reg, items, arrived, stack, rows):
        """Uncertified/failed essential queries re-run on the exact full
        kernel (already holding a stream permit — run inline)."""
        full_items = [(tok, k, term_ids, filt)
                      for tok, k, term_ids, filt, _ess in items]
        self.stats["ess_refires"] = self.stats.get("ess_refires", 0) \
            + len(full_items)
        # the largest bucket is the only warm v1 shape
        self._launch_group(reg, self.nb_buckets[-1], full_items, arrived,
                           stack, rows, first=False)

    def _launch_essential_inner(self, reg, bucket, items, arrived,
                                stack, rows, responded=None):
        from elasticsearch_tpu.ops.fastpath import (
            NE_SLOTS, bm25_essential_topk_batch)
        dp = reg["dp"]
        with host_span("fastpath.pack"):
            sel = np.full((self.q_batch, bucket), dp.zero_block,
                          np.int32)
            ws = np.zeros((self.q_batch, bucket), self._weight_dtype())
            mask_ids = np.zeros(self.q_batch, np.int32)
            ne_start = np.zeros((self.q_batch, NE_SLOTS), np.int32)
            ne_len = np.zeros((self.q_batch, NE_SLOTS), np.int32)
            ne_idf = np.zeros((self.q_batch, NE_SLOTS), self._weight_dtype())
            ne_bound = np.zeros(self.q_batch, self._weight_dtype())
            starts, nbs, idf = reg["starts"], reg["nb"], reg["idf"]
            bad: list = []
            for qi, (tok, k, term_ids, filt, essd) in enumerate(items):
                _bkt, ess_terms, ne_terms, bound, theta, total = essd
                pos = 0
                for t in ess_terms:
                    cnt = int(nbs[t])
                    st = int(starts[t])
                    sel[qi, pos:pos + cnt] = np.arange(st, st + cnt,
                                                       dtype=np.int32)
                    ws[qi, pos:pos + cnt] = idf[t]
                    pos += cnt
                for ti, t in enumerate(ne_terms):
                    ne_start[qi, ti] = reg["post_start"][t]
                    ne_len[qi, ti] = reg["post_len"][t]
                    ne_idf[qi, ti] = idf[t]
                ne_bound[qi] = bound
                if filt:
                    row = rows.get(filt)
                    if row is None:
                        bad.append(tok)
                        sel[qi, :] = dp.zero_block
                        ws[qi, :] = 0.0
                        continue
                    mask_ids[qi] = row
        masks = stack
        k_static = self.max_k
        out, t_done = self._launch_cohort(
            "search.fastpath.essential_cohort", items, arrived, True,
            bm25_essential_topk_batch, dp.block_docids, dp.block_tfs,
            reg["flat_docids"], reg["flat_tfs"], sel, ws, reg["block_lens"],
            dp.doc_lens, masks, mask_ids, ne_start, ne_len, ne_idf,
            ne_bound,
            self._weight_dtype()(dp.avg_len), reg["k1"], reg["b"],
            k_static)
        idx_b = reg["index"].encode()
        h = self.front.h
        self._count_launch(masks)
        self.stats["ess_queries"] = self.stats.get("ess_queries", 0) \
            + len(items)
        bad_set = set(bad)
        if responded is None:
            responded = set()
        refire: list = []
        with host_span("fastpath.respond"):
            for qi, (tok, k, term_ids, filt, essd) in enumerate(items):
                if tok in bad_set:
                    self._respond_empty(tok, reg)
                    responded.add(tok)
                    continue
                ok = int(out[qi, 2 * k_static:][0])
                if not ok:
                    refire.append((tok, k, term_ids, filt, essd))
                    continue
                vals = out[qi, :k_static]
                ids = _unpack_ids(out[qi, k_static:2 * k_static])
                nhit = int(min(k, np.isfinite(vals).sum()))
                v = np.ascontiguousarray(vals[:nhit])
                d = np.ascontiguousarray(ids[:nhit])
                if h is None:
                    return
                self.lib.es_fast_respond(
                    h, tok, idx_b,
                    d.ctypes.data_as(ctypes.c_void_p),
                    v.ctypes.data_as(ctypes.c_void_p),
                    nhit, essd[5], b"eq",
                    (t_done - arrived[tok]) // 1_000_000)
                responded.add(tok)
        self.stats["fast_queries"] += len(items) - len(refire)
        if refire:
            for tok, k, term_ids, filt, _essd in refire:
                if len(reg["ess_bad"]) < 100_000:
                    reg["ess_bad"].add((tuple(term_ids), filt, k))
            self._refire_full(reg, refire, arrived, stack, rows)
            for tok, *_ in refire:
                responded.add(tok)

    # ---------------------------------------------------- shared pieces
    #
    # The launch mask stack [F_SLOTS, ND] is PERSISTENT on device: row 0
    # is the plain live mask, rows 1..F-1 are assigned to filter SETS as
    # they first appear and updated in place (`.at[row].set`). The old
    # per-launch jnp.stack of F_SLOTS×ND rows was a ~64 MB device op on
    # EVERY filtered launch. Rows are assigned ONLY on the drain
    # thread (_route_cohort) and the resolved (stack, row map) snapshot
    # rides into each launch, so launch workers never mutate it.

    def _resolve_mask_rows(self, reg, filts):
        """(stack_device, {filt: row}) for a cohort's distinct filter
        sets; unknown-term filters map to row None (match nothing)."""
        from elasticsearch_tpu.ops.fastpath import F_SLOTS
        if reg.get("mask_stack") is None:
            reg["mask_stack"] = reg["plain_masks"]
            reg["stack_map"] = {}
            reg["stack_next"] = 1
        st = reg["mask_stack"]
        smap = reg["stack_map"]
        out: Dict[tuple, Optional[int]] = {}
        for filt in filts:
            if not filt:
                continue
            row = smap.get(filt)
            if row is None:
                col = self._filter_col(reg, filt)
                if col is None:
                    out[filt] = None
                    continue
                # round-robin eviction over rows 1..F-1, but never a
                # row ALREADY RESOLVED for this cohort (evicting one
                # would silently evaluate its queries against the wrong
                # filter column); a cohort holds <= F_SLOTS-1 distinct
                # sets so a free row always exists
                taken = {r for r in out.values() if r is not None}
                taken |= {smap[f] for f in filts
                          if f and f in smap}
                for _ in range(F_SLOTS - 1):
                    row = reg["stack_next"]
                    reg["stack_next"] = 1 + (row % (F_SLOTS - 1))
                    if row not in taken:
                        break
                for old_f, old_r in list(smap.items()):
                    if old_r == row:
                        del smap[old_f]
                st = st.at[row].set(col)
                smap[filt] = row
            out[filt] = row
        reg["mask_stack"] = st
        return st, out

    def _respond_hits(self, reg, tok, v, d, k, total, took_ms,
                      term_ids=None, filt=None):
        """Marshal one query's (contract-ordered) hits back through the
        C++ front; records the exact θ when the result fills k."""
        nhit = len(v)
        if (term_ids is not None and k == self.max_k and nhit == k
                and len(reg["theta"]) < 100_000):
            # exact kth + exact total: licenses the essential lane for
            # this query on this immutable registration
            reg["theta"][(tuple(term_ids), filt, k)] = (
                float(v[-1]), total)
            self.stats["theta_stores"] = \
                self.stats.get("theta_stores", 0) + 1
        h = self.front.h
        if h is None:
            return
        self.lib.es_fast_respond(
            h, tok, reg["index"].encode(),
            d.ctypes.data_as(ctypes.c_void_p),
            v.ctypes.data_as(ctypes.c_void_p),
            nhit, total, b"eq", took_ms)

    def _filter_col(self, reg, filt):
        """Device column: base live AND the filter-set mask (cached; the
        kernel contract is "base live AND filters" — deleted docs must
        never resurface through a filter column). None ⇒ a filter term
        is unknown (the filter matches nothing)."""
        import jax.numpy as jnp
        cached = reg["filter_live"].get(filt)
        if cached is not None:
            return cached
        dp, dev = reg["dp"], reg["dev"]
        pf = dp.host
        terms = []
        for t in filt:
            if not (0 <= t < len(pf.terms)):
                return None
            terms.append((reg["field"], (pf.terms[t],), False))
        mask, _host = dev.composed_filter_mask(terms)
        col = jnp.logical_and(dev.live, mask)
        if len(reg["filter_live"]) < 256:
            reg["filter_live"][filt] = col
        return col

    def _mesh_active(self, reg) -> bool:
        """The ONE gate for replica-sharded v1 cohorts: a mesh bound at
        registration AND the backend still enabled — the
        ESTPU_MESH_SERVING=0 kill switch must reach already-registered
        indices immediately, not at the next re-registration (the
        unsharded signature may cold-compile once; a kill switch is
        allowed that)."""
        return (reg.get("rmesh") is not None
                and self.mesh_backend is not None
                and self.mesh_backend.enabled())

    def _v1_inputs(self, reg, sel, ws, stack, mask_ids):
        """The v1 kernel's launch inputs, replica-sharded over the
        registration's mesh when one is bound: corpus arrays ride as
        replicated handles (cached by identity — the mask stack
        re-replicates only when a filter row actually changed), the
        per-query rows shard P("replica"). ONE compile signature per
        bucket and mask variant either way (warm and serve both come
        through here); an unmasked launch passes stack and mask_ids as
        None (``_mask_args``)."""
        dp = reg["dp"]
        rmesh = reg.get("rmesh")
        mb = self.mesh_backend
        if rmesh is None or mb is None or not self._mesh_active(reg):
            return (dp.block_docids, dp.block_tfs, sel, ws,
                    reg["block_lens"], stack, mask_ids)
        return (mb.replicated(rmesh, dp.block_docids),
                mb.replicated(rmesh, dp.block_tfs),
                mb.shard_rows(rmesh, sel),
                mb.shard_rows(rmesh, ws),
                mb.replicated(rmesh, reg["block_lens"]),
                None if stack is None else mb.replicated(rmesh, stack),
                None if mask_ids is None else mb.shard_rows(rmesh,
                                                            mask_ids))

    def _launch_group(self, reg, bucket, items, arrived, stack, rows,
                      first=True):
        from elasticsearch_tpu.ops.fastpath import bm25_topk_total_batch
        dp = reg["dp"]
        q = len(items)
        with host_span("fastpath.pack"):
            sel = np.full((self.q_batch, bucket), dp.zero_block,
                          np.int32)
            ws = np.zeros((self.q_batch, bucket), self._weight_dtype())
            mask_ids = np.zeros(self.q_batch, np.int32)
            starts, nbs, idf = reg["starts"], reg["nb"], reg["idf"]
            no_match: list = []
            for qi, (tok, k, term_ids, filt) in enumerate(items):
                pos = 0
                for t in term_ids:
                    if t < 0:
                        continue
                    cnt = int(nbs[t])
                    s = int(starts[t])
                    sel[qi, pos:pos + cnt] = np.arange(s, s + cnt,
                                                       dtype=np.int32)
                    ws[qi, pos:pos + cnt] = idf[t]
                    pos += cnt
                if filt:
                    row = rows.get(filt)
                    if row is None:          # unknown filter term ⇒ no hits
                        no_match.append(tok)
                        sel[qi, :] = dp.zero_block
                        ws[qi, :] = 0.0
                        continue
                    mask_ids[qi] = row
        k_static = self.max_k
        bd, bt, sel_m, ws_m, bl, mk, mi = self._v1_inputs(
            reg, sel, ws, *_mask_args(stack, mask_ids))
        out, t_done = self._launch_cohort(
            "search.fastpath.v1_cohort", items, arrived, first,
            bm25_topk_total_batch, bd, bt, sel_m, ws_m, bl, mk, mi,
            self._weight_dtype()(dp.avg_len), reg["k1"], reg["b"],
            k_static)
        self._count_launch(mk)
        if self._mesh_active(reg):
            self.stats["mesh_cohorts"] = \
                self.stats.get("mesh_cohorts", 0) + 1
            self.mesh_backend._dispatch("replica", q)
        self.stats["fast_queries"] += q
        no_match_set = set(no_match)
        with host_span("fastpath.respond"):
            for qi, (tok, k, term_ids, filt) in enumerate(items):
                if tok in no_match_set:
                    self._respond_empty(tok, reg)
                    continue
                vals = out[qi, :k_static]
                ids = _unpack_ids(out[qi, k_static:2 * k_static])
                total = int(out[qi, 2 * k_static:][0])
                nhit = int(min(k, np.isfinite(vals).sum()))
                v = vals[:nhit]
                d = ids[:nhit]
                # ES tie order: equal scores rank by docid ascending (the
                # device top_k's tie order is arbitrary)
                order = np.lexsort((d, -v))
                took_ms = (t_done - arrived[tok]) // 1_000_000
                self._respond_hits(reg, tok,
                                   np.ascontiguousarray(v[order]),
                                   np.ascontiguousarray(d[order]),
                                   k, total, took_ms, term_ids, filt)
