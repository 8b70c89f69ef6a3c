"""Search execution context: shard-level stats + per-segment device state.

Mirrors the reference's QueryShardContext + ContextIndexSearcher roles (ref:
index/query/QueryShardContext.java, search/internal/ContextIndexSearcher.java):
queries compile against shard-level term statistics (Lucene computes IDF from
IndexSearcher-level stats so scores are segment-independent) and execute
per segment against HBM-resident DeviceSegments.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.index.mapper import MapperService
from elasticsearch_tpu.index.segment import Segment
from elasticsearch_tpu.ops.device import DeviceSegment


class ShardStats:
    """Shard-level (cross-segment) field/term statistics for BM25."""

    def __init__(self, segments: List[Segment]):
        self.segments = segments
        self._field_cache: Dict[str, Tuple[int, float]] = {}
        self._df_cache: Dict[Tuple[str, str], int] = {}

    def field_stats(self, field: str) -> Tuple[int, float]:
        """(doc_count_with_field, avg_field_length) across the shard."""
        cached = self._field_cache.get(field)
        if cached is None:
            doc_count = 0
            sum_ttf = 0
            for seg in self.segments:
                pf = seg.postings.get(field)
                if pf is not None:
                    doc_count += pf.doc_count
                    sum_ttf += pf.sum_total_term_freq
            cached = (doc_count, sum_ttf / doc_count if doc_count else 1.0)
            self._field_cache[field] = cached
        return cached

    def doc_freq(self, field: str, term: str) -> int:
        key = (field, term)
        cached = self._df_cache.get(key)
        if cached is None:
            cached = 0
            for seg in self.segments:
                pf = seg.postings.get(field)
                if pf is not None:
                    tid = pf.term_id(term)
                    if tid >= 0:
                        cached += int(pf.doc_freq[tid])
            self._df_cache[key] = cached
        return cached


class SegmentContext:
    """One segment's view for query execution."""

    def __init__(self, segment: Segment, device: DeviceSegment,
                 mapper: MapperService, stats: ShardStats,
                 k1: float = 1.2, b: float = 0.75):
        self.segment = segment
        self.device = device
        self.mapper = mapper
        self.stats = stats
        self.k1 = k1
        self.b = b

    @property
    def n_docs_padded(self) -> int:
        return self.device.n_docs_padded

    @property
    def live(self):
        return self.device.live

    def all_true(self):
        """Mask of all real (non-padding) docs."""
        m = np.zeros(self.n_docs_padded, bool)
        m[: self.segment.n_docs] = True
        return jnp.asarray(m)

    def numeric_column(self, field: str):
        col = self.device.numerics.get(field)
        miss = self.device.numeric_missing.get(field)
        if col is None:
            col = jnp.zeros(self.n_docs_padded, jnp.float32)
            miss = jnp.ones(self.n_docs_padded, bool)
        return col, miss

    def keyword_ord_column(self, field: str):
        """Per-doc first-ordinal sort key for a keyword field, or None.

        Segment term dicts are sorted, so segment-local ordinals order
        lexicographically WITHIN the segment (the Lucene
        SortedSetDocValues model); cross-segment merges must compare the
        term strings (searcher host-side re-sort)."""
        kv = self.segment.keywords.get(field)
        if kv is None:
            return None
        col = np.zeros(self.n_docs_padded, np.float32)
        miss = np.ones(self.n_docs_padded, bool)
        col[: self.segment.n_docs] = np.maximum(kv.ords, 0)
        miss[: self.segment.n_docs] = kv.ords < 0
        return jnp.asarray(col), jnp.asarray(miss)


# DeviceSegment cache: segments are immutable except their live mask, so the
# cache key is (segment name, live_version); a delete only re-uploads live.
class DeviceSegmentCache:
    def __init__(self, device=None, vector_dtype=jnp.bfloat16,
                 breaker=None):
        from collections import OrderedDict as _OD
        # insertion/touch order IS the LRU order the hbm breaker's
        # eviction pressure walks (admission past the limit evicts
        # least-recently-used device segments before tripping)
        self._cache: "_OD[str, Tuple[int, DeviceSegment]]" = _OD()
        self._lock = threading.Lock()
        self._device = device
        self._vector_dtype = vector_dtype
        # hbm child breaker (utils/breaker.py CircuitBreaker) — None
        # keeps every admission site a single branch
        self.breaker = breaker
        self._charged: Dict[str, int] = {}   # segment name -> hbm bytes
        self.hbm_breaker_evictions = 0       # LRU evictions forced by it
        # request-breaker-accounted host allocator; ShardSearchers built
        # over this cache inherit it (searcher.py)
        self.bigarrays = None
        # compiled-LogicalPlan memo keyed by (segment names, epoch,
        # query json, k1, b) — ShardSearchers are per-request, this
        # cache is the persistent home (None = query not plannable).
        # Skipping parse→rewrite→compile on repeats is a large slice of
        # the per-query Python cost in the serving hot loop.
        from collections import OrderedDict
        self.plan_cache: "OrderedDict[tuple, object]" = OrderedDict()
        self.plan_cache_max = 512
        # engine observability: plan-cache counters (incremented by the
        # searcher, the cache's only client) + the node-level HBM peak
        # watermark, refreshed on every DeviceSegment build and on every
        # stats read
        self.plan_cache_hits = 0
        self.plan_cache_misses = 0
        self.plan_cache_evictions = 0
        self.peak_hbm_bytes = 0
        # lifetime device-segment builds (uploads admitted to HBM) —
        # with hbm_breaker_evictions, the churn pair a profiled query
        # snapshots before/after so `profile: true` charges HBM
        # admissions/evictions to the request that caused them
        self.admissions = 0

    def set_breaker(self, breaker) -> None:
        """Wire the `hbm` child breaker (node startup: Node/ClusterNode).
        Charges already-resident segments so accounting matches reality
        even when wiring happens after warm-up."""
        with self._lock:
            self.breaker = breaker
            if breaker is not None:
                for name, (_v, dev) in self._cache.items():
                    if name not in self._charged:
                        nbytes = dev.hbm_bytes()
                        breaker.add_without_breaking(nbytes)
                        self._charged[name] = nbytes

    def _admit_locked(self, nbytes: int, label: str,
                      exclude: str) -> None:
        """Charge the hbm breaker for ``nbytes``, applying LRU eviction
        pressure first: past the limit, least-recently-used device
        segments are dropped (their bytes released) until the charge
        fits; the breaker trips only when eviction cannot free enough
        (ref: the fielddata breaker + IndicesFieldDataCache eviction
        interplay, recast for device memory)."""
        br = self.breaker
        if br is None:
            return
        # evict-first probe: over-limit admissions drop LRU residents
        # WITHOUT counting a trip; the breaker's trip counter (and the
        # raised CircuitBreakingException) fires only when eviction has
        # nothing left to free
        while br.limit >= 0 and \
                (br.used + nbytes) * br.overhead > br.limit:
            victim = next((n for n in self._cache if n != exclude),
                          None)
            if victim is None:
                break
            self._cache.pop(victim)
            br.release(self._charged.pop(victim, 0))
            self.hbm_breaker_evictions += 1
        br.add_estimate_bytes_and_maybe_break(nbytes, label)

    def _release_locked(self, name: str) -> None:
        if self.breaker is not None:
            self.breaker.release(self._charged.pop(name, 0))
        else:
            self._charged.pop(name, None)

    def account_hbm(self, name: str, delta: int,
                    label: str = "filter_mask") -> None:
        """Admission/release of device bytes a resident DeviceSegment
        builds after its own admission — filter masks, posting lengths
        (called by ops/device.py). Positive deltas go through the same
        eviction-pressure admission as segment builds; negative deltas
        (mask LRU eviction) release. Orphan segments (already evicted
        from this cache) are not accounted."""
        with self._lock:
            if self.breaker is None or name not in self._charged:
                # unwired cache, or an orphan segment already evicted:
                # no accounting (set_breaker charges residents by their
                # FULL hbm_bytes — masks included — when wiring later)
                return
            if delta >= 0:
                self._admit_locked(delta, label, exclude=name)
            else:
                self.breaker.release(-delta)
            self._charged[name] = self._charged.get(name, 0) + delta

    def get(self, segment: Segment) -> DeviceSegment:
        with self._lock:
            entry = self._cache.get(segment.name)
            if entry is not None:
                version, dev = entry
                if version == segment.live_version:
                    self._cache.move_to_end(segment.name)
                    return dev
                if dev.segment is segment or dev.n_docs == segment.n_docs:
                    dev.update_live(segment.live)
                    self._cache[segment.name] = (segment.live_version, dev)
                    self._cache.move_to_end(segment.name)
                    return dev
                # stale copy replaced below: release its accounting
                self._cache.pop(segment.name, None)
                self._release_locked(segment.name)
            # segment admission charges AFTER the build (the slab sizes
            # fall out of it) — the breaker bounds steady-state
            # residency; the build itself transiently overshoots by one
            # segment, like the reference's fielddata loads that are
            # accounted as they materialize
            dev = DeviceSegment(segment, self._device, self._vector_dtype)
            nbytes = dev.hbm_bytes()
            # admission: evict LRU residents before ever tripping
            self._admit_locked(
                nbytes, f"device_segment[{segment.name}]",
                exclude=segment.name)
            if self.breaker is not None:
                self._charged[segment.name] = nbytes
            dev.hbm_sink = self
            self.admissions += 1
            self._cache[segment.name] = (segment.live_version, dev)
            total = sum(d.hbm_bytes() for _v, d in self._cache.values())
            self.peak_hbm_bytes = max(self.peak_hbm_bytes, total)
            return dev

    def evict(self, names) -> None:
        """Drop device copies of retired segments (called by IndexService
        after merges/deletes so HBM doesn't grow with dead segments)."""
        with self._lock:
            for name in names:
                if self._cache.pop(name, None) is not None:
                    self._release_locked(name)

    def evict_except(self, names: set) -> None:
        with self._lock:
            for name in list(self._cache):
                if name not in names:
                    del self._cache[name]
                    self._release_locked(name)

    def churn_counters(self) -> Tuple[int, int]:
        """(admissions, breaker_evictions) lifetime pair — a profiled
        query snapshots it before/after to report the HBM churn that
        happened during its window (node-wide: concurrent queries'
        uploads land in the same delta)."""
        with self._lock:
            return self.admissions, self.hbm_breaker_evictions

    # -- engine observability (the `engine` stats rollup) -----------------

    def _devices(self, segment_names=None) -> Dict[str, DeviceSegment]:
        with self._lock:
            devs = {name: dev for name, (_v, dev) in self._cache.items()}
        if segment_names is not None:
            devs = {n: d for n, d in devs.items() if n in segment_names}
        return devs

    def hbm_stats(self, segment_names=None) -> Dict[str, object]:
        """HBM bytes rolled up over live DeviceSegments, per slab class.

        ``segment_names=None`` is the node-level view and refreshes the
        peak watermark; a name set gives the per-index/per-shard slice
        (its peak is tracked by the owner — IndexService.stats())."""
        from elasticsearch_tpu.ops.device import HBM_SLAB_CLASSES
        devs = self._devices(segment_names)
        by_class = dict.fromkeys(HBM_SLAB_CLASSES, 0)
        total = 0
        for dev in devs.values():
            for cls, n in dev.hbm_bytes_by_class().items():
                by_class[cls] = by_class.get(cls, 0) + n
                total += n
        out: Dict[str, object] = {"total_bytes": total,
                                  "by_class": by_class,
                                  "segments": len(devs)}
        if segment_names is None:
            self.peak_hbm_bytes = max(self.peak_hbm_bytes, total)
            out["peak_bytes"] = self.peak_hbm_bytes
            # lifetime segment uploads; the per-query delta is what
            # `profile: true` charges to a request (churn_counters)
            out["admissions"] = self.admissions
            # admissions forced to drop an LRU resident by the hbm
            # breaker (zero in a healthy, fits-in-HBM deployment)
            out["breaker_evictions"] = self.hbm_breaker_evictions
        return out

    def cache_stats(self, segment_names=None) -> Dict[str, object]:
        """Device-cache counters aggregated over live DeviceSegments
        (+ the compiled-plan memo, which is cache-global and only
        reported on the unfiltered node-level view)."""
        agg: Dict[str, Dict[str, int]] = {}
        for dev in self._devices(segment_names).values():
            for cache_name, stats in dev.cache_stats().items():
                bucket = agg.setdefault(cache_name, {})
                for k, v in stats.items():
                    bucket[k] = bucket.get(k, 0) + v
        agg.setdefault("filter_mask", {"hits": 0, "misses": 0,
                                       "evictions": 0, "entries": 0,
                                       "bytes": 0})
        agg.setdefault("bound_plan", {"hits": 0, "misses": 0,
                                      "evictions": 0, "entries": 0})
        if segment_names is None:
            agg["plan"] = {"hits": self.plan_cache_hits,
                           "misses": self.plan_cache_misses,
                           "evictions": self.plan_cache_evictions,
                           "entries": len(self.plan_cache)}
        return agg

    def engine_stats(self, segment_names=None) -> Dict[str, object]:
        return {"hbm": self.hbm_stats(segment_names),
                "caches": self.cache_stats(segment_names)}
