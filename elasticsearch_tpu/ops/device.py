"""Device-resident segment state.

The analogue of Lucene's on-heap/off-heap segment readers, re-homed in TPU
HBM: a DeviceSegment uploads a segment's postings blocks, norms, live mask
and vector slabs to the device once; every query then only ships a few
hundred bytes of block ids and weights (the "JNI→JAX bridge" data plane of
BASELINE.json, without a process hop).

Shape discipline for XLA caching (everything under jit compiles per shape,
SURVEY.md §7 "hard parts" #2):
- doc count pads to a multiple of ``DOC_PAD`` (padded docs are dead in the
  live mask and have doc_len = avg so no NaN/0-div),
- one reserved all-zeros postings block sits at index ``num_blocks`` —
  query block lists pad with it (weight 0) and bucket to powers of two,
  so NB only takes O(log) distinct values across queries.
"""

from __future__ import annotations

from collections import OrderedDict
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.index.segment import BLOCK_SIZE, Segment
from elasticsearch_tpu.ops.vector import prepare_vectors
from elasticsearch_tpu.telemetry.tracing import host_span

DOC_PAD = 1024
MIN_BLOCK_BUCKET = 8

# Filter-mask cache knobs (per DeviceSegment). Each entry is one bool
# column: n_docs_padded bytes on device + the same on host (the host copy
# validates block-max pruning thresholds without a device readback).
FILTER_MASK_CACHE_MAX = 64

# HBM slab classes — the accounting buckets of `GET /_nodes/stats`'s
# engine section (the TPU-native analogue of the reference's segment
# stats + fielddata memory accounting in NodeIndicesStats). Every
# device-resident array of a DeviceSegment belongs to exactly one class,
# so `sum(hbm_bytes_by_class().values()) == hbm_bytes()` by construction.
HBM_SLAB_CLASSES = ("postings", "norms", "live_mask", "vectors",
                    "doc_values", "ordinals", "filter_masks",
                    "posting_lens")

# readback site -> its span name ("readback:<site>"), built once per site
_READBACK_SPANS: Dict[str, str] = {}


def readback(site: str, *arrays, profile: bool = True):
    """THE tracked device→host funnel: every product-path transfer of a
    jitted output to host memory goes through here so its call site,
    byte count, and duration land in the per-node flight recorder
    (telemetry/flightrecorder.py) — provenance for the post-readback
    degraded regime. estpu-lint's ESTPU-RB rules flag ``np.asarray`` /
    ``jax.device_get`` / ``.block_until_ready()`` on jitted outputs
    anywhere else in the engine dirs, keeping attribution total.

    ``site`` is a stable dotted label (``"search.batching.plan_cohort"``);
    returns the host array for one input, a tuple for several. Also
    feeds the per-request ``profile: true`` readback counters, so the
    two sites that used to hand-roll that share one implementation.
    Costs two TLS getattrs plus the transfer when nothing is ambient.
    """
    from elasticsearch_tpu.search import profile as _prof
    from elasticsearch_tpu.telemetry import flightrecorder as _flight
    fr = _flight.current()
    # profile=False: cohort-wide transfers (the batcher's ONE packed
    # readback) keep per-entry attribution in their cohort meta instead
    # of charging the whole cohort's bytes to the leader's request
    prof_on = profile and _prof.recording()
    t_prof = _prof.now_ns() if prof_on else 0
    t_fr = fr.clock() if fr is not None else 0.0
    name = _READBACK_SPANS.get(site)
    if name is None:
        name = _READBACK_SPANS.setdefault(site, "readback:" + site)
    # the host's wait for the device, as a span on the profiler's clock
    with host_span(name):
        out = tuple(np.asarray(a) for a in arrays)
    if fr is not None:
        fr.record_readback(
            site, sum(int(a.nbytes) for a in out),
            duration_ns=int((fr.clock() - t_fr) * 1e9))
    if prof_on:
        _prof.record_readback(t_prof, *out)
    return out[0] if len(out) == 1 else out


def round_up(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def block_bucket(n: int) -> int:
    """Round a selected-block count up to the next power-of-two bucket."""
    b = MIN_BLOCK_BUCKET
    while b < n:
        b *= 2
    return b


def host_any_mask(pf, terms, nd: int) -> np.ndarray:
    """Host-side any-of term-presence mask over ``nd`` docs — the single
    implementation behind both the cached device filter masks
    (DeviceSegment.filter_mask) and the plan compiler's CPU-side
    threshold validation (search/plan.py)."""
    mask = np.zeros(nd, bool)
    rows = []
    for t in terms:
        tid = pf.term_id(t)
        if tid >= 0:
            s = int(pf.term_block_start[tid])
            rows.append(np.arange(s, s + int(pf.term_block_count[tid]),
                                  dtype=np.int64))
    if rows:
        rows = np.concatenate(rows)
        d = pf.block_docids[rows].reshape(-1)
        tf = pf.block_tfs[rows].reshape(-1)
        ok = tf > 0.0
        mask[d[ok][d[ok] < nd]] = True
    return mask


class DevicePostings:
    """One field's postings on device, with the reserved zero block."""

    def __init__(self, pf, n_docs_padded: int, device=None):
        tb = pf.block_docids.shape[0]
        docids = np.concatenate(
            [pf.block_docids, np.zeros((1, BLOCK_SIZE), np.int32)], axis=0)
        tfs = np.concatenate(
            [pf.block_tfs, np.zeros((1, BLOCK_SIZE), np.float32)], axis=0)
        put = partial(jax.device_put, device=device)
        self.block_docids = put(docids)
        self.block_tfs = put(tfs)
        self.block_max_tf = put(np.concatenate([pf.block_max_tf, [0.0]]).astype(np.float32))
        self.block_min_len = put(np.concatenate([pf.block_min_len, [0.0]]).astype(np.float32))
        lens = np.zeros(n_docs_padded, np.float32)
        lens[: len(pf.field_lengths)] = pf.field_lengths
        avg = pf.avg_field_length
        lens[len(pf.field_lengths):] = avg  # padded docs: harmless norm
        self.doc_lens = put(lens)
        self.zero_block = tb  # index of the reserved all-zeros block
        self.avg_len = float(avg)
        # host-side lookup stays on the host (term dict is a CPU structure)
        self.term_block_start = pf.term_block_start
        self.term_block_count = pf.term_block_count
        self.doc_freq = pf.doc_freq
        self.host = pf

    def select_blocks(self, term_ids, weights) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side: term ids + per-term weights -> padded (block ids,
        per-block weights) bucketed to a power of two."""
        ids = []
        ws = []
        for tid, w in zip(term_ids, weights):
            if tid < 0:
                continue
            start = int(self.term_block_start[tid])
            count = int(self.term_block_count[tid])
            ids.extend(range(start, start + count))
            ws.extend([w] * count)
        n = block_bucket(max(1, len(ids)))
        pad = n - len(ids)
        ids.extend([self.zero_block] * pad)
        ws.extend([0.0] * pad)
        return np.asarray(ids, np.int32), np.asarray(ws, np.float32)


class DeviceVectors:
    def __init__(self, vv, n_docs_padded: int, dtype=jnp.bfloat16, device=None):
        prepped, norms = prepare_vectors(vv.vectors, vv.similarity, dtype)
        nd, d = prepped.shape
        if n_docs_padded > nd:
            prepped = np.concatenate(
                [prepped, np.zeros((n_docs_padded - nd, d), prepped.dtype)], axis=0)
            norms = np.concatenate([norms, np.zeros(n_docs_padded - nd, np.float32)])
        put = partial(jax.device_put, device=device)
        self.vectors = put(prepped)
        self.norms = put(norms)
        self.sq_norms = put((norms * norms).astype(np.float32))
        self.has_value = put(np.concatenate(
            [vv.has_value, np.zeros(n_docs_padded - nd, bool)]))
        self.similarity = vv.similarity
        self.dims = vv.dims


class DeviceSegment:
    """A segment resident in device HBM. Built once per (segment, device);
    refresh swaps whole DeviceSegments (epoch pointer swap, SURVEY.md §7
    stage 4)."""

    def __init__(self, segment: Segment, device=None, vector_dtype=jnp.bfloat16):
        self.segment = segment
        self.name = segment.name
        self.n_docs = segment.n_docs
        self.n_docs_padded = max(DOC_PAD, round_up(segment.n_docs, DOC_PAD))
        # packing invariant (ops/plan.py pack_result): docids ride
        # device→host readbacks as float32 casts, exact only < 2^24 —
        # enforce LOUDLY at build time, not as silent wraparound later
        from elasticsearch_tpu.ops.plan import check_packed_id_limit
        check_packed_id_limit(self.n_docs_padded,
                              f"DeviceSegment[{segment.name}]")
        self._device = device
        # backpressure sink (search/context.py DeviceSegmentCache):
        # filter-mask and posting-length builds charge the hbm breaker
        # through it; None for standalone DeviceSegments outside a cache
        self.hbm_sink = None
        # LRU filter-mask cache — the analogue of Lucene's LRUQueryCache
        # for filter clauses (ref: search/LRUQueryCache.java via
        # IndicesQueryCache): an any-of terms filter caches as ONE dense
        # bool column, so its postings never enter the per-query sort.
        # Keyed by (field, terms); segment immutability (epoch swaps
        # replace whole DeviceSegments) keeps entries valid for the
        # segment's lifetime.
        self._filter_masks: "OrderedDict[tuple, tuple]" = OrderedDict()
        # field -> its postings' document lengths in block layout, built
        # on first use (``posting_lens``)
        self._posting_lens: Dict[str, jax.Array] = {}
        # BoundPlan cache (search/searcher.py): repeated queries reuse
        # their device-resident selection arrays — skipping bind_plan AND
        # the per-launch host→device uploads of the selections
        self._bound_plans: "OrderedDict[tuple, object]" = OrderedDict()
        # device-cache stats (engine observability — the analogue of
        # IndicesQueryCache stats): plain ints, advisory counters on a
        # GIL'd hot path. Bound-plan counters are incremented by the
        # searcher (the cache's only reader/writer).
        self.filter_mask_hits = 0
        self.filter_mask_misses = 0
        self.filter_mask_evictions = 0
        self.bound_plan_hits = 0
        self.bound_plan_misses = 0
        self.bound_plan_evictions = 0
        live = np.zeros(self.n_docs_padded, bool)
        live[: segment.n_docs] = segment.live
        self.live = jax.device_put(live, device=device)
        self.postings: Dict[str, DevicePostings] = {
            f: DevicePostings(pf, self.n_docs_padded, device)
            for f, pf in segment.postings.items()
        }
        self.vectors: Dict[str, DeviceVectors] = {
            f: DeviceVectors(vv, self.n_docs_padded, vector_dtype, device)
            for f, vv in segment.vectors.items()
        }
        # numeric doc values as dense device columns (range filters, sorts,
        # script features)
        put = partial(jax.device_put, device=device)
        self.numerics: Dict[str, jax.Array] = {}
        self.numeric_missing: Dict[str, jax.Array] = {}
        for f, nv in segment.numerics.items():
            vals = np.zeros(self.n_docs_padded, np.float64)
            vals[: len(nv.values)] = np.nan_to_num(nv.values, nan=0.0)
            miss = np.ones(self.n_docs_padded, bool)
            miss[: len(nv.missing)] = nv.missing
            self.numerics[f] = put(vals.astype(np.float32))
            self.numeric_missing[f] = put(miss)

    def keyword_ord_major(self, field: str):
        """(device docid-permutation int32 [total], host term_starts
        int64 [n_terms+1]) — every keyword value position sorted by ord,
        the ord-major layout the device terms-agg collector reduces over
        (ops/aggs.py). Built lazily once per immutable segment; None
        when the field has no keyword values."""
        cache = getattr(self, "_kw_ord_major", None)
        if cache is None:
            cache = self._kw_ord_major = {}
        if field in cache:
            return cache[field]
        kv = self.segment.keywords.get(field)
        if kv is None or len(kv.all_ords) == 0:
            cache[field] = None
            return None
        order = np.argsort(kv.all_ords, kind="stable")
        pos_doc = np.searchsorted(kv.offsets,
                                  np.arange(len(kv.all_ords)),
                                  side="right") - 1
        perm_docs = pos_doc[order].astype(np.int32)
        sorted_ords = kv.all_ords[order]
        term_starts = np.searchsorted(
            sorted_ords, np.arange(len(kv.terms) + 1)).astype(np.int64)
        entry = (jax.device_put(perm_docs, device=self._device),
                 term_starts)
        cache[field] = entry
        return entry

    def filter_mask(self, field: str, terms) -> Tuple[jax.Array, np.ndarray]:
        """Any-of terms-presence mask for ``field``, LRU-cached.

        Returns ``(device_mask, host_mask)`` — bool [n_docs_padded]. Built
        host-side from the segment's block postings (a pure gather — no
        device work) and uploaded once; subsequent queries reuse the
        column. The host copy stays available so the plan compiler can
        validate pruning thresholds CPU-side (search/plan.py).
        ref: Lucene LRUQueryCache — cached filters become bitsets that
        skip per-query scoring entirely."""
        key = (field, tuple(sorted(set(terms))))
        hit = self._filter_masks.get(key)
        if hit is not None:
            self.filter_mask_hits += 1
            self._filter_masks.move_to_end(key)
            return hit
        self.filter_mask_misses += 1
        dp = self.postings.get(field)
        if dp is not None:
            mask = host_any_mask(dp.host, key[1], self.n_docs_padded)
        else:
            mask = np.zeros(self.n_docs_padded, bool)
        # hbm admission BEFORE the device upload (the host mask has the
        # same nbytes) — a trip here surfaces as a typed per-shard
        # circuit_breaking_exception the coordinator fails over, and
        # nothing lands in device memory past the limit
        self._account_hbm(int(mask.nbytes))
        dev_mask = jax.device_put(mask, device=self._device)
        entry = (dev_mask, mask)
        self._filter_masks[key] = entry
        while len(self._filter_masks) > FILTER_MASK_CACHE_MAX:
            _k, (evicted, _h) = self._filter_masks.popitem(last=False)
            self.filter_mask_evictions += 1
            self._account_hbm(-int(evicted.nbytes))
        return entry

    def composed_filter_mask(self, conversions) -> Tuple[jax.Array,
                                                         np.ndarray]:
        """AND-composition of cached filter masks for a whole filter SET
        (``conversions``: [(field, terms, negate)]), itself cached. The
        returned DEVICE object is identical for every query using the
        same filters — the batcher keys cohorts on that identity, so one
        [ND] column serves a whole batched launch."""
        key = ("composed", tuple(
            (f, tuple(sorted(set(t))), bool(neg))
            for f, t, neg in sorted(conversions,
                                    key=lambda c: (c[0], c[1], c[2]))))
        hit = self._filter_masks.get(key)
        if hit is not None:
            self.filter_mask_hits += 1
            self._filter_masks.move_to_end(key)
            return hit
        self.filter_mask_misses += 1
        host = None
        for fname, terms, negate in key[1]:
            _, hm = self.filter_mask(fname, terms)
            hm = ~hm if negate else hm
            host = hm.copy() if host is None else (host & hm)
        self._account_hbm(int(host.nbytes))
        dev_mask = jax.device_put(host, device=self._device)
        entry = (dev_mask, host)
        self._filter_masks[key] = entry
        while len(self._filter_masks) > FILTER_MASK_CACHE_MAX:
            _k, (evicted, _h) = self._filter_masks.popitem(last=False)
            self.filter_mask_evictions += 1
            self._account_hbm(-int(evicted.nbytes))
        return entry

    def posting_lens(self, field: str) -> jax.Array:
        """float32 [TB + 1, B]: the document length of each posting of
        ``field``, laid out as its block arrays (``[r, j]`` is the
        length of doc ``block_docids[r, j]``), so a kernel reads the
        lengths of its selected blocks as block rows beside their tfs
        (ops/fastpath.py ``_postings``). Padding lanes hold the length
        of the doc they point at, which no score reads (tf 0). Built on
        the device once per immutable segment, on first use — the fast
        path's registration, the one reader, so paths that never read
        it never hold it — and charged like a filter mask."""
        table = self._posting_lens.get(field)
        if table is None:
            dp = self.postings[field]
            self._account_hbm(
                dp.block_docids.size * dp.doc_lens.dtype.itemsize,
                "posting_lens")
            table = jnp.take(dp.doc_lens, dp.block_docids, mode="clip")
            self._posting_lens[field] = table
        return table

    def _account_hbm(self, delta: int, label: str = "filter_mask") -> None:
        """Charge/release device bytes built after admission (filter
        masks, posting lengths) against the owning cache's hbm breaker
        (no-op for standalone segments)."""
        sink = self.hbm_sink
        if sink is not None:
            sink.account_hbm(self.name, delta, label)

    def update_live(self, live: np.ndarray) -> None:
        """Re-upload only the live mask (deletes don't touch postings)."""
        padded = np.zeros(self.n_docs_padded, bool)
        padded[: len(live)] = live
        self.live = jax.device_put(padded, device=self.live.devices().pop()
                                   if hasattr(self.live, "devices") else None)

    def hbm_bytes_by_class(self) -> Dict[str, int]:
        """Device-resident bytes per slab class (HBM_SLAB_CLASSES) —
        the engine-stats accounting model. ``postings`` is the block
        arrays + block-max metadata; ``norms`` the per-doc field-length
        columns (the analogue of Lucene's norms); ``doc_values`` the
        numeric columns + their missing masks; ``ordinals`` the lazy
        keyword ord-major permutations; ``filter_masks`` the LRU-cached
        device filter columns (so eviction visibly RETURNS bytes);
        ``posting_lens`` the per-posting document lengths the fast path
        registers (``posting_lens()``)."""
        out = dict.fromkeys(HBM_SLAB_CLASSES, 0)
        out["live_mask"] = int(self.live.nbytes)
        for dp in self.postings.values():
            out["postings"] += int(dp.block_docids.nbytes +
                                   dp.block_tfs.nbytes +
                                   dp.block_max_tf.nbytes +
                                   dp.block_min_len.nbytes)
            out["norms"] += int(dp.doc_lens.nbytes)
        for dv in self.vectors.values():
            out["vectors"] += int(dv.vectors.nbytes + dv.norms.nbytes +
                                  dv.sq_norms.nbytes +
                                  dv.has_value.nbytes)
        for arr in self.numerics.values():
            out["doc_values"] += int(arr.nbytes)
        for arr in self.numeric_missing.values():
            out["doc_values"] += int(arr.nbytes)
        for entry in (getattr(self, "_kw_ord_major", None) or {}).values():
            if entry is not None:
                out["ordinals"] += int(entry[0].nbytes)
        for dev_mask, _host in self._filter_masks.values():
            out["filter_masks"] += int(dev_mask.nbytes)
        for table in self._posting_lens.values():
            out["posting_lens"] += int(table.nbytes)
        return out

    def hbm_bytes(self) -> int:
        """Total device-resident bytes — BY CONSTRUCTION the sum of
        ``hbm_bytes_by_class()`` (the node-stats invariant pinned in
        tests/test_engine_stats.py)."""
        return sum(self.hbm_bytes_by_class().values())

    def cache_stats(self) -> Dict[str, Dict[str, int]]:
        """Per-segment device-cache counters (engine observability —
        ref: IndicesQueryCache / LRUQueryCache stats)."""
        fm_bytes = sum(int(m.nbytes) for m, _h in
                       self._filter_masks.values())
        return {
            "filter_mask": {
                "hits": self.filter_mask_hits,
                "misses": self.filter_mask_misses,
                "evictions": self.filter_mask_evictions,
                "entries": len(self._filter_masks),
                "bytes": fm_bytes,
            },
            "bound_plan": {
                "hits": self.bound_plan_hits,
                "misses": self.bound_plan_misses,
                "evictions": self.bound_plan_evictions,
                "entries": len(self._bound_plans),
            },
        }
