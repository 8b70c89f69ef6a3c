"""Mesh-sharded serving backend: REST `_search` → one SPMD program.

The integration the reference achieves with TransportSearchAction's
scatter-gather (ref: action/search/TransportSearchAction.java:93,469-523 —
per-shard RPC fan-out, SearchPhaseController.java:154-218 coordinator
merge): on a device mesh the same multi-shard query runs as ONE
``shard_map`` program — every device scores its shard's postings with the
fused plan kernel (ops/plan.py plan_topk_mesh), then a single
``all_gather`` over the shard axis + on-device re-top-k replaces the
coordinator merge, and a ``psum`` replaces the total-hits accumulation.
The merge rides ICI instead of RPC.

Per-shard differences the RPC path exhibits are preserved exactly:
term weights (idf) and keyword constants come from each shard's own
statistics (ES default per-shard IDF; dfs_query_then_fetch would psum
the stats first — sharded_dfs_stats in parallel/sharded.py), so a mesh
search returns byte-identical results to the per-shard loop it replaces.

Corpus residency: per (index, shards-epoch) the per-shard postings stack
onto a leading shard axis and ``device_put`` with a ``P("shard")``
sharding — each device holds only its shard, the HBM analogue of one
Lucene shard per data node. Multi-host meshes run the identical program;
only the Mesh changes (collectives ride ICI in-host, DCN across hosts).

:class:`MeshSearchBackend` is the serving entry: ``search/service.py``
dispatches eligible multi-shard queries to it (bm25/bool via the plan
kernel, pure kNN via the vector kernels below) and both
``search/batching.py`` and the native front (``search/fastpath.py``)
borrow its replica-axis helpers to fan query COHORTS across devices.
Every ineligible shape falls back to the per-shard loop with a typed
``fallback.<reason>`` counter — never an error — and the dispatch/
fallback/residency surface ships via ``GET /_kernels`` (rest/api.py).

Ceilings honored with clean fallback (see ops/plan.py / sharded.py):
``PACKED_ID_LIMIT`` (2^24: packed readback ids ride float32 casts) and
``GID_INT32_LIMIT`` (2^31: global-id arithmetic with x64 off — the
sharded kernel library falls back to a host int64 merge past it).
"""

from __future__ import annotations

import os
import threading
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.index.segment import BLOCK_SIZE, Segment
from elasticsearch_tpu.ops import plan as plan_ops
from elasticsearch_tpu.ops.device import block_bucket, readback
from elasticsearch_tpu.search.plan import LogicalPlan, compile_plan
from elasticsearch_tpu.telemetry.engine import tracked_jit
from jax import shard_map

DOC_PAD = 1024


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


class _CompositePostings:
    """A shard's postings for one field, across MULTIPLE segments,
    presented as one block layout: block arrays concatenate (docids
    offset by each segment's doc base — padding entries keep tf=0, so
    every kernel's tf>0 guard ignores their shifted docids), term
    lookups return one RANGE PER SUB-SEGMENT."""

    def __init__(self, pfs: List, doc_bases: List[int],
                 n_docs_total: int):
        present = [(j, pf) for j, pf in enumerate(pfs) if pf is not None]
        self._block_offsets = {}
        docids, tfs = [], []
        off = 0
        for j, pf in present:
            docids.append(pf.block_docids + np.int32(doc_bases[j]))
            tfs.append(pf.block_tfs)
            self._block_offsets[j] = off
            off += pf.block_docids.shape[0]
        self.block_docids = (np.concatenate(docids) if docids
                             else np.zeros((0, BLOCK_SIZE), np.int32))
        self.block_tfs = (np.concatenate(tfs) if tfs
                          else np.zeros((0, BLOCK_SIZE), np.float32))
        lens = np.ones(n_docs_total, np.float32)
        sum_ttf = 0
        doc_count = 0
        for j, pf in present:
            nd = len(pf.field_lengths)
            lens[doc_bases[j]: doc_bases[j] + nd] = pf.field_lengths
            sum_ttf += pf.sum_total_term_freq
            doc_count += pf.doc_count
        self.field_lengths = lens
        self.avg_field_length = sum_ttf / max(1, doc_count)
        self._pfs = present

    def term_id(self, term: str) -> int:
        # 0/-1 presence flag: block ranges come from term_block_ranges
        for _j, pf in self._pfs:
            if pf.term_id(term) >= 0:
                return 0
        return -1

    def term_block_ranges(self, term: str) -> List[Tuple[int, int]]:
        out = []
        for j, pf in self._pfs:
            tid = pf.term_id(term)
            if tid >= 0:
                out.append((self._block_offsets[j]
                            + int(pf.term_block_start[tid]),
                            int(pf.term_block_count[tid])))
        return out


class _CompositeShard:
    """Multiple segments of one shard presented as a single
    segment-like object for the mesh corpus (the per-device analogue of
    stacking a shard's segments into one resident layout; ref:
    TransportSearchAction fans out per shard, not per segment)."""

    def __init__(self, segments: List[Segment]):
        self.sub_segments = segments
        self.name = "+".join(seg.name for seg in segments)
        self.doc_bases = []
        total = 0
        for seg in segments:
            self.doc_bases.append(total)
            total += seg.n_docs
        self.n_docs = total
        self.postings = _CompositePostingsMap(self)

    @property
    def live(self) -> np.ndarray:
        return np.concatenate([seg.live for seg in self.sub_segments]) \
            if self.sub_segments else np.zeros(0, bool)

    @property
    def live_version(self):
        return tuple(seg.live_version for seg in self.sub_segments)

    def locate(self, docid: int) -> Tuple[int, int]:
        """composite docid → (segment_idx, local_docid)."""
        import bisect
        j = bisect.bisect_right(self.doc_bases, docid) - 1
        return j, docid - self.doc_bases[j]


class _CompositePostingsMap:
    def __init__(self, shard: _CompositeShard):
        self._shard = shard
        self._cache: Dict[str, Optional[_CompositePostings]] = {}

    def get(self, name: str):
        if name not in self._cache:
            pfs = [seg.postings.get(name)
                   for seg in self._shard.sub_segments]
            self._cache[name] = (
                _CompositePostings(pfs, self._shard.doc_bases,
                                   self._shard.n_docs)
                if any(pf is not None for pf in pfs) else None)
        return self._cache[name]


def _term_ranges(pf, term: str) -> List[Tuple[int, int]]:
    """Block ranges for a term — one per sub-segment on composites,
    a single contiguous range on plain PostingsFields."""
    ranges = getattr(pf, "term_block_ranges", None)
    if ranges is not None:
        return ranges(term)
    tid = pf.term_id(term)
    if tid < 0:
        return []
    return [(int(pf.term_block_start[tid]),
             int(pf.term_block_count[tid]))]


class MeshFieldState:
    """One field's postings stacked over shards, device-sharded."""

    def __init__(self, mesh: Mesh, pfs: List, n_docs_padded: int):
        s = len(pfs)
        tb_max = max((pf.block_docids.shape[0] for pf in pfs if pf is not None),
                     default=0)
        docids = np.zeros((s, tb_max + 1, BLOCK_SIZE), np.int32)
        tfs = np.zeros((s, tb_max + 1, BLOCK_SIZE), np.float32)
        lens = np.ones((s, n_docs_padded), np.float32)
        for i, pf in enumerate(pfs):
            if pf is None:
                continue
            tb = pf.block_docids.shape[0]
            docids[i, :tb] = pf.block_docids
            tfs[i, :tb] = pf.block_tfs
            nd = len(pf.field_lengths)
            lens[i, :nd] = np.maximum(pf.field_lengths, 1.0)
            lens[i, nd:] = max(float(pf.avg_field_length), 1.0)
        # leading axis is the shard axis; shard_map slices it per device
        shard_spec = NamedSharding(mesh, P("shard"))
        self.block_docids = jax.device_put(docids, shard_spec)
        self.block_tfs = jax.device_put(tfs, shard_spec)
        self.doc_lens = jax.device_put(lens, shard_spec)
        self.zero_block = tb_max      # common reserved all-zeros block row
        self.pfs = pfs                # host term dicts for binding


class MeshVectorState:
    """One dense-vector field stacked over shards, device-sharded —
    the ``P("shard")`` analogue of per-node DeviceVectors slabs
    (ops/device.py). Slab values are IDENTICAL to the per-shard device
    cache's (same prepare_vectors, same dtype), so mesh kNN scores are
    byte-identical to the per-shard loop's."""

    def __init__(self, mesh: Mesh, segments: List, field: str,
                 n_docs_padded: int, dtype):
        from elasticsearch_tpu.ops.vector import prepare_vectors
        vvs = [seg.vectors.get(field) if hasattr(seg, "vectors") else None
               for seg in segments]
        self.hosts = vvs              # host slabs for the exact re-rank
        sims = {vv.similarity for vv in vvs if vv is not None}
        self.similarity = next(iter(sims)) if len(sims) == 1 else None
        dims = next((vv.dims for vv in vvs if vv is not None), 1)
        s = len(segments)
        slab = np.zeros((s, n_docs_padded, dims), np.dtype(dtype))
        sqn = np.zeros((s, n_docs_padded), np.float32)
        hv = np.zeros((s, n_docs_padded), bool)
        for i, vv in enumerate(vvs):
            if vv is None or self.similarity is None:
                continue
            prepped, norms = prepare_vectors(vv.vectors, self.similarity,
                                             dtype)
            n = prepped.shape[0]
            slab[i, :n] = prepped
            sqn[i, :n] = (norms * norms).astype(np.float32)
            hv[i, :len(vv.has_value)] = vv.has_value
        shard_spec = NamedSharding(mesh, P("shard"))
        self.vectors = jax.device_put(slab, shard_spec)
        self.sq_norms = jax.device_put(sqn, shard_spec)
        self.has_value = jax.device_put(hv, shard_spec)
        self.dtype = self.vectors.dtype


class MeshCorpus:
    """A multi-shard index resident on a device mesh (one shard per
    device), built lazily per field from each shard's single segment."""

    def __init__(self, mesh: Mesh, segments: List[Segment]):
        self.mesh = mesh
        self.segments = segments
        self.n_shards = len(segments)
        nd = max((seg.n_docs for seg in segments), default=1)
        self.n_docs_padded = max(DOC_PAD, _round_up(nd, DOC_PAD))
        self.live_versions: Tuple[int, ...] = ()
        self.live = None
        self.refresh_live()
        self._fields: Dict[str, MeshFieldState] = {}
        self._vfields: Dict[Tuple[str, str], Optional[MeshVectorState]] = {}

    def refresh_live(self) -> None:
        """Deletes touch only the live bitmaps — re-upload just those
        (postings are immutable per segment, like the per-shard device
        cache's live-only refresh, search/context.py)."""
        versions = tuple(seg.live_version for seg in self.segments)
        if self.live is not None and versions == self.live_versions:
            return
        live = np.zeros((self.n_shards, self.n_docs_padded), bool)
        for i, seg in enumerate(self.segments):
            live[i, : seg.n_docs] = seg.live
        self.live = jax.device_put(
            live, NamedSharding(self.mesh, P("shard")))
        self.live_versions = versions

    def field(self, name: str) -> Optional[MeshFieldState]:
        if name not in self._fields:
            pfs = [seg.postings.get(name) for seg in self.segments]
            if all(pf is None for pf in pfs):
                return None
            self._fields[name] = MeshFieldState(
                self.mesh, pfs, self.n_docs_padded)
        return self._fields[name]

    def vector_field(self, name: str, dtype) -> Optional[MeshVectorState]:
        key = (name, str(np.dtype(dtype)))
        if key not in self._vfields:
            vs = MeshVectorState(self.mesh, self.segments, name,
                                 self.n_docs_padded, dtype)
            self._vfields[key] = vs if vs.similarity is not None else None
        return self._vfields[key]

    def device_arrays(self):
        """Every mesh-resident array of this corpus, tagged by slab
        class (the per-device HBM residency surface)."""
        if self.live is not None:
            yield "live_mask", self.live
        for fs in self._fields.values():
            yield "postings", fs.block_docids
            yield "postings", fs.block_tfs
            yield "norms", fs.doc_lens
        for vs in self._vfields.values():
            if vs is not None:
                yield "vectors", vs.vectors
                yield "vectors", vs.sq_norms
                yield "vectors", vs.has_value


def plans_mesh_compatible(plans: List[LogicalPlan]) -> bool:
    """All shards compiled the same query to the same structure with no
    dense factors (dense columns are not mesh-resident yet)."""
    if any(p is None for p in plans):
        return False
    p0 = plans[0]
    if any(p.dense for p in plans):
        return False
    for p in plans[1:]:
        if (len(p.groups) != len(p0.groups) or p.combine != p0.combine
                or p.msm != p0.msm or p.n_must != p0.n_must
                or p.n_filter != p0.n_filter):
            return False
    return True


def bind_mesh(corpus: MeshCorpus, plans: List[LogicalPlan]):
    """Bind one LogicalPlan per shard (weights/consts carry each shard's
    own idf) into stacked [S, ...] selection + group arrays. Returns None
    when a referenced field has no postings anywhere."""
    s = corpus.n_shards
    p0 = plans[0]
    ngroups = len(p0.groups)

    field_names: List[str] = []
    seen = set()
    for g in p0.groups:
        for t in g.terms:
            if t.field not in seen:
                seen.add(t.field)
                field_names.append(t.field)

    per_field_sel: Dict[str, List[Tuple[list, list, list, list, list]]] = {}
    for fname in field_names:
        fs = corpus.field(fname)
        if fs is None:
            continue
        shard_sels = []
        for si in range(s):
            pf = fs.pfs[si]
            ids: List[int] = []
            grps: List[int] = []
            subs: List[int] = []
            ws: List[float] = []
            consts: List[bool] = []
            if pf is not None:
                for gi, g in enumerate(plans[si].groups):
                    for t in g.terms:
                        if t.field != fname:
                            continue
                        for start, count in _term_ranges(pf, t.term):
                            ids.extend(range(start, start + count))
                            grps.extend([gi] * count)
                            subs.extend([t.sub] * count)
                            ws.extend([t.weight] * count)
                            consts.extend([t.const] * count)
            shard_sels.append((ids, grps, subs, ws, consts))
        per_field_sel[fname] = shard_sels

    if not per_field_sel:
        return None

    streams = []
    shard_spec = NamedSharding(corpus.mesh, P("shard"))
    for fname, shard_sels in per_field_sel.items():
        fs = corpus.field(fname)
        nb = block_bucket(max(1, max(len(e[0]) for e in shard_sels)))
        sel = np.full((s, nb), fs.zero_block, np.int32)
        grp = np.full((s, nb), ngroups, np.int32)
        sub = np.zeros((s, nb), np.int32)
        w = np.zeros((s, nb), np.float32)
        cst = np.zeros((s, nb), bool)
        avg = np.ones(s, np.float32)
        for si, (ids, grps, subs, ws, consts) in enumerate(shard_sels):
            n = len(ids)
            sel[si, :n] = ids
            grp[si, :n] = grps
            sub[si, :n] = subs
            w[si, :n] = ws
            cst[si, :n] = consts
            pf = fs.pfs[si]
            if pf is not None:
                avg[si] = max(float(pf.avg_field_length), 1.0)
        streams.append(plan_ops.FieldStream(
            fs.block_docids, fs.block_tfs, fs.doc_lens,
            jax.device_put(avg, shard_spec),
            jax.device_put(sel, shard_spec),
            jax.device_put(grp, shard_spec),
            jax.device_put(sub, shard_spec),
            jax.device_put(w, shard_spec),
            jax.device_put(cst, shard_spec)))

    gpad = max(4, block_bucket(max(1, ngroups)))
    kind = np.full((s, gpad), plan_ops.FILTER, np.int32)
    req = np.full((s, gpad), 1 << 30, np.int32)
    const = np.full((s, gpad), np.nan, np.float32)
    for si, p in enumerate(plans):
        for gi, g in enumerate(p.groups):
            kind[si, gi] = g.kind
            req[si, gi] = g.req
            const[si, gi] = g.const_score
    bonus = np.asarray([p.bonus for p in plans], np.float32)
    return (streams,
            jax.device_put(kind, shard_spec),
            jax.device_put(req, shard_spec),
            jax.device_put(const, shard_spec),
            jax.device_put(bonus, shard_spec))


# ---------------------------------------------------------------------------
# Mesh kNN kernels: the dense-vector analogue of plan_topk_mesh. Scoring
# mirrors KnnQuery.do_execute (search/queries.py) OPERATION FOR
# OPERATION — same formulas, same masking order, same cut semantics —
# so a mesh-served kNN `_search` is byte-identical to the per-shard
# dense loop it replaces.
# ---------------------------------------------------------------------------


def _knn_local_scores(vectors, sq_norms, has_value, qvec, similarity):
    """Per-shard (scores, mask) through the SAME ops/vector.py kernels
    and ES transforms KnnQuery.do_execute uses — shared code, not
    copies, so the mesh path cannot numerically drift from the
    per-shard loop."""
    from elasticsearch_tpu.ops import vector as vec_ops
    q = qvec[None, :]
    if similarity == "cosine":
        scores = (1.0 + vec_ops.cosine_scores(q, vectors)[0]) / 2.0
    elif similarity == "dot_product":
        scores = (1.0 + vec_ops.dot_scores(q, vectors)[0]) / 2.0
    else:  # l2_norm
        neg_sq = vec_ops.l2_scores(q, vectors, sq_norms)[0]
        scores = 1.0 / (1.0 - neg_sq)
    mask = has_value
    return jnp.where(mask, scores, 0.0), mask


@tracked_jit("mesh_knn_nominate",
             static_argnames=("mesh", "similarity", "nc"))
def _mesh_knn_nominate(vectors, sq_norms, has_value, qvec,
                       mesh: Mesh, similarity: str, nc: int):
    """Quantized-slab nomination: per-shard top-``nc`` candidate ids
    (the ids KnnQuery._exact_rerank reads back per shard — here ONE
    [S, nc] readback for the whole mesh)."""

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard"), P("shard"), P()),
             out_specs=P("shard"))
    def step(v, sn, hv, q):
        scores, _ = _knn_local_scores(v[0], sn[0], hv[0], q, similarity)
        _, ids = jax.lax.top_k(scores, nc)
        return ids[None, :]

    return step(vectors, sq_norms, has_value, qvec)


@tracked_jit("mesh_knn_step",
             static_argnames=("mesh", "nd", "similarity", "boost",
                              "cut", "k", "with_patch"))
def _mesh_knn_step(vectors, sq_norms, has_value, live, qvec,
                   patch_ids, patch_vals, mesh: Mesh, nd: int,
                   similarity: str, boost: float, cut: int, k: int,
                   with_patch: bool):
    """The full mesh kNN program: per-shard scoring (+ optional exact
    re-rank patch + candidate cut, mirroring KnnQuery.do_execute), live
    mask, psum'd totals, per-shard top-k and the all_gather merge —
    one packed readback. ``cut=0`` disables the per-shard candidate
    cut (cut >= n_docs_padded on the per-shard path)."""

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                       P(), P("shard"), P("shard")),
             out_specs=P())
    def step(v, sn, hv, lv, q, pid, pv):
        scores, mask = _knn_local_scores(v[0], sn[0], hv[0], q,
                                         similarity)
        if with_patch:
            # the exact-f32 re-rank scatter (KnnQuery._exact_rerank):
            # pad lanes carry unique out-of-range ids and drop
            scores = scores.at[pid[0]].set(pv[0], mode="drop",
                                           unique_indices=True)
        if cut:
            kth = jnp.sort(jnp.where(mask, scores, -jnp.inf))[nd - cut]
            mask = mask & (scores >= kth)
            scores = jnp.where(mask, scores, 0.0)
        if boost != 1.0:
            scores = scores * boost
        mask = mask & lv[0]
        vals, ids = jax.lax.top_k(jnp.where(mask, scores, -jnp.inf), k)
        shard_idx = jax.lax.axis_index("shard").astype(jnp.int32)
        gids = jnp.where(vals > -jnp.inf, ids + shard_idx * nd,
                         plan_ops._SENTINEL)
        av = jax.lax.all_gather(vals, "shard")
        ag = jax.lax.all_gather(gids, "shard")
        tv, ti = jax.lax.top_k(av.reshape(-1), k)
        tg = jnp.take(ag.reshape(-1), ti)
        tg = jnp.where(tv > -jnp.inf, tg, plan_ops._SENTINEL)
        total = jax.lax.psum(jnp.sum(mask.astype(jnp.int32)), "shard")
        return plan_ops.pack_result(tv, tg, total)

    return step(vectors, sq_norms, has_value, live, qvec,
                patch_ids, patch_vals)


class MeshSearchBackend:
    """Service-side entry: caches MeshCorpus per shard-set epoch and runs
    compatible multi-shard queries as one SPMD launch.

    Dispatches count under ``dispatch.<axis>`` (``shard`` = sharded-
    corpus SPMD serving, ``replica`` = query-cohort fan-out via the
    replica helpers); every refusal counts under ``fallback.<reason>``
    and the caller runs the per-shard loop — fallback is ALWAYS clean
    (no error surfaces to the request). ``metrics`` (a node
    MetricsRegistry, wired by Node) mirrors both as
    ``search.mesh.dispatch{axis}`` / ``search.mesh.fallback{reason}``.
    """

    #: replica-corpus handle cache bound (strong refs pin sources, which
    #: are long-lived registration/device-cache arrays anyway)
    REPLICA_CACHE_MAX = 64

    def __init__(self, max_cached: int = 4, min_devices: int = 2):
        from collections import OrderedDict
        self._cache: Dict[tuple, MeshCorpus] = {}
        self._cache_lock = threading.Lock()
        self._max_cached = max_cached
        self.min_devices = min_devices
        self.mesh_searches = 0   # stat: queries served via the mesh
        self.counters: Dict[str, int] = {}
        self.metrics = None      # node MetricsRegistry (wired by Node)
        self._replica_meshes: Dict[int, Mesh] = {}
        # LRU (touch-on-hit): churning entries (the fastpath mask stack
        # swaps identity on every filter-row update) age out while the
        # hot corpus handles stay resident
        self._replicated: "OrderedDict[tuple, tuple]" = OrderedDict()
        self._replica_lock = threading.Lock()

    # ------------------------------------------------------------- gates
    @staticmethod
    def enabled() -> bool:
        """Kill switch: ``ESTPU_MESH_SERVING=0`` forces the per-shard
        loop everywhere (fallback counters still tick)."""
        return os.environ.get("ESTPU_MESH_SERVING", "1") != "0"

    @staticmethod
    def available_devices() -> int:
        return len(jax.devices())

    def _count(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    def _dispatch(self, axis: str, n: int = 1) -> None:
        self._count(f"dispatch.{axis}", n)
        if self.metrics is not None:
            self.metrics.inc("search.mesh.dispatch", n, axis=axis)

    def _fallback(self, reason: str) -> None:
        self._count(f"fallback.{reason}")
        if self.metrics is not None:
            self.metrics.inc("search.mesh.fallback", reason=reason)

    # ------------------------------------------------------------ corpus
    def corpus_for(self, index_name: str,
                   shard_segments: List[Segment]) -> MeshCorpus:
        # keyed by segment NAMES (postings identity); deletes only bump
        # live_version and refresh the live bitmaps in place
        key = (index_name, tuple(seg.name for seg in shard_segments))
        with self._cache_lock:
            corpus = self._cache.get(key)
            if corpus is None:
                from elasticsearch_tpu.parallel.sharded import make_mesh
                mesh = make_mesh(n_shards=len(shard_segments))
                corpus = MeshCorpus(mesh, shard_segments)
                while len(self._cache) >= self._max_cached:
                    self._cache.pop(next(iter(self._cache)))
                self._cache[key] = corpus
            else:
                corpus.segments = shard_segments
                corpus.refresh_live()
        return corpus

    # ------------------------------------------------------------- stats
    def residency(self) -> Dict[str, Dict[str, int]]:
        """Per-DEVICE resident bytes by slab class over every cached
        mesh corpus — the `GET /_kernels` mesh.residency surface (the
        one-Lucene-shard-per-data-node HBM analogue, per chip)."""
        out: Dict[str, Dict[str, int]] = {}
        with self._cache_lock:
            corpora = list(self._cache.values())
        for corpus in corpora:
            for klass, arr in corpus.device_arrays():
                try:
                    shards = arr.addressable_shards
                except Exception:
                    continue
                for sh in shards:
                    dev = out.setdefault(str(sh.device), {})
                    dev[klass] = dev.get(klass, 0) + int(sh.data.nbytes)
        return out

    def stats(self) -> Dict[str, object]:
        with self._replica_lock:
            rep_bytes = sum(e[1].nbytes for e in self._replicated.values())
        return {
            "enabled": self.enabled(),
            "devices": self.available_devices(),
            "mesh_searches": self.mesh_searches,
            "counters": dict(sorted(self.counters.items())),
            "residency": self.residency(),
            "replica_corpus_bytes": int(rep_bytes),
        }

    # ---------------------------------------------- replica-axis helpers
    #
    # The second serving mode the tentpole names: query COHORTS (the
    # continuous-batching launches of search/batching.py and the native
    # front's fastpath cohorts) fan across a 1-D ("replica",) mesh —
    # corpus replicated (P()), the cohort's per-query rows sharded
    # P("replica"). The SAME jitted kernels run; GSPMD partitions the
    # vmapped program over the query axis (the pjit/PartitionSpec
    # pattern, SNIPPETS.md [2][3]), so per-query results stay
    # byte-identical to the single-device launch.

    def replica_mesh_for(self, q_rows: int) -> Optional[Mesh]:
        """Largest power-of-two ("replica",) mesh that divides a
        ``q_rows``-row cohort, or None when fewer than min_devices
        devices exist (the caller launches single-device)."""
        if not self.enabled():
            return None
        try:
            devices = jax.devices()
        except Exception:
            return None
        n = 1
        while n * 2 <= min(q_rows, len(devices)):
            n *= 2
        if n < max(2, self.min_devices):
            return None
        mesh = self._replica_meshes.get(n)
        if mesh is None:
            mesh = Mesh(np.asarray(devices[:n]), ("replica",))
            self._replica_meshes[n] = mesh
        return mesh

    def replicated(self, mesh: Mesh, arr):
        """A fully-replicated (P()) handle of a device/host corpus
        array, cached by source identity (sources are long-lived corpus
        arrays; a refresh swaps the source object and naturally
        re-replicates)."""
        key = (id(mesh), id(arr))
        with self._replica_lock:
            entry = self._replicated.get(key)
            if entry is not None and entry[0] is arr:
                self._replicated.move_to_end(key)
                return entry[1]
        rep = jax.device_put(arr, NamedSharding(mesh, P()))
        with self._replica_lock:
            self._replicated[key] = (arr, rep)
            while len(self._replicated) > self.REPLICA_CACHE_MAX:
                self._replicated.popitem(last=False)
        return rep

    def shard_rows(self, mesh: Mesh, arr):
        """Shard a cohort's leading (query) axis over the replica mesh."""
        return jax.device_put(arr, NamedSharding(mesh, P("replica")))

    # ----------------------------------------------------------- serving
    def execute(self, index_name: str, searchers, query,
                k: int) -> Optional[Tuple[list, int]]:
        """Try the mesh path: searchers = the index's per-shard
        ShardSearchers. Returns ([(shard_idx, seg_idx, local_docid,
        score)], total) sorted by (-score, shard, docid), or None to
        fall back to the per-shard loop (typed fallback counter)."""
        if not self.enabled():
            self._fallback("disabled")
            return None
        n_shards = len(searchers)
        if k < 1:
            self._fallback("size_zero")
            return None   # size:0 — per-shard path keeps max_score semantics
        if n_shards < 2:
            self._fallback("single_shard")
            return None
        if self.available_devices() < n_shards:
            self._fallback("not_enough_devices")
            return None
        if any(len(s.segments) == 0 for s in searchers):
            self._fallback("empty_shard")
            return None
        if any(getattr(s, "dfs_global_stats", False) for s in searchers):
            # dfs_query_then_fetch scores every shard with AGGREGATED
            # statistics; the mesh residency binds each shard's own
            # stats (ES-default per-shard IDF) — the loop keeps dfs
            # exact (sharded_dfs_stats is the future on-mesh answer)
            self._fallback("dfs_stats")
            return None
        from elasticsearch_tpu.search.queries import KnnQuery
        if isinstance(query, KnnQuery):
            return self._execute_knn(index_name, searchers, query, k)
        if any(len(s.segments) != 1 for s in searchers) \
                and os.environ.get("ESTPU_MESH_COMPOSITE") != "1":
            # composite (multi-segment) residency concatenates a
            # shard's segments into ONE kernel array — the segmented
            # sums then round with a different cumsum prefix base than
            # the per-segment loop, so scores drift in the last float32
            # bits. The serving contract here is BYTE-identical results
            # (the scroll one-executor rule, searcher.py), so unmerged
            # shards take the per-shard loop; force-merged layouts (the
            # mesh residency model) serve on-mesh. ESTPU_MESH_COMPOSITE=1
            # opts into the approximate composite mode. Checked BEFORE
            # the per-shard compiles: an unmerged index must not pay
            # S plan compiles per request just to fall back.
            self._fallback("multi_segment")
            return None
        # probe shard 0 first: ineligible queries (dense factors, scripts,
        # sorts…) bail after ONE compile instead of S
        first = compile_plan(query.rewrite(searchers[0]), searchers[0])
        if first is None or first.dense:
            self._fallback("plan_incompatible")
            return None
        plans = [first]
        for s in searchers[1:]:
            rq = query.rewrite(s)
            plans.append(compile_plan(rq, s))
        if not plans_mesh_compatible(plans):
            self._fallback("plan_incompatible")
            return None
        shard_views = [s.segments[0] if len(s.segments) == 1
                       else _CompositeShard(list(s.segments))
                       for s in searchers]
        # float-pack id overflow guard: the packed readback carries
        # GLOBAL ids (shard * nd_padded + docid) as float32 casts, exact
        # only < 2^24 — past that, fall back to the per-shard RPC merge
        # instead of silently corrupting low docid bits
        nd_max = max((v.n_docs for v in shard_views), default=1)
        nd_padded = max(DOC_PAD, _round_up(nd_max, DOC_PAD))
        if n_shards * nd_padded >= plan_ops.PACKED_ID_LIMIT:
            import logging
            logging.getLogger(__name__).warning(
                "mesh fast path skipped: %d shards x %d padded docs "
                ">= 2^24 float-packed global-id ceiling; using the "
                "per-shard fallback", n_shards, nd_padded)
            self._fallback("packed_id_ceiling")
            return None
        corpus = self.corpus_for(index_name, shard_views)
        bound = bind_mesh(corpus, plans)
        if bound is None:
            self.mesh_searches += 1
            self._dispatch("shard")
            return [], 0   # no query term exists in any shard
        streams, gk, gr, gc, bo = bound
        p0 = plans[0]
        packed = self._launch(
            corpus, "plan_topk_mesh",
            lambda: plan_ops.plan_topk_mesh(
                streams, gk, gr, gc, bo, corpus.live, corpus.mesh,
                corpus.n_docs_padded, p0.n_must, p0.n_filter, p0.msm,
                float(p0.tie), float(searchers[0].k1),
                float(searchers[0].b), int(k), p0.combine))
        self.mesh_searches += 1
        self._dispatch("shard")
        return self._unpack_docs(corpus, packed, int(k))

    def _launch(self, corpus: MeshCorpus, kernel: str, fn):
        """Run one mesh launch under the profile seam: stage-timed as
        ``launch`` and, when a `profile: true` recorder is active,
        attributed per chip via a device record carrying the mesh shape
        and device list (the PR-8 record_device contract)."""
        from elasticsearch_tpu.search import profile as _prof
        recording = _prof.recording()
        t0 = _prof.now_ns() if recording else 0
        with _prof.span("launch"):
            out = fn()
            packed = np.asarray(out)   # ONE readback for the mesh query
        launch_ms = round((_prof.now_ns() - t0) / 1e6, 3) if recording \
            else 0.0
        if recording:
            _prof.record_device({
                "kernel": kernel,
                "mesh_shape": {"shard": corpus.n_shards},
                "device": [str(d) for d in
                           np.asarray(corpus.mesh.devices).flat],
                "launch_ms": launch_ms,
                "readback_bytes": int(packed.nbytes),
            })
        return packed

    def _unpack_docs(self, corpus: MeshCorpus, packed: np.ndarray,
                     k: int) -> Tuple[list, int]:
        vals, gids, total = plan_ops.unpack_result(packed, k)
        nd = corpus.n_docs_padded
        docs = []
        for v, g in zip(vals, gids):
            if v <= -np.inf:
                continue
            shard, docid = int(g) // nd, int(g) % nd
            view = corpus.segments[shard]
            if isinstance(view, _CompositeShard):
                seg_idx, docid = view.locate(docid)
            else:
                seg_idx = 0
            docs.append((shard, seg_idx, docid, float(v)))
        return docs, int(total)

    # --------------------------------------------------------------- kNN
    def _execute_knn(self, index_name: str, searchers, query,
                     k: int) -> Optional[Tuple[list, int]]:
        """Mesh path for a bare top-level kNN query: per-shard brute
        force + all_gather merge, byte-identical to the per-shard dense
        loop (KnnQuery per shard + coordinator merge). Quantized slabs
        keep the exact-f32 re-rank: one [S, nc] nomination readback,
        the same host numpy re-rank per shard, and the exact scores
        ride back into the final SPMD launch as a scatter patch."""
        if query.filter_query is not None:
            self._fallback("knn_filter")
            return None
        if any(len(s.segments) != 1 for s in searchers):
            self._fallback("knn_multi_segment")
            return None
        from elasticsearch_tpu.search.searcher import MAX_TOPK
        k = min(max(int(k), 1), MAX_TOPK)
        pads = {max(DOC_PAD, _round_up(s.segments[0].n_docs, DOC_PAD))
                for s in searchers}
        if len(pads) != 1:
            # the per-shard candidate cut / nomination depth clamp to
            # EACH shard's padded size — non-uniform pads would change
            # semantics shard by shard
            self._fallback("knn_nonuniform_padding")
            return None
        n_shards = len(searchers)
        nd = pads.pop()
        if n_shards * nd >= plan_ops.PACKED_ID_LIMIT:
            self._fallback("packed_id_ceiling")
            return None
        dtype = getattr(searchers[0].cache, "_vector_dtype", jnp.bfloat16)
        corpus = self.corpus_for(
            index_name, [s.segments[0] for s in searchers])
        vs = corpus.vector_field(query.field, dtype)
        if vs is None:
            self._fallback("knn_missing_field")
            return None
        if vs.similarity not in ("cosine", "dot_product", "l2_norm"):
            self._fallback("knn_similarity")
            return None
        qvec = jnp.asarray(np.asarray(query.query_vector, np.float32))
        cut = query.k or query.num_candidates
        cut = int(cut) if cut is not None and int(cut) < nd else 0
        quantized = vs.dtype != jnp.float32
        patch_ids = np.zeros((n_shards, 1), np.int32) + nd
        patch_vals = np.zeros((n_shards, 1), np.float32)
        if quantized:
            nc = int(query.num_candidates or 3 * (query.k or 1000))
            nc = min(nc, nd)
            ids = readback(
                "parallel.mesh_executor.knn_nominate",
                _mesh_knn_nominate(
                    vs.vectors, vs.sq_norms, vs.has_value, qvec,
                    corpus.mesh, vs.similarity, nc))   # [S, nc]
            patch_ids = np.zeros((n_shards, nc), np.int32)
            patch_vals = np.zeros((n_shards, nc), np.float32)
            for si in range(n_shards):
                vv = vs.hosts[si]
                # pad lanes: unique out-of-range targets (mode="drop")
                patch_ids[si] = nd + np.arange(nc, dtype=np.int32)
                if vv is None:
                    continue
                ids_h = ids[si][ids[si] < vv.vectors.shape[0]]
                from elasticsearch_tpu.ops.vector import (
                    exact_rerank_scores,
                )
                exact = exact_rerank_scores(
                    vv.vectors[ids_h],
                    np.asarray(query.query_vector, np.float32),
                    vs.similarity)
                patch_ids[si, :len(ids_h)] = ids_h
                patch_vals[si, :len(ids_h)] = exact
        shard_spec = NamedSharding(corpus.mesh, P("shard"))
        packed = self._launch(
            corpus, "mesh_knn_step",
            lambda: _mesh_knn_step(
                vs.vectors, vs.sq_norms, vs.has_value, corpus.live,
                qvec, jax.device_put(patch_ids, shard_spec),
                jax.device_put(patch_vals, shard_spec), corpus.mesh,
                nd, vs.similarity, float(query.boost), cut, k,
                quantized))
        self.mesh_searches += 1
        self._dispatch("knn")
        return self._unpack_docs(corpus, packed, k)


# Backwards-compatible name (pre-backend sessions): the executor IS the
# backend now.
MeshSearchExecutor = MeshSearchBackend
