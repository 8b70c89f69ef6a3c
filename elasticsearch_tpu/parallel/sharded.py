"""Sharded search execution over a device mesh.

The TPU-native replacement for the reference's scatter-gather protocol
(ref: SURVEY.md §2.3 — an index = N shards, every query fans out to all
shards and the coordinator merges per-shard top-k via
SearchPhaseController.mergeTopDocs / QueryPhaseResultConsumer incremental
reduce). Here the fan-out/merge is a single SPMD program over a
``jax.sharding.Mesh``:

- axis ``"shard"`` — partitions the corpus (postings blocks, doc lengths,
  live masks, vector slabs). The data-parallel axis of a search engine.
- axis ``"replica"`` — partitions the *query batch* (read scaling, the
  replica-count analogue). No communication crosses this axis.

Per device: score local blocks → local top-k; then ONE
``all_gather`` over the shard axis + re-top-k replaces the coordinator's
incremental reduce — the merge rides ICI instead of RPC (BASELINE.json
north star: "TopScoreDocCollector's top-k merge replaced by collectives +
on-device partial sort").

Multi-host note: with a multi-host mesh these same collectives ride
ICI within a host and DCN across hosts — the jit program is unchanged;
only the Mesh changes (jax.sharding semantics).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticsearch_tpu.index.segment import BLOCK_SIZE
from jax import shard_map


# int32 global-id ceiling: with x64 off, `ids + shard * nd` computes in
# int32 and jnp.int64 requests silently narrow (JAX warns and truncates).
# Past this, the merge runs host-side in real int64 instead (exact).
GID_INT32_LIMIT = 2 ** 31


def _gids_exceed_int32(index: "ShardedIndex") -> bool:
    if jax.config.jax_enable_x64:
        return False
    if index.n_shards * index.n_docs_padded < GID_INT32_LIMIT:
        return False
    import logging
    logging.getLogger(__name__).warning(
        "sharded merge: %d shards x %d padded docs >= 2^31 with x64 "
        "disabled — global ids would wrap in int32; falling back to the "
        "host-side int64 merge", index.n_shards, index.n_docs_padded)
    return True


def _host_merge_topk(vals: np.ndarray, ids: np.ndarray, nd: int, k: int):
    """Merge per-shard local top-k [S, Q, k] host-side with exact int64
    global ids (the overflow-safe replacement for the on-device
    all_gather merge)."""
    s, q, kk = vals.shape
    gids = ids.astype(np.int64) + \
        (np.arange(s, dtype=np.int64)[:, None, None] * np.int64(nd))
    vv = vals.transpose(1, 0, 2).reshape(q, s * kk)
    gg = gids.transpose(1, 0, 2).reshape(q, s * kk)
    order = np.argsort(-vv, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(vv, order, axis=1),
            np.take_along_axis(gg, order, axis=1))


def _local_bm25_topk_all_shards(index: "ShardedIndex", sel_blocks,
                                sel_weights, k, k1, b):
    """Per-shard local top-k [S, Q, k] with LOCAL ids (no global-id
    arithmetic on device)."""
    step = jax.vmap(partial(
        _shard_bm25_topk_local, nd=index.n_docs_padded,
        avg_len=index.avg_len, k1=k1, b=b, k=k))
    return step(index.block_docids, index.block_tfs, index.doc_lens,
                index.live, jnp.asarray(sel_blocks),
                jnp.asarray(sel_weights))


def _local_knn_topk_all_shards(index: "ShardedIndex", queries, k):
    q = jnp.asarray(queries)

    def one(vectors, live):
        scores = jnp.einsum("qd,nd->qn", q.astype(vectors.dtype),
                            vectors, preferred_element_type=jnp.float32)
        masked = jnp.where(live[None, :], scores, -jnp.inf)
        return jax.lax.top_k(masked, k)

    return jax.vmap(one)(index.vectors, index.live)


def make_mesh(n_shards: Optional[int] = None, n_replicas: int = 1,
              devices=None) -> Mesh:
    """A ("replica", "shard") mesh over the available devices."""
    devices = list(devices if devices is not None else jax.devices())
    if n_shards is None:
        n_shards = len(devices) // n_replicas
    grid = np.array(devices[: n_replicas * n_shards]).reshape(
        n_replicas, n_shards)
    return Mesh(grid, ("replica", "shard"))


class ShardedIndex:
    """Corpus state laid out for a mesh: every per-shard array stacked on a
    leading shard axis and device_put with the shard-axis sharding.

    Built from per-shard (postings-style) numpy arrays padded to a common
    shape. The stacked arrays live distributed — each device holds only its
    own shard's slice (the HBM analogue of one Lucene shard per node).
    """

    def __init__(self, mesh: Mesh,
                 block_docids: np.ndarray,   # [S, TB, B] int32
                 block_tfs: np.ndarray,      # [S, TB, B] float32
                 doc_lens: np.ndarray,       # [S, ND] float32
                 live: np.ndarray,           # [S, ND] bool
                 avg_len: float,
                 vectors: Optional[np.ndarray] = None,  # [S, ND, D]
                 ):
        self.mesh = mesh
        shard_spec = NamedSharding(mesh, P("shard"))
        self.block_docids = jax.device_put(block_docids, shard_spec)
        self.block_tfs = jax.device_put(block_tfs, shard_spec)
        self.doc_lens = jax.device_put(doc_lens, shard_spec)
        self.live = jax.device_put(live, shard_spec)
        self.avg_len = float(avg_len)
        self.vectors = (jax.device_put(vectors, shard_spec)
                        if vectors is not None else None)
        self.n_shards = block_docids.shape[0]
        self.n_docs_padded = doc_lens.shape[1]


def sharded_bm25_topk(index: ShardedIndex,
                      sel_blocks: np.ndarray,    # [S, Q, NB] int32 per shard
                      sel_weights: np.ndarray,   # [S, Q, NB] float32
                      k: int, k1: float = 1.2, b: float = 0.75):
    """Batched sharded BM25 top-k: every shard scores its local postings
    for all Q queries, local top-k, all-gather + merge over the shard axis.

    Returns (scores [Q, k], global_docids [Q, k]) where global docid =
    shard_idx * n_docs_padded + local docid. Results replicated.
    """
    if _gids_exceed_int32(index):
        vals, ids = _local_bm25_topk_all_shards(
            index, sel_blocks, sel_weights, k, k1, b)
        return _host_merge_topk(np.asarray(vals), np.asarray(ids),
                                index.n_docs_padded, k)
    mesh = index.mesh
    nd = index.n_docs_padded

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                       P("shard", "replica"), P("shard", "replica")),
             out_specs=(P("replica"), P("replica")))
    def step(docids, tfs, lens, live, sel, ws):
        # corpus varies over "shard"; the query batch (dim 1 of sel/ws)
        # splits over "replica" — read scaling with zero cross-replica comm
        # leading shard axis is size 1 inside the shard_map body
        docids, tfs, lens, live = docids[0], tfs[0], lens[0], live[0]
        sel, ws = sel[0], ws[0]

        vals, ids = _shard_bm25_topk_local(
            docids, tfs, lens, live, sel, ws, nd, index.avg_len,
            k1, b, k)                                       # [Q, k]
        shard_idx = jax.lax.axis_index("shard")
        # global ids widen to int64 only under x64 (shard*nd can pass
        # 2^31 at many-shard scale); x64-off deployments stay int32 —
        # requesting int64 there just truncates with a warning
        gid_t = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        gids = ids.astype(gid_t) + shard_idx.astype(gid_t) * nd
        # merge across shards: all_gather over ICI, re-top-k on device
        return _merge_over_shards(vals, gids, k)

    return step(index.block_docids, index.block_tfs, index.doc_lens,
                index.live, jnp.asarray(sel_blocks), jnp.asarray(sel_weights))


def sharded_knn_topk(index: ShardedIndex,
                     queries: np.ndarray,   # [Q, D] float32
                     k: int):
    """Sharded brute-force kNN: queries replicated, vector slab sharded
    over "shard" — per-shard MXU matmul + local top-k + all-gather merge
    (the dense analogue of the per-shard query phase)."""
    if _gids_exceed_int32(index):
        vals, ids = _local_knn_topk_all_shards(index, queries, k)
        return _host_merge_topk(np.asarray(vals), np.asarray(ids),
                                index.n_docs_padded, k)
    mesh = index.mesh
    nd = index.n_docs_padded

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard"), P("replica")),
             out_specs=(P("replica"), P("replica")))
    def step(vectors, live, q):
        vectors, live = vectors[0], live[0]
        scores = jnp.einsum("qd,nd->qn", q.astype(vectors.dtype), vectors,
                            preferred_element_type=jnp.float32)
        masked = jnp.where(live[None, :], scores, -jnp.inf)
        vals, ids = jax.lax.top_k(masked, k)                 # [Q, k]
        shard_idx = jax.lax.axis_index("shard")
        # global ids widen to int64 only under x64 (shard*nd can pass
        # 2^31 at many-shard scale); x64-off deployments stay int32 —
        # requesting int64 there just truncates with a warning
        gid_t = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        gids = ids.astype(gid_t) + shard_idx.astype(gid_t) * nd
        all_vals = jax.lax.all_gather(vals, "shard", axis=1)
        all_gids = jax.lax.all_gather(gids, "shard", axis=1)
        qn = all_vals.shape[0]
        top_vals, top_idx = jax.lax.top_k(all_vals.reshape(qn, -1), k)
        top_gids = jnp.take_along_axis(all_gids.reshape(qn, -1), top_idx, axis=1)
        return top_vals, top_gids

    return step(index.vectors, index.live, jnp.asarray(queries))


def _shard_bm25_topk_local(docids, tfs, lens, live, sel, ws, nd,
                           avg_len, k1, b, k):
    """Per-shard batched BM25 local top-k [Q, k] (the shared body of the
    sharded BM25 and hybrid kernels)."""
    def score_one(sel_q, ws_q):
        d = jnp.take(docids, sel_q, axis=0)
        tf = jnp.take(tfs, sel_q, axis=0)
        dl = jnp.take(lens, d)
        norm = k1 * (1.0 - b + b * dl / avg_len)
        contrib = ws_q[:, None] * jnp.where(tf > 0, tf / (tf + norm), 0.0)
        scores = jnp.zeros(nd, jnp.float32).at[d.reshape(-1)].add(
            contrib.reshape(-1), mode="drop")
        masked = jnp.where(live & (scores > 0), scores, -jnp.inf)
        return jax.lax.top_k(masked, k)

    return jax.vmap(score_one)(sel, ws)


def _merge_over_shards(vals, gids, k):
    """all_gather over the shard axis + re-top-k (the on-device
    coordinator merge shared by every sharded kernel)."""
    av = jax.lax.all_gather(vals, "shard", axis=1)
    ag = jax.lax.all_gather(gids, "shard", axis=1)
    q = av.shape[0]
    tv, ti = jax.lax.top_k(av.reshape(q, -1), k)
    return tv, jnp.take_along_axis(ag.reshape(q, -1), ti, axis=1)


def sharded_hybrid_rrf(index: ShardedIndex,
                       sel_blocks: np.ndarray,    # [S, Q, NB] int32
                       sel_weights: np.ndarray,   # [S, Q, NB] float32
                       queries: np.ndarray,       # [Q, D] float32
                       k: int, k1: float = 1.2, b: float = 0.75,
                       rank_constant: int = 60):
    """Hybrid BM25 + kNN with reciprocal rank fusion, fully on-mesh
    (BASELINE.md config 5 at multi-chip scale): each shard scores both
    branches locally, the per-branch top-k merges over the shard axis
    via all_gather, and the RRF fusion — a segmented sum of 1/(c+rank)
    contributions keyed by global docid — reuses ops/bm25.py's
    segmented_topk (no host round-trips). The query batch splits over
    the replica axis like the sibling kernels (read scaling).

    Returns (rrf_scores [Q, k], global_docids [Q, k]), replica-sharded
    over Q."""
    from elasticsearch_tpu.ops.bm25 import segmented_topk

    if _gids_exceed_int32(index):
        # host fusion over the overflow-safe per-branch merges
        b_vals, b_gids = sharded_bm25_topk(index, sel_blocks,
                                           sel_weights, k, k1, b)
        v_vals, v_gids = sharded_knn_topk(index, queries, k)
        c = float(rank_constant)
        q_n = np.asarray(b_vals).shape[0]
        out_v = np.zeros((q_n, k), np.float32)
        out_g = np.zeros((q_n, k), np.int64)
        for qi in range(q_n):
            fused: Dict[int, float] = {}
            for vals, gids in ((np.asarray(b_vals)[qi],
                                np.asarray(b_gids)[qi]),
                               (np.asarray(v_vals)[qi],
                                np.asarray(v_gids)[qi])):
                for rank, (v, g) in enumerate(zip(vals, gids)):
                    if np.isfinite(v):
                        fused[int(g)] = fused.get(int(g), 0.0) + \
                            1.0 / (c + rank + 1.0)
            top = sorted(fused.items(), key=lambda e: (-e[1], e[0]))[:k]
            for j, (g, v) in enumerate(top):
                out_v[qi, j] = v
                out_g[qi, j] = g
        return out_v, out_g

    mesh = index.mesh
    nd = index.n_docs_padded
    c = float(rank_constant)

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard"), P("shard"), P("shard"),
                       P("shard"), P("shard", "replica"),
                       P("shard", "replica"), P("replica")),
             out_specs=(P("replica"), P("replica")))
    def step(docids, tfs, lens, live, vectors, sel, ws, qv):
        docids, tfs, lens, live = docids[0], tfs[0], lens[0], live[0]
        vectors = vectors[0]
        sel, ws = sel[0], ws[0]

        b_vals, b_ids = _shard_bm25_topk_local(
            docids, tfs, lens, live, sel, ws, nd, index.avg_len,
            k1, b, k)                                        # [Q, k]
        v_scores = jnp.einsum("qd,nd->qn", qv.astype(vectors.dtype),
                              vectors,
                              preferred_element_type=jnp.float32)
        v_masked = jnp.where(live[None, :], v_scores, -jnp.inf)
        v_vals, v_ids = jax.lax.top_k(v_masked, k)           # [Q, k]

        shard_idx = jax.lax.axis_index("shard")
        off = shard_idx.astype(jnp.int64) * nd
        b_gids = b_ids.astype(jnp.int64) + off
        v_gids = v_ids.astype(jnp.int64) + off

        gb_vals, gb_gids = _merge_over_shards(b_vals, b_gids, k)
        gv_vals, gv_gids = _merge_over_shards(v_vals, v_gids, k)

        # RRF contributions: 1/(c + rank + 1); empty slots contribute 0
        ranks = jnp.arange(k, dtype=jnp.float32)
        rc = 1.0 / (c + ranks + 1.0)

        def fuse_one(bg, bvals, vg, vvals):
            gids = jnp.concatenate([bg, vg])
            contrib = jnp.concatenate([
                jnp.where(jnp.isfinite(bvals), rc, 0.0),
                jnp.where(jnp.isfinite(vvals), rc, 0.0)])
            # dtype-safe sentinel: int64 narrows to int32 when x64 is off
            sentinel = jnp.asarray(jnp.iinfo(gids.dtype).max, gids.dtype)
            key = jnp.where(contrib > 0, gids, sentinel)
            return segmented_topk(key, contrib, k, sentinel)

        return jax.vmap(fuse_one)(gb_gids, gb_vals, gv_gids, gv_vals)

    return step(index.block_docids, index.block_tfs, index.doc_lens,
                index.live, index.vectors, jnp.asarray(sel_blocks),
                jnp.asarray(sel_weights), jnp.asarray(queries))


def sharded_dfs_stats(index: ShardedIndex,
                      sel_blocks: np.ndarray,   # [S, NB]
                      ) -> jax.Array:
    """The DFS phase analogue (ref: search/dfs/DfsPhase.java — all-shard
    term-statistics gather for consistent IDF): per-shard doc-freq counts
    psum'd over the shard axis."""
    mesh = index.mesh

    @partial(shard_map, mesh=mesh, check_vma=False,
             in_specs=(P("shard"), P("shard")),
             out_specs=P())
    def step(tfs, sel):
        tfs, sel = tfs[0], sel[0]
        t = jnp.take(tfs, sel, axis=0)           # [NB, B]
        local_df = (t > 0).sum(axis=1)           # per selected block
        return jax.lax.psum(local_df, "shard")

    return step(index.block_tfs, jnp.asarray(sel_blocks))


def build_sharded_index(mesh: Mesh, segments: List, field: str,
                        with_vectors: Optional[str] = None) -> Tuple[ShardedIndex, List]:
    """Stack per-shard segments (padded to common shapes) into a
    ShardedIndex. segments: one Segment per shard (shards beyond
    len(segments) are empty)."""
    s = mesh.shape["shard"]
    pfs = [seg.postings.get(field) for seg in segments]
    max_tb = max((pf.block_docids.shape[0] for pf in pfs if pf is not None),
                 default=0) + 1  # +1 zero pad block
    max_nd = max((seg.n_docs for seg in segments), default=1)
    max_nd = ((max_nd + 1023) // 1024) * 1024

    block_docids = np.zeros((s, max_tb, BLOCK_SIZE), np.int32)
    block_tfs = np.zeros((s, max_tb, BLOCK_SIZE), np.float32)
    doc_lens = np.ones((s, max_nd), np.float32)
    live = np.zeros((s, max_nd), bool)
    total_len = 0.0
    total_docs = 0
    for i, seg in enumerate(segments[:s]):
        pf = seg.postings.get(field)
        if pf is None:
            continue
        tb = pf.block_docids.shape[0]
        block_docids[i, :tb] = pf.block_docids
        block_tfs[i, :tb] = pf.block_tfs
        doc_lens[i, : seg.n_docs] = np.maximum(pf.field_lengths, 1.0)
        live[i, : seg.n_docs] = seg.live
        total_len += pf.field_lengths.sum()
        total_docs += pf.doc_count

    vectors = None
    if with_vectors is not None:
        dims = next(seg.vectors[with_vectors].dims for seg in segments
                    if with_vectors in seg.vectors)
        vectors = np.zeros((s, max_nd, dims), np.float32)
        for i, seg in enumerate(segments[:s]):
            vv = seg.vectors.get(with_vectors)
            if vv is not None:
                from elasticsearch_tpu.ops.vector import prepare_vectors
                prepped, _ = prepare_vectors(vv.vectors, vv.similarity,
                                             np.float32)
                vectors[i, : len(prepped)] = prepped

    avg_len = total_len / max(1, total_docs)
    return ShardedIndex(mesh, block_docids, block_tfs, doc_lens, live,
                        avg_len, vectors), pfs
