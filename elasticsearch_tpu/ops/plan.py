"""Fused query-plan top-k kernel — the serving-path hot loop.

This is the TPU replacement for Lucene's BooleanQuery/ConjunctionDISI
scoring stack (ref: search/internal/ContextIndexSearcher.java:196-232 —
per-segment ``BulkScorer.score``; BooleanWeight/ConjunctionDISI iterator
trees). Instead of executing each clause into a dense [ND] score/mask pair
via scatter (XLA scatter-add serializes on TPU — measured ~70ms/launch,
see ops/bm25.py), the whole boolean tree executes as ONE sorted
segmented-reduction program over the query's postings:

  1. gather the selected postings blocks of every scoring/filtering clause
     (gathers vectorize), tagging each posting with (group, subgroup) ids —
     a "group" is one bool clause (a match query, a term filter, …), a
     "subgroup" one term within it;
  2. sort (docid, group, subgroup, contribution) lexicographically
     (`lax.sort` — bitonic on the VPU);
  3. segmented reductions over the sorted runs compute, per (doc, group):
     distinct-subgroup counts (minimum_should_match / operator=and inside a
     clause) and summed BM25 contributions; then per doc: which groups are
     present, must/filter/should satisfaction, must_not exclusion, and the
     combined score (sum or dis-max);
  4. dense, vectorized column predicates (range/exists/numeric-term — no
     scatter anywhere in their construction) enter as one gathered
     ``dense_mask`` lookup;
  5. `lax.top_k` over the per-doc run totals yields (scores, docids) and an
     exact matching-doc count, with NO dense [ND] accumulator in the path.

Cost is O(P log P) in the query's postings count P — corpus-size
independent, like Lucene's skip-list iteration, but branch-free and
batchable (vmap over queries = continuous batching, SURVEY.md §7 hard
part 5).

Group kinds mirror the bool query's occur classes (ref:
BoolQueryBuilder / Lucene BooleanClause.Occur).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.telemetry.engine import tracked_jit

MUST = 0
SHOULD = 1
FILTER = 2
MUST_NOT = 3

_SENTINEL = 0x7FFFFFFF  # padding docid; sorts after every real docid

# float32 represents every integer < 2^24 exactly — the ceiling for ids
# that ride packed readbacks as float casts (pack_result). Segment doc
# counts sit far below it; the mesh path's GLOBAL ids (shard * nd_padded
# + docid) can approach it at many-shard scale and must fall back to the
# per-shard RPC merge instead of silently losing low bits.
PACKED_ID_LIMIT = 1 << 24


def check_packed_id_limit(nd: int, where: str) -> None:
    """Enforce the ``nd < 2^24`` float-pack invariant loudly at build /
    register time (a violation later would corrupt docids silently)."""
    if nd >= PACKED_ID_LIMIT:
        raise ValueError(
            f"{where}: {nd} docs (padded) >= 2^24 — float32-packed "
            f"readback ids would lose precision; shard the corpus "
            f"further (ops/plan.py pack_result invariant)")


class FieldStream(NamedTuple):
    """One field's postings selection for a query plan.

    Device-resident corpus arrays plus the per-query selection: block ids
    and, per selected block, the owning (group, subgroup), the scoring
    weight (idf·boost), and whether the clause scores constant-per-match
    (keyword term semantics: Lucene keyword fields index no norms, score =
    idf·tf/(tf+k1) with tf=1) instead of full BM25.
    """

    block_docids: jax.Array   # int32 [TB+1, B] (with reserved zero block)
    block_tfs: jax.Array      # float32 [TB+1, B]
    doc_lens: jax.Array       # float32 [ND]
    avg_len: jax.Array        # float32 scalar (shard-level stat)
    sel_blocks: jax.Array     # int32 [NB]
    sel_group: jax.Array      # int32 [NB]
    sel_sub: jax.Array        # int32 [NB]
    sel_weight: jax.Array     # float32 [NB]
    sel_const: jax.Array      # bool [NB] — constant-score contribution


def _prev(x: jax.Array, fill) -> jax.Array:
    return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])


def _segsum(x: jax.Array, is_start: jax.Array) -> jax.Array:
    """Inclusive prefix sums within runs delimited by ``is_start``.

    A float32 prefix sum over the WHOLE selection carries an absolute
    error of ~2^-24 times the running prefix, so a plain ``cumsum``
    minus the run-start prefix moved scores by ~1e-4 relative at corpus
    scale and reordered boundary docs. The per-step rounding errors of
    the prefix sum are recovered exactly (``x - (cs - cs_prev)``; the
    subtraction of neighbouring prefixes is exact) and summed on their
    own, much smaller, scale, so each run sum is accurate to a few ulps
    of the run itself. Two cumsums and a gather compile in seconds,
    where a segmented ``associative_scan`` under ``vmap`` took minutes
    on TPU."""
    cs = jnp.cumsum(x)
    prev = _prev(cs, 0.0)
    err = jnp.cumsum(x - (cs - prev))
    start = jax.lax.cummax(jnp.where(
        is_start, jnp.arange(x.shape[0], dtype=jnp.int32), 0))
    return ((cs - jnp.take(prev, start))
            + (err - jnp.take(_prev(err, 0.0), start)))


def _stable_topk(cand, keys, k: int):
    """STABLE top-k of ``cand`` [P] with the exactness contract's tie
    order: ``cand`` is key-ascending-ordered, so keeping the FIRST ties
    at the kth value takes the LOWEST keys (Lucene/CPU-baseline
    semantics — TPU top_k alone breaks ties arbitrarily). Returns
    (vals, ids) in cand's dtype."""
    vals1 = jax.lax.top_k(cand, k)[0]
    kth = vals1[k - 1]
    gt = cand > kth
    eq = cand == kth
    need = k - gt.sum()
    eq_rank = jnp.cumsum(eq.astype(jnp.int32))
    cand2 = jnp.where(gt | (eq & (eq_rank <= need)), cand, -jnp.inf)
    vals, pos = jax.lax.top_k(cand2, k)
    ids = jnp.take(keys, pos)
    ids = jnp.where(jnp.isfinite(vals), ids, _SENTINEL)
    return vals, ids


def _segmax(x: jax.Array, is_start: jax.Array) -> jax.Array:
    """Inclusive prefix max within runs (associative segmented-max scan)."""

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.maximum(va, vb))

    _, out = jax.lax.associative_scan(comb, (is_start, x))
    return out


def plan_topk_body(streams: Tuple[FieldStream, ...],
                   group_kind: jax.Array,    # int32 [G]
                   group_req: jax.Array,     # int32 [G]
                   group_const: jax.Array,   # float32 [G]; NaN = sum contribs
                   live: jax.Array,          # bool [ND]
                   dense_mask: jax.Array,    # bool [ND] (all-true if unused)
                   n_must: jax.Array, n_filter: jax.Array, msm: jax.Array,
                   bonus: jax.Array, tie: jax.Array,
                   after_score: jax.Array,   # float32; _score search_after
                   k1: float, b: float, k: int, combine: str,
                   with_dense: bool, with_after: bool = False,
                   script_fn=None):
    """The kernel body, un-jitted: also called from inside shard_map
    (parallel/mesh_executor.py) where the surrounding SPMD program owns
    the jit.

    ``script_fn(score, docids) -> score`` is the script_score transform
    (a stable per-(segment, script) closure over device columns —
    search/plan.py binds it): applied to the combined per-doc score
    before top-k, so expression script_score queries ride this batched
    kernel instead of the per-request dense path (BASELINE config 3
    through the product path)."""
    parts_d, parts_tf, parts_c, parts_g, parts_s = [], [], [], [], []
    for st in streams:
        d = jnp.take(st.block_docids, st.sel_blocks, axis=0)    # [NB, B]
        tf = jnp.take(st.block_tfs, st.sel_blocks, axis=0)
        dl = jnp.take(st.doc_lens, d)
        norm = k1 * (1.0 - b + b * dl / st.avg_len)
        hit = tf > 0.0
        bm25 = st.sel_weight[:, None] * jnp.where(hit, tf / (tf + norm), 0.0)
        contrib = jnp.where(st.sel_const[:, None],
                            jnp.where(hit, st.sel_weight[:, None], 0.0), bm25)
        parts_d.append(d.reshape(-1))
        parts_tf.append(tf.reshape(-1))
        parts_c.append(contrib.reshape(-1))
        parts_g.append(jnp.broadcast_to(
            st.sel_group[:, None], d.shape).reshape(-1))
        parts_s.append(jnp.broadcast_to(
            st.sel_sub[:, None], d.shape).reshape(-1))

    d_all = jnp.concatenate(parts_d)
    tf_all = jnp.concatenate(parts_tf)
    c_all = jnp.concatenate(parts_c)
    g_all = jnp.concatenate(parts_g)
    s_all = jnp.concatenate(parts_s)

    nd = live.shape[0]
    valid = (tf_all > 0.0) & jnp.take(live, jnp.clip(d_all, 0, nd - 1))
    dkey = jnp.where(valid, d_all, _SENTINEL)
    c_all = jnp.where(valid, c_all, 0.0)

    dkey, g, s, c = jax.lax.sort((dkey, g_all, s_all, c_all), num_keys=3)

    new_doc = dkey != _prev(dkey, -1)
    new_grp = new_doc | (g != _prev(g, -1))
    new_sub = new_grp | (s != _prev(s, -1))
    is_grp_last = jnp.concatenate([new_grp[1:], jnp.ones(1, bool)])
    is_doc_last = jnp.concatenate([new_doc[1:], jnp.ones(1, bool)])

    # per-(doc, group): distinct subgroups matched + summed contribution
    sub_cnt = _segsum(new_sub.astype(jnp.float32), new_grp)
    grp_score = _segsum(c, new_grp)

    ng = group_kind.shape[0]
    gc = jnp.clip(g, 0, ng - 1)
    kind = jnp.take(group_kind, gc)
    req = jnp.take(group_req, gc)
    cval = jnp.take(group_const, gc)
    present = is_grp_last & (sub_cnt >= req.astype(jnp.float32))
    gscore = jnp.where(jnp.isnan(cval), grp_score, cval)
    scoring = (kind == MUST) | (kind == SHOULD)

    score_in = jnp.where(present & scoring, gscore, 0.0)
    must_in = (present & (kind == MUST)).astype(jnp.float32)
    filt_in = (present & (kind == FILTER)).astype(jnp.float32)
    should_in = (present & (kind == SHOULD)).astype(jnp.float32)
    mnot_in = (present & (kind == MUST_NOT)).astype(jnp.float32)

    doc_score = _segsum(score_in, new_doc)
    doc_must = _segsum(must_in, new_doc)
    doc_filt = _segsum(filt_in, new_doc)
    doc_should = _segsum(should_in, new_doc)
    doc_mnot = _segsum(mnot_in, new_doc)

    if combine == "dismax":
        mx_in = jnp.where(present & scoring, gscore, -jnp.inf)
        doc_max = _segmax(mx_in, new_doc)
        score = jnp.where(jnp.isfinite(doc_max),
                          doc_max + tie * (doc_score - doc_max), 0.0)
    else:
        score = doc_score
    score = score + bonus
    if script_fn is not None:
        score = jnp.asarray(
            script_fn(score, jnp.clip(dkey, 0, nd - 1)), score.dtype)

    passed = (is_doc_last & (dkey != _SENTINEL)
              & (doc_must >= n_must.astype(jnp.float32))
              & (doc_filt >= n_filter.astype(jnp.float32))
              & (doc_should >= msm.astype(jnp.float32))
              & (doc_mnot == 0.0))
    if with_dense:
        passed = passed & jnp.take(dense_mask, jnp.clip(dkey, 0, nd - 1))
    if with_after:
        # search_after on _score: strictly-after the cursor; ties excluded
        # (as in the dense executor — reliable tie paging needs a trailing
        # _doc key, which implies a sort spec and the dense path)
        passed = passed & (score < after_score)

    cand = jnp.where(passed, score, -jnp.inf)
    if k > cand.shape[0]:
        pad = k - cand.shape[0]
        cand = jnp.concatenate([cand, jnp.full(pad, -jnp.inf)])
        dkey = jnp.concatenate(
            [dkey, jnp.full(pad, _SENTINEL, dkey.dtype)])
    # cand is in docid order: ties at the k-th score keep the lowest
    # docids, as Lucene does (TPU top_k alone breaks ties arbitrarily)
    vals, ids = _stable_topk(cand, dkey, k)
    total = jnp.sum(passed.astype(jnp.int32))
    return vals, ids, total


_plan_topk_impl = tracked_jit(
    "plan_topk", static_argnames=("k", "combine", "k1", "b", "with_dense",
                                  "with_after", "script_fn"))(plan_topk_body)


def pack_result(vals: jax.Array, ids: jax.Array,
                total: jax.Array) -> jax.Array:
    """Pack (vals [k] f32, ids [k] i32, total i32) into ONE [2k+1] f32
    buffer: one device→host readback per launch instead of three.

    Ints ride as FLOAT CASTS, not bitcasts: float32 represents every
    integer < 2^24 exactly (doc ids and totals are bounded by segment
    doc count, << 2^24). Bitcast packing was dropped in round 5 after an
    earlier runtime read multi-bitcast concats back as zeros; whether
    the chip needs the casts is ROADMAP debt D5. The sentinel id
    (2^31-1) is not f32-exact; ``unpack_ids`` clips it."""
    return jnp.concatenate([
        vals.astype(jnp.float32),
        ids.astype(jnp.float32),
        jnp.reshape(total, (1,)).astype(jnp.float32),
    ])


def unpack_ids(buf: np.ndarray) -> np.ndarray:
    """Float-packed int lanes -> int32, sentinel-safe. The cast ORDER
    is load-bearing: the sentinel rides as 2^31 exactly, which float32
    CAN represent but int32 can't — a direct cast is UB, and np.clip
    in f32 can't even express 2^31-1. int64 first, then clip, then
    narrow. Every packed-readback unpacker must go through this."""
    return np.clip(buf.astype(np.int64), 0, 0x7FFFFFFF).astype(np.int32)


def unpack_result(buf: np.ndarray, k: int):
    """Host-side inverse of pack_result on an np.float32 [2k+1] row."""
    vals = buf[:k]
    ids = unpack_ids(buf[k:2 * k])
    total = int(buf[2 * k])
    return vals, ids, total


def _plan_topk_packed_body(streams, group_kind, group_req, group_const,
                           live, dense_mask, n_must, n_filter, msm,
                           bonus, tie, after_score, k1, b, k, combine,
                           with_dense, with_after=False, script_fn=None):
    return pack_result(*plan_topk_body(
        streams, group_kind, group_req, group_const, live, dense_mask,
        n_must, n_filter, msm, bonus, tie, after_score, k1, b, k,
        combine, with_dense, with_after, script_fn))


_plan_topk_packed_impl = tracked_jit(
    "plan_topk_packed",
    static_argnames=("k", "combine", "k1", "b", "with_dense",
                     "with_after", "script_fn"))(_plan_topk_packed_body)


def plan_topk(streams, group_kind, group_req, group_const, live,
              dense_mask: Optional[jax.Array],
              n_must: int, n_filter: int, msm: int,
              bonus: float = 0.0, tie: float = 0.0,
              k1: float = 1.2, b: float = 0.75, k: int = 10,
              combine: str = "sum",
              after_score: Optional[float] = None,
              packed: bool = False, script_fn=None):
    """Single-query entry. ``dense_mask=None`` skips the gather entirely
    (the common pure-postings case compiles without it). ``packed=True``
    returns ONE [2k+1] device buffer (see pack_result) for single-readback
    serving."""
    with_dense = dense_mask is not None
    if not with_dense:
        dense_mask = jnp.ones(1, bool)  # placeholder, not read
    with_after = after_score is not None
    impl = _plan_topk_packed_impl if packed else _plan_topk_impl
    return impl(
        tuple(streams), np.asarray(group_kind, np.int32),
        np.asarray(group_req, np.int32),
        np.asarray(group_const, np.float32), live, dense_mask,
        np.int32(n_must), np.int32(n_filter), np.int32(msm),
        np.float32(bonus), np.float32(tie),
        np.float32(after_score if with_after else 0.0),
        float(k1), float(b), int(k), combine, with_dense, with_after,
        script_fn)


@tracked_jit("plan_topk_batch",
             static_argnames=("k", "combine", "k1", "b", "with_dense",
                              "script_fn"))
def _plan_topk_batch_impl(streams, group_kind, group_req, group_const,
                          live, dense_mask, n_must, n_filter, msm,
                          bonus, tie, k1, b, k, combine, with_dense,
                          script_fn=None):
    """vmap over the query axis of the selection/group arrays; corpus
    arrays are shared (in_axes=None), and so is the optional dense
    filter mask — cohorts are keyed by filter identity (the cached
    composed column), so one [ND] mask serves the whole batch with no
    per-query stacking."""

    def one(sel_blocks, sel_group, sel_sub, sel_weight, sel_const,
            gk, gr, gcst, nm, nf, ms, bo, ti):
        sts = tuple(
            FieldStream(st.block_docids, st.block_tfs, st.doc_lens,
                        st.avg_len, sb, sg, ss, sw, sc)
            for st, sb, sg, ss, sw, sc in zip(
                streams, sel_blocks, sel_group, sel_sub, sel_weight,
                sel_const))
        return pack_result(*plan_topk_body(
            sts, gk, gr, gcst, live, dense_mask,
            nm, nf, ms, bo, ti, jnp.float32(0.0),
            k1, b, k, combine, with_dense, script_fn=script_fn))

    sel_b = tuple(st.sel_blocks for st in streams)   # each [Q, NB]
    sel_g = tuple(st.sel_group for st in streams)
    sel_s = tuple(st.sel_sub for st in streams)
    sel_w = tuple(st.sel_weight for st in streams)
    sel_c = tuple(st.sel_const for st in streams)
    return jax.vmap(one)(sel_b, sel_g, sel_s, sel_w, sel_c,
                         group_kind, group_req, group_const,
                         n_must, n_filter, msm, bonus, tie)


def plan_topk_batch(streams, group_kind, group_req, group_const, live,
                    n_must, n_filter, msm, bonus, tie,
                    k1: float = 1.2, b: float = 0.75, k: int = 10,
                    combine: str = "sum", dense_mask=None,
                    script_fn=None):
    """Batched entry: every per-query array has a leading [Q] axis; the
    corpus arrays inside ``streams`` stay unbatched (shared), as is the
    optional [ND] ``dense_mask`` (one filter column for the whole
    cohort). Returns PACKED [Q, 2k+1] rows (pack_result) — one readback
    serves the whole batch. This is the continuous-batching launch
    shape (SURVEY.md §7 hard part 5)."""
    with_dense = dense_mask is not None
    if not with_dense:
        dense_mask = jnp.ones(1, bool)   # placeholder, not read
    return _plan_topk_batch_impl(
        tuple(streams), np.asarray(group_kind, np.int32),
        np.asarray(group_req, np.int32),
        np.asarray(group_const, np.float32), live, dense_mask,
        np.asarray(n_must, np.int32), np.asarray(n_filter, np.int32),
        np.asarray(msm, np.int32), np.asarray(bonus, np.float32),
        np.asarray(tie, np.float32),
        float(k1), float(b), int(k), combine, with_dense, script_fn)


@tracked_jit("plan_topk_mesh",
             static_argnames=("mesh", "nd", "n_must", "n_filter", "msm",
                              "tie", "k1", "b", "k", "combine"))
def plan_topk_mesh(streams, group_kind, group_req, group_const, bonus,
                   live, mesh, nd: int, n_must: int, n_filter: int,
                   msm: int, tie: float, k1: float, b: float, k: int,
                   combine: str):
    """ONE SPMD program for a multi-shard query over a device mesh: the
    TransportSearchAction scatter-gather re-expressed as collectives.

    Every input carries a leading shard axis, sharded ``P("shard")``
    (parallel/mesh_executor.py stacks per-shard selections/corpora this
    way); each device scores its own shard with :func:`plan_topk_body`,
    then ONE ``all_gather`` over the shard axis + on-device re-top-k
    replaces the coordinator merge and a ``psum`` the total-hits
    accumulation. Returns a replicated packed [2k+1] buffer
    (:func:`pack_result`) — one readback for the whole mesh query.

    Global ids are ``shard * nd + local`` in int32: the packed float
    readback bounds them below ``PACKED_ID_LIMIT`` (2^24), enforced by
    the caller, so int32 can never overflow here."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    in_specs = (tuple(FieldStream(*([P("shard")] * 9)) for _ in streams),
                P("shard"), P("shard"), P("shard"), P("shard"),
                P("shard"))

    @shard_map(mesh=mesh, check_vma=False, in_specs=in_specs,
               out_specs=P())
    def step(sts, gk, gr, gc, bo, lv):
        local = tuple(
            FieldStream(st.block_docids[0], st.block_tfs[0],
                        st.doc_lens[0], st.avg_len[0],
                        st.sel_blocks[0], st.sel_group[0],
                        st.sel_sub[0], st.sel_weight[0],
                        st.sel_const[0])
            for st in sts)
        vals, ids, total = plan_topk_body(
            local, gk[0], gr[0], gc[0], lv[0], jnp.ones(1, bool),
            jnp.int32(n_must), jnp.int32(n_filter), jnp.int32(msm),
            bo[0], jnp.float32(tie), jnp.float32(0.0),
            k1, b, k, combine, False, False)
        shard_idx = jax.lax.axis_index("shard").astype(jnp.int32)
        gids = jnp.where(ids == _SENTINEL, _SENTINEL,
                         ids + shard_idx * nd)
        # ONE all_gather over ICI + on-device re-top-k = coordinator merge,
        # ordered (score desc, global id asc) like the per-shard loop
        av = jax.lax.all_gather(vals, "shard")        # [S, k]
        ag = jax.lax.all_gather(gids, "shard")
        neg, tg = jax.lax.sort((-av.reshape(-1), ag.reshape(-1)),
                               num_keys=2)
        tv = -neg[:k]
        tg = jnp.where(tv > -jnp.inf, tg[:k], _SENTINEL)
        # pack → one readback for the whole mesh query
        return pack_result(tv, tg, jax.lax.psum(total, "shard"))

    return step(tuple(streams), group_kind, group_req, group_const,
                bonus, live)


# ---------------------------------------------------------------------------
# Impact-ordered block selection (host-side, pure numpy).
#
# Lucene's impact-ordered postings let block-max WAND spend its
# evaluation budget on the blocks with the highest score upper bounds
# instead of the lowest docids (ref: Lucene ImpactsEnum /
# MaxScoreBulkScorer). The TPU analogue: the serving fast path selects
# postings BLOCKS into a fixed lane budget per launch, so WHICH blocks
# enter the budget decides recall-at-budget. These helpers precompute a
# per-block BM25 upper bound at registration (block-max tf × idf, the
# same bound the θ/MaxScore lane derives), order each term's block list
# by descending bound once, and select per query under a budget by
# impact — with the residual bound of everything excluded, so callers
# can run the block-max safe-termination check (no unseen doc can reach
# the kth score) on readback.
#
# Layout convention: term t's blocks occupy the contiguous index range
# [starts[t], starts[t]+counts[t]) of the block arrays, docid-ascending
# by block index. ``order``/``ub_desc`` use the SAME flat layout, but
# within each term's range the entries are impact-sorted: position
# starts[t]+j holds the block id (resp. bound) of t's (j+1)-th
# highest-impact block.
# ---------------------------------------------------------------------------


class TermImpacts(NamedTuple):
    """Registration-time impact metadata for one postings field."""

    ub: np.ndarray        # float64 [TB] per-block score upper bound
    order: np.ndarray     # int32 [TB] impact-sorted block ids per term
    ub_desc: np.ndarray   # float64 [TB] bounds in `order`'s layout


def build_term_impacts(starts, counts, block_max_tf, block_min_len,
                       idf, avg_len: float, k1: float,
                       b: float) -> TermImpacts:
    """Per-block BM25 upper bounds + per-term impact ordering.

    The bound is the block-max saturation at the block's minimum length
    times the term's idf — the max contribution ANY doc in the block can
    make (the same quantity the θ-lane's ``maxc`` takes the per-term max
    of). Empty blocks (max tf 0) bound to 0."""
    starts = np.asarray(starts, np.int64)
    counts = np.asarray(counts, np.int64)
    mtf = np.asarray(block_max_tf, np.float64)
    mln = np.asarray(block_min_len, np.float64)
    sat = np.where(mtf > 0,
                   mtf / (mtf + k1 * (1.0 - b + b * mln / avg_len)), 0.0)
    tb = mtf.shape[0]
    # term id owning each block: the packed layout is contiguous and
    # gap-free (segment.py builds starts as the exact cumsum of
    # counts) — enforce loudly, a gap would silently shift every
    # term's impact range (the check_packed_id_limit style)
    if int(counts.sum()) != tb:
        raise ValueError(
            f"packed block layout violated: sum(counts)="
            f"{int(counts.sum())} != n_blocks={tb}")
    term_of = np.repeat(np.arange(len(counts)), counts)
    ub = sat * np.asarray(idf, np.float64)[term_of]
    # impact order per term: argsort of (term, -ub, block) — one global
    # stable sort keeps it vectorized; ties keep docid (block) order
    order = np.lexsort((np.arange(tb), -ub, term_of)).astype(np.int32)
    return TermImpacts(ub=ub, order=order, ub_desc=ub[order])


def select_blocks_impact(term_ids, budget: int, starts, counts,
                         impacts: TermImpacts):
    """Budgeted per-query block selection by descending impact.

    Returns ``(per_term, miss_bound)``: ``per_term`` is a list of int32
    arrays (one per term id, ASCENDING block ids — the slot-sorted
    invariant the merge kernels require), ``miss_bound`` the sum over
    terms of the max bound among that term's EXCLUDED blocks (a doc
    appears in at most one block per term, so no doc's true score can
    exceed its observed score by more than ``miss_bound``; an entirely
    unseen doc is bounded by ``miss_bound`` itself). ``miss_bound`` is
    0.0 exactly when the selection is complete (exact serving)."""
    segs = [(int(starts[t]), int(counts[t])) for t in term_ids]
    total = sum(c for _, c in segs)
    if total <= budget:
        return ([np.arange(s, s + c, dtype=np.int32) for s, c in segs],
                0.0)
    ud = impacts.ub_desc
    cat = np.concatenate([ud[s:s + c] for s, c in segs])
    # threshold = budget-th largest bound; strictly-greater blocks are
    # all in, ties fill the remainder in term order (deterministic)
    thr = np.partition(cat, total - budget)[total - budget]
    n_gt = [int(np.searchsorted(-ud[s:s + c], -thr, side="left"))
            for s, c in segs]
    spare = budget - sum(n_gt)
    per_term: list = []
    miss = 0.0
    for (s, c), j in zip(segs, n_gt):
        # extend through the tie band while budget remains
        while spare > 0 and j < c and ud[s + j] == thr:
            j += 1
            spare -= 1
        take = impacts.order[s:s + j]
        per_term.append(np.sort(take).astype(np.int32))
        if j < c:
            miss += float(ud[s + j])
    return per_term, miss


def select_blocks_prefix(term_ids, budget: int, starts, counts):
    """Posting-order baseline: each term keeps the PREFIX of its block
    list, lowest docids first, dropping tail blocks round-robin until
    the budget fits (the selection a budget-blind path would make).
    Same return convention as :func:`select_blocks_impact` minus the
    bound (callers compare recall, not certificates)."""
    cnts = [int(counts[t]) for t in term_ids]
    while sum(cnts) > budget:
        i = int(np.argmax(cnts))
        over = sum(cnts) - budget
        cnts[i] = max(0, cnts[i] - max(1, min(over, cnts[i] // 2)))
    return [np.arange(int(starts[t]), int(starts[t]) + c, dtype=np.int32)
            for t, c in zip(term_ids, cnts)]


def impact_safe_termination(kth: float, next_best: float,
                            miss_bound: float) -> bool:
    """The block-max safe-termination check on a truncated launch's
    readback: with every doc's possible gain bounded by ``miss_bound``,
    the observed top-k SET is provably the true top-k when the best
    excluded candidate (``next_best``: the (k+1)-th observed score, or
    0.0 when fewer than k+1 docs matched — an unseen doc's observed
    score) cannot close the gap to the kth. Observed scores of the
    returned docs remain lower bounds (callers report totals with
    relation ``gte``)."""
    if miss_bound <= 0.0:
        return True
    if not np.isfinite(kth):
        return False          # fewer than k hits: unseen docs could fill
    floor = max(float(next_best) if np.isfinite(next_best) else 0.0, 0.0)
    return floor + miss_bound < kth


# ---------------------------------------------------------------------------
# Scatter-free dense builders (for the fallback path: aggs need full masks)
# ---------------------------------------------------------------------------

def _unique_scatter_indices(dkey: jax.Array, is_last: jax.Array,
                            nd: int) -> jax.Array:
    """Strictly-unique scatter targets: run-last lanes write their docid,
    every other lane writes a distinct out-of-bounds slot (dropped).
    Guaranteed-unique indices let XLA emit a parallel scatter instead of
    the serialized duplicate-handling form (the ~70ms trap)."""
    lane = jnp.arange(dkey.shape[0], dtype=jnp.int32)
    return jnp.where(is_last & (dkey != _SENTINEL), dkey, nd + lane)


@tracked_jit(static_argnames=("k1", "b", "max_run"))
def bm25_dense_scores_sorted(block_docids, block_tfs, sel_blocks,
                             sel_weights, doc_lens, avg_len,
                             k1: float, b: float, max_run: int = 32):
    """Dense per-doc BM25 scores [ND] via sort + DOUBLING segmented sum
    + ONE unique-index scatter — the scatter-free replacement for
    ops/bm25.bm25_block_scores (whose scatter-add serializes on TPU).
    This is the scorer behind the dense path — every aggs/sort/script
    query rides it (VERDICT r2 item 3: aggs were paying the serialized
    scatter). The doubling scan keeps full f32 accuracy — a global
    cumsum's prefix error reorders boundary docs at corpus scale.

    ``max_run`` must bound the longest per-doc run (= the number of term
    INSTANCES in the selection: one entry per term per doc). Callers
    with unbounded term counts (analyzed match text, fuzzy/wildcard
    expansions) pass ``scan_run_bound(n_terms)`` — a 31-term query under
    the old fixed cap of 32 silently dropped contributions."""
    d = jnp.take(block_docids, sel_blocks, axis=0)
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    dl = jnp.take(doc_lens, d)
    norm = k1 * (1.0 - b + b * dl / avg_len)
    contrib = sel_weights[:, None] * jnp.where(tf > 0.0, tf / (tf + norm), 0.0)

    dflat = d.reshape(-1)
    cflat = contrib.reshape(-1)
    valid = tf.reshape(-1) > 0.0
    dkey = jnp.where(valid, dflat, _SENTINEL)
    dkey, c = jax.lax.sort((dkey, jnp.where(valid, cflat, 0.0)), num_keys=1)
    x = c
    step = 1
    while step < min(max_run, dkey.shape[0]):
        prev_x = jnp.pad(x[:-step], (step, 0))
        prev_k = jnp.pad(dkey[:-step], (step, 0), constant_values=-1)
        x = x + jnp.where(prev_k == dkey, prev_x, 0.0)
        step *= 2
    new_doc = dkey != _prev(dkey, -1)
    is_last = jnp.concatenate([new_doc[1:], jnp.ones(1, bool)])
    nd = doc_lens.shape[0]
    idx = _unique_scatter_indices(dkey, is_last, nd)
    scores = jnp.zeros(nd, jnp.float32)
    return scores.at[idx].set(x, mode="drop", unique_indices=True)


@tracked_jit
def match_count_sorted(block_docids, block_tfs, sel_blocks, clause_ids,
                       live_template):
    """int32 [ND] distinct-clause counts via sort + run boundaries + ONE
    unique-index scatter — the scatter-free replacement for
    ops/bm25.match_count (bool must / minimum_should_match on the dense
    fallback path). ``live_template`` only supplies ND."""
    d = jnp.take(block_docids, sel_blocks, axis=0)           # [NB, B]
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    cid = jnp.broadcast_to(clause_ids[:, None], d.shape)
    dflat, cflat = d.reshape(-1), cid.reshape(-1)
    valid = tf.reshape(-1) > 0.0
    dkey = jnp.where(valid, dflat, _SENTINEL)
    dkey, cl = jax.lax.sort((dkey, cflat), num_keys=2)
    new_doc = dkey != _prev(dkey, -1)
    new_pair = new_doc | (cl != _prev(cl, -1))
    is_last = jnp.concatenate([new_doc[1:], jnp.ones(1, bool)])
    counts = _segsum(new_pair.astype(jnp.float32), new_doc)
    nd = live_template.shape[0]
    idx = _unique_scatter_indices(dkey, is_last, nd)
    out = jnp.zeros(nd, jnp.int32)
    return out.at[idx].set(counts.astype(jnp.int32), mode="drop",
                           unique_indices=True)


@tracked_jit
def match_mask_sorted(block_docids, block_tfs, sel_blocks, live_template):
    """bool [ND] any-of mask via the same unique-scatter trick — the
    scatter-free replacement for ops/bm25.match_mask."""
    d = jnp.take(block_docids, sel_blocks, axis=0)
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    dflat = d.reshape(-1)
    valid = tf.reshape(-1) > 0.0
    dkey = jnp.where(valid, dflat, _SENTINEL)
    dkey = jax.lax.sort(dkey)
    new_doc = dkey != _prev(dkey, -1)
    is_last = jnp.concatenate([new_doc[1:], jnp.ones(1, bool)])
    nd = live_template.shape[0]
    idx = _unique_scatter_indices(dkey, is_last, nd)
    out = jnp.zeros(nd, bool)
    return out.at[idx].set(jnp.ones_like(dkey, bool), mode="drop",
                           unique_indices=True)
