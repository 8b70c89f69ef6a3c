"""Query-plan compiler: QueryBuilder trees → fused top-k kernel plans.

The serving-path replacement for the dense (scores, mask) execution model
(ref: the reference compiles QueryBuilder → Lucene Weight/BulkScorer,
search/internal/ContextIndexSearcher.java:196-232; here the analogous
compilation target is ops/plan.py's sorted segmented-reduction kernel).

A query is *plannable* when it decomposes into:
- postings **groups** — clauses scored/filtered from a text/keyword field's
  postings (match, multi_match, term, terms, constant_score over those),
  each with its own presence requirement (operator=and /
  minimum_should_match inside the clause);
- **dense factors** — pure column predicates (range, exists, ids,
  numeric/date/bool term(s), match_all) whose masks are vectorized
  compares with no scatter anywhere;
composed by at most one level of bool occur semantics (must / filter /
should / must_not + minimum_should_match), or a top-level dis_max /
multi_match over plannable children.

Everything else (scripts, nested bools, positional queries, aggs paths)
falls back to the dense executor — kept for when a full [ND] score vector
is semantically required.

Compilation happens once per shard (terms analyzed, idf from shard-level
stats — exactly the stats the dense path uses); binding resolves term →
postings-block ids per segment.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field as dc_field
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from elasticsearch_tpu.index.mapper import (
    ConstantKeywordFieldType,
    KeywordFieldType,
    TextFieldType,
)
from elasticsearch_tpu.ops import bm25 as bm25_ops
from elasticsearch_tpu.ops import plan as plan_ops
from elasticsearch_tpu.ops.device import block_bucket
from elasticsearch_tpu.search import queries as q

NAN = float("nan")
_NEVER = 1 << 30  # requirement no group can meet (pad groups)

# Floor for the selected-block bucket (powers of two above it). Serving
# deployments raise it to collapse the distinct compiled shapes — each
# (bucket, k) pair is one XLA compile (~20-40s on TPU first time).
MIN_PLAN_BUCKET = 0

# Filter/must_not groups at least this many postings blocks wide execute
# as cached dense masks (ops/device.py filter_mask — the LRUQueryCache
# analogue) instead of entering the per-query sort. Smaller filters are
# cheaper to sort than to cache.
FILTER_CACHE_MIN_BLOCKS = 8

# Block-max window pruning (ref: Lucene block-max WAND,
# TopDocsCollectorContext.java:210-217). The docid space splits into
# PRUNE_WINDOWS windows; a window whose BM25 upper bound (from
# block_max_tf / block_min_len) cannot reach the k-th best CPU-verified
# candidate score is dropped, and postings blocks overlapping only
# dropped windows leave the selection before the power-of-two bucket is
# chosen — the sort shrinks, recall stays exactly 1.0. Only queries with
# at least PRUNE_MIN_BLOCKS selected blocks pay the host-side bound pass.
PRUNE_WINDOWS = 512
PRUNE_MIN_BLOCKS = 384


@dataclass
class TermEntry:
    field: str
    term: str
    sub: int          # subgroup id within the group
    weight: float     # idf · boost (0 for pure-presence entries)
    const: bool       # constant-per-match contribution (keyword scoring)


@dataclass
class GroupPlan:
    kind: int                     # plan_ops.MUST / SHOULD / FILTER / MUST_NOT
    req: int                      # distinct subgroups required for presence
    const_score: float            # NaN = sum of contributions
    terms: List[TermEntry] = dc_field(default_factory=list)


@dataclass
class LogicalPlan:
    groups: List[GroupPlan]
    dense: List[Tuple[Any, bool]]         # (QueryBuilder, negate)
    n_must: int                           # postings MUST groups
    n_filter: int                         # postings FILTER groups
    msm: int
    bonus: float                          # constant score of dense must/
                                          # constant clauses every hit gets
    combine: str = "sum"
    tie: float = 0.0
    # expression-tier script_score transform: (source, sorted-params
    # tuple). Applied to the combined per-doc score inside the kernel —
    # BASELINE config 3 rides the batched plan path (ref:
    # ScriptScoreQuery.java:51,91-109; the reference scores per doc
    # through a Lucene ScoreScript, here the expression compiles to one
    # fused columnar transform)
    script: Optional[Tuple[str, tuple]] = None

    def postings_required(self) -> bool:
        """True iff every passing doc must match ≥1 postings group — the
        kernel can only see docs that appear in the gathered postings."""
        return self.n_must >= 1 or self.n_filter >= 1 or self.msm >= 1


# ---------------------------------------------------------------------------
# clause classification
# ---------------------------------------------------------------------------

def _is_postings_field(mapper, field: str) -> bool:
    ft = mapper.field_type(field)
    if isinstance(ft, ConstantKeywordFieldType):
        return False
    return (ft is None or isinstance(ft, (TextFieldType, KeywordFieldType))
            or getattr(ft, "docvalue_kind", None) == "flattened")


def _is_dense_clause(node, mapper) -> bool:
    """Clauses whose do_execute builds masks from dense columns only —
    no postings scatter anywhere (range/exists/ids/match_all and term(s)
    on numeric/date/bool/constant_keyword/range fields)."""
    if isinstance(node, (q.RangeQuery, q.ExistsQuery, q.IdsQuery,
                         q.MatchAllQuery)):
        return True
    if isinstance(node, (q.TermQuery, q.TermsQuery)):
        return not _is_postings_field(mapper, node.field)
    return False


def _analyze(searcher, field: str, text: str) -> List[str]:
    # the dense executor's analysis, verbatim — one tokenization for both
    # paths (queries._analyze_terms only reads .mapper, which ShardSearcher
    # exposes just like SegmentContext)
    return q._analyze_terms(searcher, field, text)


def _idf(searcher, field: str, term: str) -> float:
    doc_count, _ = searcher.stats.field_stats(field)
    df = searcher.stats.doc_freq(field, term)
    return bm25_ops.idf(df, doc_count) if df > 0 else 0.0


# ---------------------------------------------------------------------------
# per-clause group builders (return None when not plannable)
# ---------------------------------------------------------------------------

def _group_for_match(node: "q.MatchQuery", searcher, kind: int,
                     scale: float) -> Optional[GroupPlan]:
    if not _is_postings_field(searcher.mapper, node.field):
        return None
    terms = _analyze(searcher, node.field, node.query)
    if not terms:
        return None  # matches nothing; dense fallback returns empty fast
    uniq = {t: i for i, t in enumerate(sorted(set(terms)))}
    if node.operator == "and":
        req = len(uniq)
    elif node.minimum_should_match:
        # parsed over the token count (duplicates included), clamped to the
        # distinct-term count; ≤1 means "any term" — exactly the dense
        # path's required/need computation (queries.MatchQuery.do_execute)
        r = q.parse_minimum_should_match(
            node.minimum_should_match, len(terms))
        req = 1 if r <= 1 else min(r, len(uniq))
    else:
        req = 1
    g = GroupPlan(kind, req, NAN)
    for t in terms:  # duplicates kept: they double the contribution, as in
        # the dense path (select_blocks extends per occurrence)
        g.terms.append(TermEntry(node.field, t, uniq[t],
                                 _idf(searcher, node.field, t) * scale,
                                 False))
    return g


def _group_for_term(node: "q.TermQuery", searcher, kind: int,
                    scale: float) -> Optional[GroupPlan]:
    mapper = searcher.mapper
    if not _is_postings_field(mapper, node.field):
        return None
    ft = mapper.field_type(node.field)
    term = str(node.value)
    if isinstance(ft, TextFieldType):
        g = GroupPlan(kind, 1, NAN)
        g.terms.append(TermEntry(node.field, term,
                                 0, _idf(searcher, node.field, term) * scale,
                                 False))
        return g
    # keyword/unmapped/flattened: constant score idf·1/(1+k1), no norms
    # (ref: Lucene keyword fields omit norms; see queries.TermQuery)
    const = _idf(searcher, node.field, term) / (1.0 + searcher.k1) * scale
    g = GroupPlan(kind, 1, const)
    g.terms.append(TermEntry(node.field, term, 0, 0.0, False))
    return g


def _group_for_terms(node: "q.TermsQuery", searcher, kind: int,
                     scale: float) -> Optional[GroupPlan]:
    if not _is_postings_field(searcher.mapper, node.field):
        return None
    g = GroupPlan(kind, 1, 1.0 * scale)   # constant_score(1.0) any-of
    for v in node.values:
        g.terms.append(TermEntry(node.field, str(v), 0, 0.0, False))
    return g


def _group_for_clause(node, searcher, kind: int,
                      scale: float) -> Optional[GroupPlan]:
    scale = scale * getattr(node, "boost", 1.0)
    if isinstance(node, q.MatchQuery):
        return _group_for_match(node, searcher, kind, scale)
    if isinstance(node, q.TermQuery):
        return _group_for_term(node, searcher, kind, scale)
    if isinstance(node, q.TermsQuery):
        return _group_for_terms(node, searcher, kind, scale)
    if isinstance(node, q.ConstantScoreQuery):
        inner = _group_for_clause(node.filter_query, searcher, kind, 1.0)
        if inner is None:
            return None
        inner.kind = kind
        inner.const_score = 1.0 * scale   # score is the boost, not BM25
        for t in inner.terms:
            t.weight = 0.0
        return inner
    return None


# ---------------------------------------------------------------------------
# top-level compilation
# ---------------------------------------------------------------------------

def _plan_script_spec(node: "q.ScriptScoreQuery",
                      searcher) -> Optional[Tuple[str, tuple]]:
    """(source, params) when the script can ride the kernel: the
    EXPRESSION tier only (statement scripts interpret per doc on host),
    scalar params, no min_score, and a dry trace over dummy columns
    succeeds (catches vector functions / unsupported constructs)."""
    from elasticsearch_tpu.search.script import (ScriptContext,
                                                 ScriptException,
                                                 _DocColumn,
                                                 compile_script)
    if node.min_score is not None:
        return None
    if not all(isinstance(v, (int, float, str, bool))
               for v in node.params.values()):
        return None
    try:
        compiled = compile_script(node.source)
    except ScriptException:
        return None
    if not getattr(compiled, "vectorized", False):
        return None

    def dummy_cols(field):
        return _DocColumn(jnp.zeros(2, jnp.float32),
                          jnp.zeros(2, bool))
    try:
        out = compiled(ScriptContext(dummy_cols, dict(node.params),
                                     score=jnp.zeros(2, jnp.float32)))
        jnp.asarray(out, jnp.float32)
    except Exception:       # noqa: BLE001 — anything odd → dense path
        return None
    return (node.source, tuple(sorted(node.params.items())))


def compile_plan(query, searcher,
                 post_filter=None) -> Optional[LogicalPlan]:
    """Compile a rewritten query (+ optional post_filter folded in as a
    filter — valid when no aggregations run) into a LogicalPlan, or None
    when the tree needs the dense executor."""
    script_spec = None
    if isinstance(query, q.ScriptScoreQuery):
        script_spec = _plan_script_spec(query, searcher)
        if script_spec is None:
            return None
        query = query.query
    plan = _compile_tree(query, searcher)
    if plan is None:
        return None
    plan.script = script_spec
    if post_filter is not None:
        g = _group_for_clause(post_filter, searcher, plan_ops.FILTER, 1.0)
        if g is not None:
            g.const_score = NAN
            plan.groups.append(g)
            plan.n_filter += 1
        elif _is_dense_clause(post_filter, searcher.mapper):
            plan.dense.append((post_filter, False))
        else:
            return None
    if not plan.postings_required():
        return None
    # negative boosts would feed negative contributions into the kernel's
    # cumsum/cummax segmented sums (which require x >= 0) — dense fallback
    if plan.bonus < 0:
        return None
    for g in plan.groups:
        if any(t.weight < 0 for t in g.terms):
            return None
        if not math.isnan(g.const_score) and g.const_score < 0:
            return None
    return plan


def _compile_tree(query, searcher) -> Optional[LogicalPlan]:
    boost = getattr(query, "boost", 1.0)
    if isinstance(query, q.BoolQuery):
        return _compile_bool(query, searcher, boost)
    if isinstance(query, q.MultiMatchQuery):
        return _compile_multi_match(query, searcher, boost)
    if isinstance(query, q.DisMaxQuery):
        return _compile_dismax(query, searcher, boost)
    g = _group_for_clause(query, searcher, plan_ops.MUST, 1.0)
    if g is not None:
        # top-level boost is inside the group scale already via
        # _group_for_clause's getattr(node, "boost")
        return LogicalPlan([g], [], 1, 0, 0, 0.0)
    return None


def _compile_bool(node: "q.BoolQuery", searcher,
                  boost: float) -> Optional[LogicalPlan]:
    groups: List[GroupPlan] = []
    dense: List[Tuple[Any, bool]] = []
    bonus = 0.0
    n_must = n_filter = 0
    n_required_any = 0  # must+filter clauses of any kind (for msm default)

    for clause in node.must:
        g = _group_for_clause(clause, searcher, plan_ops.MUST, boost)
        if g is not None:
            groups.append(g)
            n_must += 1
        elif _is_dense_clause(clause, searcher.mapper):
            dense.append((clause, False))
            # a required constant-score clause adds its score to every hit
            # (dense masks score 1.0·boost in the dense path)
            bonus += getattr(clause, "boost", 1.0) * boost
        else:
            return None
        n_required_any += 1
    for clause in node.filter:
        g = _group_for_clause(clause, searcher, plan_ops.FILTER, 1.0)
        if g is not None:
            g.const_score = NAN   # filters never score
            groups.append(g)
            n_filter += 1
        elif _is_dense_clause(clause, searcher.mapper):
            dense.append((clause, False))
        else:
            return None
        n_required_any += 1
    for clause in node.must_not:
        g = _group_for_clause(clause, searcher, plan_ops.MUST_NOT, 1.0)
        if g is not None:
            g.const_score = NAN
            groups.append(g)
        elif _is_dense_clause(clause, searcher.mapper):
            dense.append((clause, True))
        else:
            return None
    for clause in node.should:
        g = _group_for_clause(clause, searcher, plan_ops.SHOULD, boost)
        if g is None:
            return None   # dense should-clauses: conditional +1 scoring —
            # rare; dense fallback keeps exact semantics
        groups.append(g)

    if node.minimum_should_match is None:
        msm = 1 if (node.should and n_required_any == 0) else 0
    else:
        msm = q.parse_minimum_should_match(
            node.minimum_should_match, len(node.should))
    if node.should and msm > len(node.should):
        msm = len(node.should)
    return LogicalPlan(groups, dense, n_must, n_filter, msm, bonus)


def _compile_multi_match(node: "q.MultiMatchQuery", searcher,
                         boost: float) -> Optional[LogicalPlan]:
    fields = node.fields
    if not fields or fields == ["*"]:
        fields = [name for name, ft in searcher.mapper.mapper.fields.items()
                  if isinstance(ft, TextFieldType)]
    if not fields:
        return None
    groups = []
    for f in fields:
        g = _group_for_match(q.MatchQuery(f, node.query), searcher,
                             plan_ops.SHOULD, boost)
        if g is None:
            return None
        groups.append(g)
    if node.type == "most_fields":
        return LogicalPlan(groups, [], 0, 0, 1, 0.0, combine="sum")
    if node.type == "best_fields":
        return LogicalPlan(groups, [], 0, 0, 1, 0.0, combine="dismax",
                           tie=node.tie_breaker)
    return None   # cross_fields/phrase types: dense fallback


def _compile_dismax(node: "q.DisMaxQuery", searcher,
                    boost: float) -> Optional[LogicalPlan]:
    groups = []
    for sub in node.queries:
        g = _group_for_clause(sub, searcher, plan_ops.SHOULD, boost)
        if g is None:
            return None
        groups.append(g)
    if not groups:
        return None
    return LogicalPlan(groups, [], 0, 0, 1, 0.0, combine="dismax",
                       tie=node.tie_breaker)


# ---------------------------------------------------------------------------
# per-segment binding + execution
# ---------------------------------------------------------------------------

@dataclass
class BoundPlan:
    """A LogicalPlan bound to one segment's device arrays: ready-to-launch
    kernel arguments (the per-query bytes shipped to device are just the
    selection arrays — a few hundred bytes)."""
    streams: List[plan_ops.FieldStream]
    group_kind: np.ndarray
    group_req: np.ndarray
    group_const: np.ndarray
    dense_mask: Optional[jnp.ndarray]
    n_must: int
    n_filter: int
    msm: int
    bonus: float
    tie: float
    combine: str
    empty: bool = False   # no query term exists in this segment
    # host copies of cached-filter masks folded into dense_mask, as
    # (mask, negate) — lets block-max pruning validate its threshold
    # candidates CPU-side (no readback)
    host_masks: List[Tuple[np.ndarray, bool]] = dc_field(default_factory=list)
    # True when block-max pruning dropped blocks: the kernel's matching-doc
    # count is then a LOWER bound (hits.total relation becomes "gte")
    pruned: bool = False
    # dense_mask is a CACHED shared object (composed filter column):
    # batch cohorts may key on its identity and pass it unbatched
    dense_shared: bool = False
    # stable per-(segment, script) closure applied to the per-doc score
    # inside the kernel (ops/plan.plan_topk_body script_fn); identity is
    # the batch-cohort key, so it must come from _bind_script's cache
    script_fn: Optional[Any] = None


def _group_field_blocks(g: GroupPlan, ctx) -> Optional[Tuple[str, int]]:
    """(field, total postings blocks) of a single-field group, else None."""
    fields = {t.field for t in g.terms}
    if len(fields) != 1:
        return None
    fname = next(iter(fields))
    dp = ctx.device.postings.get(fname)
    if dp is None:
        return fname, 0
    n = 0
    for t in g.terms:
        tid = dp.host.term_id(t.term)
        if tid >= 0:
            n += int(dp.term_block_count[tid])
    return fname, n


def _convert_filters(plan: LogicalPlan, ctx):
    """Split groups into kernel groups vs cached-mask conversions.

    FILTER / MUST_NOT groups with any-of presence semantics (req <= 1)
    and ≥ FILTER_CACHE_MIN_BLOCKS postings blocks execute as dense cached
    masks (ops/device.py filter_mask — ref: Lucene LRUQueryCache via
    UsageTrackingQueryCachingPolicy: hot filters become bitsets), so their
    postings never enter the query's sort. At least one enumerating
    postings group must remain — the kernel only sees docs present in the
    gathered postings.

    Returns (kernel_groups, [(field, terms, negate)], kernel_filter_count).
    """
    must_enum = plan.n_must >= 1
    should_enum = plan.msm >= 1 and any(
        g.kind == plan_ops.SHOULD for g in plan.groups)

    sized = []
    for gi, g in enumerate(plan.groups):
        if g.kind not in (plan_ops.FILTER, plan_ops.MUST_NOT) or g.req > 1:
            continue
        fb = _group_field_blocks(g, ctx)
        if fb is not None and fb[1] >= FILTER_CACHE_MIN_BLOCKS:
            sized.append((fb[1], gi, g, fb[0]))
    sized.sort(key=lambda e: -e[0])   # biggest filters convert first

    n_filters_left = plan.n_filter
    converted: List[Tuple[str, List[str], bool]] = []
    convert_ids = set()
    for _, gi, g, fname in sized:
        if g.kind == plan_ops.MUST_NOT:
            convert_ids.add(gi)
            converted.append((fname, [t.term for t in g.terms], True))
        elif must_enum or should_enum or n_filters_left > 1:
            convert_ids.add(gi)
            converted.append((fname, [t.term for t in g.terms], False))
            n_filters_left -= 1
    kernel = [g for gi, g in enumerate(plan.groups) if gi not in convert_ids]
    return kernel, converted, n_filters_left


def bind_plan(plan: LogicalPlan, ctx, k: int = 10,
              allow_prune: bool = False) -> BoundPlan:
    """Resolve terms → block ids against one segment (ctx: SegmentContext).
    Selection arrays bucket to powers of two so NB takes O(log) distinct
    values across queries (XLA compile-cache discipline, ops/device.py).

    ``allow_prune=True`` (legal when the caller treats hits.total as a
    lower bound — track_total_hits thresholds) additionally applies
    block-max window pruning (_prune_fields): docid windows whose BM25
    upper bound cannot reach a CPU-validated top-k threshold drop out of
    the selection entirely, shrinking the sorted bucket (ref: Lucene
    block-max WAND, TopDocsCollectorContext.java:210-217)."""
    kernel_groups, converted, n_filter = _convert_filters(plan, ctx)
    ngroups = len(kernel_groups)
    by_field: Dict[str, List[Tuple[int, int, float, bool, str]]] = {}
    for gi, g in enumerate(kernel_groups):
        for t in g.terms:
            by_field.setdefault(t.field, []).append(
                (gi, t.sub, t.weight, t.const, t.term))

    # cached dense masks first — their HOST copies also validate the
    # pruning threshold below. The COMPOSED mask of the whole filter set
    # is itself cached so repeated filter combos share one device object
    # (batch cohorts key on its identity).
    dense_mask = None
    dense_shared = False
    host_masks: List[Tuple[np.ndarray, bool]] = []
    if converted:
        dense_mask, comp_host = ctx.device.composed_filter_mask(converted)
        dense_shared = True
        host_masks.append((comp_host, False))
    for clause, negate in plan.dense:
        _, m = clause.do_execute(ctx)
        m = (~m) if negate else m
        dense_mask = m if dense_mask is None else (dense_mask & m)
        dense_shared = False   # device-column factors: identity not cached

    # ---- unpadded per-field selections (kept separate so pruning can
    # drop blocks before the power-of-two bucket is chosen)
    fields: List[Tuple[str, Any, np.ndarray, np.ndarray, np.ndarray,
                       np.ndarray, np.ndarray]] = []
    for fname, entries in by_field.items():
        dp = ctx.device.postings.get(fname)
        if dp is None:
            continue
        starts: List[int] = []
        counts: List[int] = []
        egrp: List[int] = []
        esub: List[int] = []
        ew: List[float] = []
        econst: List[bool] = []
        for gi, sub, w, const, term in entries:
            tid = dp.host.term_id(term)
            if tid < 0:
                continue
            starts.append(int(dp.term_block_start[tid]))
            counts.append(int(dp.term_block_count[tid]))
            egrp.append(gi)
            esub.append(sub)
            ew.append(w)
            econst.append(const)
        if not starts:
            continue
        # vectorized range expansion (per-request host path: no Python
        # per-block loops)
        counts_np = np.asarray(counts, np.int64)
        tot = int(counts_np.sum())
        if tot == 0:
            continue
        rep = np.repeat(np.arange(len(starts)), counts_np)
        offs = (np.arange(tot, dtype=np.int64)
                - np.repeat(np.cumsum(counts_np) - counts_np, counts_np))
        sel = (np.asarray(starts, np.int64)[rep] + offs).astype(np.int32)
        fields.append((fname, dp,
                       sel,
                       np.asarray(egrp, np.int32)[rep],
                       np.asarray(esub, np.int32)[rep],
                       np.asarray(ew, np.float32)[rep],
                       np.asarray(econst, bool)[rep],
                       rep.astype(np.int32)))

    pruned = False
    if allow_prune and fields:
        fields, pruned = _prune_fields(plan, kernel_groups, fields, ctx, k,
                                       host_masks)

    streams: List[plan_ops.FieldStream] = []
    any_entries = False
    for fname, dp, sel_u, grp_u, sub_u, w_u, c_u, _ent in fields:
        tot = len(sel_u)
        if tot == 0:
            continue
        any_entries = True
        n = max(block_bucket(tot), MIN_PLAN_BUCKET)
        sel = np.full(n, dp.zero_block, np.int32)
        sel[:tot] = sel_u
        grp = np.full(n, ngroups, np.int32)   # pads: clipped; tf=0 ⇒ inert
        grp[:tot] = grp_u
        sub_a = np.zeros(n, np.int32)
        sub_a[:tot] = sub_u
        w_a = np.zeros(n, np.float32)
        w_a[:tot] = w_u
        c_a = np.zeros(n, bool)
        c_a[:tot] = c_u
        # selections stay NUMPY: the jit boundary uploads them
        # asynchronously per launch, while batching stacks them with a
        # microseconds host np.stack — stacking device arrays instead
        # costs ~10ms of GIL-held dispatch per launch (measured), which
        # serializes the whole concurrent serving path
        streams.append(plan_ops.FieldStream(
            dp.block_docids, dp.block_tfs, dp.doc_lens,
            jnp.float32(ctx.stats.field_stats(fname)[1]),
            sel, grp, sub_a, w_a, c_a))

    gpad = max(4, block_bucket(max(1, ngroups)) if ngroups else 4)
    kind = np.full(gpad, plan_ops.FILTER, np.int32)
    req = np.full(gpad, _NEVER, np.int32)
    const = np.full(gpad, NAN, np.float32)
    for gi, g in enumerate(kernel_groups):
        kind[gi] = g.kind
        req[gi] = g.req
        const[gi] = g.const_score
    # pad groups: FILTER with unreachable req — never present, and absent
    # FILTER groups don't block (n_filter counts only real groups)

    return BoundPlan(streams, kind, req, const, dense_mask,
                     plan.n_must, n_filter, plan.msm, plan.bonus,
                     plan.tie, plan.combine, empty=not any_entries,
                     host_masks=host_masks, pruned=pruned,
                     dense_shared=dense_shared,
                     script_fn=(_bind_script(ctx, plan.script)
                                if plan.script is not None else None))


# ---------------------------------------------------------------------------
# block-max window pruning (host-side bound pass; ref: Lucene block-max
# WAND / MaxScore — TopDocsCollectorContext.java:210-217)
# ---------------------------------------------------------------------------

def _block_bounds(dp):
    """Per-block (first, last) docids, cached on the DevicePostings.
    Valid postings are a docid-ascending prefix of each block (tf=0 pads
    sit at the end with docid 0), so the masked max is the last docid."""
    lo = getattr(dp, "_block_lo", None)
    if lo is None:
        pf = dp.host
        dp._block_lo = pf.block_docids[:, 0].astype(np.int64)
        dp._block_hi = np.where(pf.block_tfs > 0.0, pf.block_docids,
                                0).max(axis=1).astype(np.int64)
        lo = dp._block_lo
    return lo, dp._block_hi




def _prune_fields(plan: LogicalPlan, kernel_groups: List[GroupPlan],
                  fields, ctx, k: int,
                  host_masks: List[Tuple[np.ndarray, bool]]):
    """Drop postings blocks that provably cannot affect the top-k.

    Correctness argument (recall exactly 1.0):
    - θ is the k-th largest *single-entry* contribution among ≥k distinct
      docs that verifiably PASS the whole query (live + every filter,
      validated host-side) — each doc's true score is ≥ its partial
      contribution, so the true k-th best score is ≥ θ.
    - A docid window's bound sums per-term maxima of
      w·max_tf/(max_tf + k1·(1−b+b·min_len/avg)) — an upper bound on any
      doc's score inside the window (score is monotonic ↑tf, ↓len).
    - Windows with bound < θ therefore contain no top-k member; blocks
      overlapping only such windows drop from every group (scoring,
      filter, must_not alike), so surviving docs keep ALL their postings
      and score exactly.
    The kernel's matching-doc count becomes a lower bound (`pruned=True`
    → hits.total relation "gte"), which is why callers gate this on
    track_total_hits thresholds.
    """
    total_blocks = sum(len(f[2]) for f in fields)
    if total_blocks < PRUNE_MIN_BLOCKS or plan.dense or plan.bonus < 0:
        return fields, False

    # adaptive backoff: on corpora whose docid space shows no block-max
    # skew (uniform synthetic data, shuffled ingestion) the bound pass
    # never prunes — exponentially skip attempts per segment so the host
    # cost vanishes there (the spirit of Lucene's usage-tracking policy)
    dev = ctx.device
    skip = getattr(dev, "_prune_skip", 0)
    if skip > 0:
        dev._prune_skip = skip - 1
        return fields, False

    # ---- eligibility + candidate sources + host-validated filters
    must_ids = [gi for gi, g in enumerate(kernel_groups)
                if g.kind == plan_ops.MUST]
    cand_ids = set()
    small_filters: List[Tuple[int, bool]] = []   # (group id, negate)
    for gi, g in enumerate(kernel_groups):
        if g.kind == plan_ops.MUST:
            if len(must_ids) != 1 or plan.msm >= 1 or g.req > 1:
                return fields, False
            cand_ids.add(gi)
        elif g.kind == plan_ops.SHOULD:
            if not must_ids and plan.msm <= 1 and g.req <= 1:
                cand_ids.add(gi)
        elif g.kind == plan_ops.MUST_NOT:
            # a kernel must_not whose postings prune away would let the
            # matching-doc count OVERcount (excluded docs sneaking back
            # in) — converted must_nots are dense columns and stay exact
            return fields, False
        else:   # small FILTER staying in the kernel
            if g.req > 1 or len({t.field for t in g.terms}) != 1:
                return fields, False
            small_filters.append((gi, False))
    if must_ids:
        cand_ids = set(must_ids)
    if not cand_ids:
        return fields, False

    nd = ctx.segment.n_docs
    if nd <= 0:
        return fields, False
    wsz = max(1, -(-nd // PRUNE_WINDOWS))
    W = -(-nd // wsz)
    k1, b = ctx.k1, ctx.b
    ng = len(kernel_groups)
    gconst = np.asarray([g.const_score for g in kernel_groups], np.float32)
    gkind = np.asarray([g.kind for g in kernel_groups], np.int32)

    # validation mask over real docs: live + converted cached filters +
    # small kernel filters
    vmask = np.asarray(ctx.segment.live[:nd], bool).copy()
    for hm, negate in host_masks:
        vmask &= ~hm[:nd] if negate else hm[:nd]
    for gi, negate in small_filters:
        g = kernel_groups[gi]
        fname = g.terms[0].field
        dp = ctx.device.postings.get(fname)
        if dp is None:
            m = np.zeros(nd, bool)
        else:
            from elasticsearch_tpu.ops.device import host_any_mask
            m = host_any_mask(dp.host, [t.term for t in g.terms], nd)
        vmask &= ~m if negate else m

    # ---- per-(group, window) upper bounds + θ candidates
    group_wb = np.zeros((ng, W), np.float64)
    group_any = np.zeros((ng, W), bool)     # presence for const groups
    theta = -np.inf
    probe_j = -(-k // 128) + 4              # blocks per candidate entry
    per_field = []                          # (wlo, whi) kept for drop pass
    for fname, dp, sel_u, grp_u, sub_u, w_u, c_u, ent_u in fields:
        pf = dp.host
        avg = ctx.stats.field_stats(fname)[1]
        lo_all, hi_all = _block_bounds(dp)
        wlo = (lo_all[sel_u] // wsz).astype(np.int64)
        whi = np.maximum(hi_all[sel_u] // wsz, wlo).astype(np.int64)
        per_field.append((wlo, whi))
        mtf = pf.block_max_tf[sel_u].astype(np.float64)
        mln = pf.block_min_len[sel_u].astype(np.float64)
        norm = k1 * (1.0 - b + b * mln / avg)
        sat = np.where(mtf > 0.0, mtf / (mtf + norm), 0.0)
        is_sum_grp = np.isnan(gconst[grp_u])   # NaN const ⇒ sum-of-contribs
        ub = np.where(is_sum_grp,
                      np.where(c_u, w_u, w_u * sat),
                      (mtf > 0.0).astype(np.float64))

        # per-entry window maxima (entries are windows-disjoint block runs)
        n_ent = int(ent_u[-1]) + 1 if len(ent_u) else 0
        if n_ent > 64:
            # pathological entry counts (huge terms lists in the kernel)
            # would make the per-entry bound pass itself the bottleneck
            return fields, False
        lens = whi - wlo + 1
        tot = int(lens.sum())
        csum = np.cumsum(lens) - lens
        widx = (np.repeat(wlo, lens)
                + (np.arange(tot, dtype=np.int64) - np.repeat(csum, lens)))
        eidx = np.repeat(ent_u.astype(np.int64), lens)
        ewm = np.zeros(n_ent * W, np.float64)
        np.maximum.at(ewm, eidx * W + widx, np.repeat(ub, lens))
        ewm = ewm.reshape(n_ent, W)

        # fold entries into group bounds: NaN-const groups SUM their
        # entries' maxima (duplicate query terms double-count, matching
        # the kernel); const groups need presence only
        ent_first = np.flatnonzero(np.diff(ent_u, prepend=-1))
        for e0 in ent_first:
            e = int(ent_u[e0])
            gi = int(grp_u[e0])
            if np.isnan(gconst[gi]):
                group_wb[gi] += ewm[e]
            group_any[gi] |= ewm[e] > 0.0

            # θ probe: top-J blocks of candidate entries, exact partial
            # contributions validated against vmask
            if gi not in cand_ids:
                continue
            blocks = sel_u[ent_u == e]
            ub_e = ub[ent_u == e]
            j = min(probe_j, len(blocks))
            topb = blocks[np.argpartition(ub_e, len(ub_e) - j)[len(ub_e) - j:]] \
                if j < len(blocks) else blocks
            d = pf.block_docids[topb].reshape(-1)
            tf = pf.block_tfs[topb].reshape(-1).astype(np.float64)
            ok = (tf > 0.0) & (d < nd)
            d, tf = d[ok], tf[ok]
            ok = vmask[d]
            d, tf = d[ok], tf[ok]
            if len(d) < k:
                continue
            if not np.isnan(gconst[gi]):
                cand = np.full(len(d), float(gconst[gi]))
            elif bool(c_u[e0]):
                cand = np.full(len(d), float(w_u[e0]))
            else:
                dnorm = k1 * (1.0 - b
                              + b * pf.field_lengths[d].astype(np.float64)
                              / avg)
                cand = float(w_u[e0]) * tf / (tf + dnorm)
            th = np.partition(cand, len(cand) - k)[len(cand) - k]
            if th > theta:
                theta = th

    def _fail():
        fails = getattr(dev, "_prune_fail", 0) + 1
        dev._prune_fail = fails
        dev._prune_skip = min(256, 2 ** min(fails, 8))
        return fields, False

    if not np.isfinite(theta) or theta <= 0.0:
        return _fail()

    # ---- combine group bounds → per-window score bound
    scoring = (gkind == plan_ops.MUST) | (gkind == plan_ops.SHOULD)
    gb = np.where(np.isnan(gconst)[:, None], group_wb,
                  np.nan_to_num(gconst)[:, None] * group_any)
    gb = gb[scoring]
    if plan.combine == "dismax":
        mx = gb.max(axis=0) if len(gb) else np.zeros(W)
        wb = mx + plan.tie * (gb.sum(axis=0) - mx)
    else:
        wb = gb.sum(axis=0) if len(gb) else np.zeros(W)

    # float32 kernel sums can exceed the float64 bound by rounding —
    # keep a small safety margin
    keep_w = wb >= theta * (1.0 - 1e-5)
    if keep_w.all():
        return _fail()
    ck = np.concatenate([[0], np.cumsum(keep_w)])

    out = []
    pruned = False
    for (fname, dp, sel_u, grp_u, sub_u, w_u, c_u, ent_u), (wlo, whi) in zip(
            fields, per_field):
        blk_keep = (ck[np.minimum(whi, W - 1) + 1] - ck[wlo]) > 0
        if blk_keep.all():
            out.append((fname, dp, sel_u, grp_u, sub_u, w_u, c_u, ent_u))
            continue
        pruned = True
        out.append((fname, dp, sel_u[blk_keep], grp_u[blk_keep],
                    sub_u[blk_keep], w_u[blk_keep], c_u[blk_keep],
                    ent_u[blk_keep]))
    if pruned:
        dev._prune_fail = 0
    else:
        fails = getattr(dev, "_prune_fail", 0) + 1
        dev._prune_fail = fails
        dev._prune_skip = min(256, 2 ** min(fails, 8))
    return out, pruned


def _bind_script(ctx, script_spec):
    """Per-(DeviceSegment, script) closure over the segment's device
    numeric columns — CACHED on the DeviceSegment so its identity is
    stable (the kernel jits on it as a static argument, and batch
    cohorts key on it)."""
    from elasticsearch_tpu.search.script import (ScriptContext,
                                                 ScriptException,
                                                 _DocColumn,
                                                 compile_script)
    dev = ctx.device
    cache = getattr(dev, "_plan_scripts", None)
    if cache is None:
        cache = dev._plan_scripts = {}
    fn = cache.get(script_spec)
    if fn is None:
        compiled = compile_script(script_spec[0])
        params = dict(script_spec[1])
        numerics = dev.numerics
        missing = dev.numeric_missing

        def fn(score, ids):
            def doc_columns(field):
                col = numerics.get(field)
                if col is None:
                    raise ScriptException(
                        f"unknown numeric field [{field}]")
                return _DocColumn(jnp.take(col, ids),
                                  jnp.take(missing[field], ids))
            sctx = ScriptContext(doc_columns, params, score=score)
            return jnp.asarray(compiled(sctx), jnp.float32)
        cache[script_spec] = fn
    return fn


def execute_bound(bp: BoundPlan, ctx, k: int, k1: float, b: float,
                  after_score: Optional[float] = None):
    """Launch the fused kernel for one segment → host (vals[k], ids[k],
    total). The device result is PACKED into one buffer so the whole
    query costs exactly one device→host readback (ops/plan.pack_result)."""
    if bp.empty:
        return (np.full(k, -np.inf, np.float32),
                np.full(k, plan_ops._SENTINEL, np.int32), 0)
    packed = plan_ops.plan_topk(
        bp.streams, bp.group_kind, bp.group_req, bp.group_const,
        ctx.live, bp.dense_mask, bp.n_must, bp.n_filter, bp.msm,
        bonus=bp.bonus, tie=bp.tie, k1=k1, b=b, k=k, combine=bp.combine,
        after_score=after_score, packed=True, script_fn=bp.script_fn)
    return plan_ops.unpack_result(np.asarray(packed), k)
