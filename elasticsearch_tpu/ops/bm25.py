"""Batched BM25 scoring kernels.

The TPU replacement for the Lucene BulkScorer hot loop (ref:
search/internal/ContextIndexSearcher.java:210-213 — per-segment
``BulkScorer.score(leafCollector, liveDocs)``). Where Lucene iterates
postings one docid at a time with skip lists, these kernels score *all*
selected postings blocks in one launch:

    gather blocks → per-posting BM25 contribution → scatter-add into a
    dense per-doc score accumulator → (top-k in ops/topk.py)

Padding discipline (set up by index/segment.py): padded lanes carry
``tf = 0`` so their contribution is exactly 0, and padded *blocks* point at
a reserved all-zeros block appended at device upload, with weight 0 — no
masks needed anywhere in the hot path.

The BM25 formula matches Lucene 8's BM25Similarity (ref: Lucene
BM25Similarity.java — the (k1+1) numerator constant is dropped, which does
not change ranking):

    idf(t)  = ln(1 + (N - df + 0.5) / (df + 0.5))
    score   = idf * tf / (tf + k1 * (1 - b + b * dl / avgdl))

Lucene quantizes dl into a 1-byte norm (SmallFloat); we keep exact float
lengths — rankings agree at matched recall, absolute scores differ slightly
(SURVEY.md §7 "Scoring parity").

Compile observability: nothing here is jitted at module level — callers
either execute these eagerly (the dense fallback) or close over them in
their own jit (bench.py, ops/plan.py), so their per-shape compiles are
attributed to the CALLING kernel's entry in the compile tracker
(telemetry/engine.py); see `GET /_kernels`.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def idf(doc_freq, doc_count) -> float:
    """Lucene BM25 idf (BM25Similarity.idf)."""
    return math.log(1.0 + (doc_count - doc_freq + 0.5) / (doc_freq + 0.5))


def bm25_contrib(sel_weights: jax.Array, tf: jax.Array, dl: jax.Array,
                 avg_len, k1: float, b: float) -> jax.Array:
    """Per-posting BM25 contribution [NB, B] — THE scoring expression
    (one definition; the dense path, the sorted-top-k path, and the
    Pallas kernel's reference all share it). The tf>0 guard protects the
    padding lanes from 0/0 NaNs."""
    norm = k1 * (1.0 - b + b * dl / avg_len)
    return sel_weights[:, None] * jnp.where(tf > 0.0, tf / (tf + norm), 0.0)


def bm25_block_scores(block_docids: jax.Array,   # int32 [TB, B] all blocks
                      block_tfs: jax.Array,      # float32 [TB, B]
                      sel_blocks: jax.Array,     # int32 [NB] selected block ids
                      sel_weights: jax.Array,    # float32 [NB] idf of owning term
                      doc_lens: jax.Array,       # float32 [ND]
                      avg_len: jax.Array,        # float32 scalar
                      k1: float, b: float) -> jax.Array:
    """Dense per-doc BM25 scores [ND] for the selected blocks.

    A doc's score is the sum over query terms of idf·tf/(tf+norm); docs
    matching no term end at exactly 0.0 (idf > 0 always, so any match
    scores > 0 — "matched" is recoverable from score > 0).
    """
    d = jnp.take(block_docids, sel_blocks, axis=0)        # [NB, B]
    tf = jnp.take(block_tfs, sel_blocks, axis=0)          # [NB, B]
    dl = jnp.take(doc_lens, d)                            # [NB, B]
    contrib = bm25_contrib(sel_weights, tf, dl, avg_len, k1, b)
    scores = jnp.zeros(doc_lens.shape[0], jnp.float32)
    return scores.at[d.reshape(-1)].add(
        contrib.reshape(-1), mode="drop", unique_indices=False)


def match_mask(block_docids: jax.Array, block_tfs: jax.Array,
               sel_blocks: jax.Array, n_docs: int) -> jax.Array:
    """bool [ND]: docs appearing in ANY selected block (term/terms filters —
    the device analogue of a Lucene TermQuery bitset)."""
    d = jnp.take(block_docids, sel_blocks, axis=0)
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    mask = jnp.zeros(n_docs, jnp.bool_)
    return mask.at[d.reshape(-1)].max(tf.reshape(-1) > 0, mode="drop")


def match_count(block_docids: jax.Array, block_tfs: jax.Array,
                sel_blocks: jax.Array, clause_ids: jax.Array,
                n_clauses: int, n_docs: int) -> jax.Array:
    """int32 [ND]: number of distinct clauses each doc matches.

    Used for bool `must`/`minimum_should_match` semantics: each selected
    block carries the id of its owning clause; per-doc presence is computed
    per clause (scatter-max into a [ND, n_clauses] plane), then summed.
    n_clauses is static and small.
    """
    d = jnp.take(block_docids, sel_blocks, axis=0)        # [NB, B]
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    present = jnp.zeros((n_docs, n_clauses), jnp.bool_)
    cid = jnp.broadcast_to(clause_ids[:, None], d.shape)  # [NB, B]
    present = present.at[d.reshape(-1), cid.reshape(-1)].max(
        tf.reshape(-1) > 0, mode="drop")
    return present.sum(axis=1, dtype=jnp.int32)


def block_max_scores(block_max_tf: jax.Array,   # float32 [TB]
                     block_min_len: jax.Array,  # float32 [TB]
                     sel_blocks: jax.Array,     # int32 [NB]
                     sel_weights: jax.Array,    # float32 [NB]
                     avg_len: jax.Array, k1: float, b: float) -> jax.Array:
    """Upper-bound score per selected block — the block-max WAND bound
    (ref: Lucene block-max impacts, TopDocsCollectorContext.java:210-217).
    Monotonic ↑ in tf, ↓ in dl ⇒ (max_tf, min_len) gives an exact bound."""
    mtf = jnp.take(block_max_tf, sel_blocks)
    mln = jnp.take(block_min_len, sel_blocks)
    norm = k1 * (1.0 - b + b * mln / avg_len)
    return sel_weights * (mtf / (mtf + norm))


# Python int literal, NOT jnp.int32(...): a module-level device scalar
# would be captured as a constant buffer by every jit using it; literals
# embed as immediates.
_SENTINEL = 0x7FFFFFFF


def scan_run_bound(n_terms: int, floor: int = 32) -> int:
    """Static ``max_run`` for the doubling segmented scans: the smallest
    power of two ≥ max(n_terms, floor). The scan's coverage window equals
    this bound (steps 1..bound/2 sum a run of exactly ``bound``), and
    rounding to a power of two caps the number of compiled variants."""
    r = floor
    while r < n_terms:
        r *= 2
    return r


def segmented_topk(keys: jax.Array, contribs: jax.Array, k: int,
                   sentinel, max_run: int = 32):
    """Top-k of per-key contribution sums WITHOUT a dense accumulator:
    sort (key, contrib) pairs by key, segmented-sum each key-run with a
    DOUBLING scan (Hillis-Steele with the key-equality carry — valid
    because runs are contiguous after the sort), then top-k over run
    totals at run-last positions.

    The doubling scan — not a global cumsum — is a PRECISION contract:
    a float32 prefix over 500K postings carries absolute error ~
    prefix·2^-24, which reorders top-k boundary docs (measured recall
    0.997 vs an exact scorer); summing each run's ≤``max_run`` elements
    directly keeps full f32 accuracy. ``max_run`` must bound the
    longest real run (per-doc entries ≤ query terms here; sentinel runs
    are longer but never read).

    Keys equal to `sentinel` (padding) sort last and never win. Returns
    (values [k], keys [k]); empty slots are (-inf, sentinel)."""
    sorted_k, sorted_c = jax.lax.sort((keys, contribs), num_keys=1)
    x = sorted_c
    step = 1
    while step < min(max_run, keys.shape[0]):
        prev_x = jnp.pad(x[:-step], (step, 0))
        prev_k = jnp.pad(sorted_k[:-step], (step, 0),
                         constant_values=-1)
        x = x + jnp.where(prev_k == sorted_k, prev_x, 0.0)
        step *= 2
    nxt = jnp.concatenate([sorted_k[1:],
                           jnp.full(1, -1, sorted_k.dtype)])
    is_last = sorted_k != nxt
    cand = jnp.where(is_last & (x > 0.0) & (sorted_k != sentinel),
                     x, -jnp.inf)
    vals, pos = jax.lax.top_k(cand, k)
    ids = jnp.take(sorted_k, pos)
    ids = jnp.where(jnp.isfinite(vals), ids, sentinel)
    return vals, ids


def bm25_sorted_topk(block_docids: jax.Array,   # int32 [TB, B]
                     block_tfs: jax.Array,      # float32 [TB, B]
                     sel_blocks: jax.Array,     # int32 [NB]
                     sel_weights: jax.Array,    # float32 [NB]
                     doc_lens: jax.Array,       # float32 [ND]
                     live: jax.Array,           # bool [ND]
                     avg_len: jax.Array, k1: float, b: float, k: int,
                     max_run: int = 32):
    """BM25 top-k WITHOUT a dense score accumulator — the TPU-native hot
    path. XLA scatter on TPU serializes updates (measured ~70ms for 8K
    postings), so instead of scattering into scores[ND] this kernel:

      1. gathers the selected postings blocks (gathers vectorize fine),
      2. sorts (docid, contribution) pairs by docid (`lax.sort` — bitonic
         on the VPU),
      3. sums each docid-run with a cumsum + run-boundary subtraction
         (the segmented-reduction trick: exclusive prefix at run start is
         propagated by cummax since prefixes are non-decreasing),
      4. top-k over run totals at run-last positions.

    Cost is O(P log P) in the number of query postings P — independent of
    corpus size, like Lucene's postings iteration, but batched and
    branch-free. Returns (values [k], docids [k]); empty slots are
    (-inf, sentinel).
    """
    d = jnp.take(block_docids, sel_blocks, axis=0)       # [NB, B]
    tf = jnp.take(block_tfs, sel_blocks, axis=0)
    dl = jnp.take(doc_lens, d)
    contrib = bm25_contrib(sel_weights, tf, dl, avg_len, k1, b)

    dflat = d.reshape(-1)
    cflat = contrib.reshape(-1)
    valid = tf.reshape(-1) > 0.0
    # padding sorts to the end; deleted docs contribute 0 and are dropped
    # by the totals>0 mask
    dkey = jnp.where(valid, dflat, _SENTINEL)
    cflat = jnp.where(valid & jnp.take(live, dflat), cflat, 0.0)
    # max_run MUST bound the per-doc term-instance count — callers with
    # unbounded term lists pass scan_run_bound(n_terms) (a 31+-term
    # query under the fixed 32 default silently drops contributions)
    return segmented_topk(dkey, cflat, k, _SENTINEL, max_run=max_run)


# ---------------------------------------------------------------------------
# Scalar reference (the "AbstractQueryTestCase" analogue: kernels are
# property-tested against this, SURVEY.md §4 lesson)
# ---------------------------------------------------------------------------

def bm25_reference_scores(postings_per_term, idfs, doc_lens, avg_len,
                          k1: float, b: float) -> np.ndarray:
    """Pure-numpy scalar BM25: postings_per_term is a list of (docids, tfs)
    arrays, one per query term, idfs the matching idf list."""
    scores = np.zeros(len(doc_lens), np.float64)
    for (docids, tfs), w in zip(postings_per_term, idfs):
        for d, tf in zip(docids, tfs):
            dl = doc_lens[d]
            scores[d] += w * tf / (tf + k1 * (1 - b + b * dl / avg_len))
    return scores


def bm25_sorted_topk_batch(block_docids: jax.Array,   # int32 [TB, B]
                           block_tfs: jax.Array,      # float32 [TB, B]
                           sel_blocks: jax.Array,     # int32 [Q, NB]
                           sel_weights: jax.Array,    # float32 [Q, NB]
                           doc_lens: jax.Array,       # float32 [ND]
                           live: jax.Array,           # bool [ND]
                           avg_len, k1: float, b: float, k: int,
                           max_run: int = 32):
    """Many queries per launch: vmap of bm25_sorted_topk over a [Q, NB]
    selection batch → ([Q, k] values, [Q, k] docids).

    This is the continuous-batching serving shape (SURVEY.md §7 hard
    part 5): launch overhead amortizes over
    Q queries, and the per-query sorts batch onto the VPU. Queries with
    fewer postings pad their selection with the reserved zero block."""
    return jax.vmap(
        lambda s, w: bm25_sorted_topk(block_docids, block_tfs, s, w,
                                      doc_lens, live, avg_len, k1, b, k,
                                      max_run=max_run)
    )(sel_blocks, sel_weights)
