"""On-device top-k selection and merge.

Replaces Lucene's TopScoreDocCollector + the coordinator's TopDocs.merge
(ref: search/query/TopDocsCollectorContext.java, action/search/
SearchPhaseController.java:154-218). Exact top-k via lax.top_k; a TPU
approximate variant via lax.approx_max_k (recall-targeted, MIPS-style
partial reduction) for latency-critical paths; and a pairwise merge used
both host-side across segments and inside collectives across shards.

Tie-breaking: Lucene orders equal scores by ascending docid. lax.top_k
does so on the CPU backend but not on TPU, where the serving kernels
use ops/plan._stable_topk instead; the merge re-sorts by (-score, docid).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from elasticsearch_tpu.telemetry.engine import tracked_jit


@tracked_jit(static_argnames=("k",))
def topk(scores: jax.Array, k: int):
    """Exact (values, indices) top-k, descending; ties → ascending index."""
    return jax.lax.top_k(scores, k)


@tracked_jit(static_argnames=("k", "recall_target"))
def approx_topk(scores: jax.Array, k: int, recall_target: float = 0.95):
    """TPU-optimized approximate top-k (lax.approx_max_k): ~constant-factor
    faster at large n; recall_target trades speed for exactness."""
    return jax.lax.approx_max_k(scores, k, recall_target=recall_target)


@tracked_jit(static_argnames=("k",))
def masked_topk(scores: jax.Array, mask: jax.Array, k: int):
    """Top-k over masked docs only. The caller supplies the full mask
    (matched & live & not-padding — filter-only queries legitimately score
    0.0, so matching is NOT inferred from score). Masked-out docs drop to
    -inf; a returned value of -inf means "fewer than k matches"."""
    masked = jnp.where(mask, scores, -jnp.inf)
    return jax.lax.top_k(masked, k)


@tracked_jit(static_argnames=("k",))
def merge_topk(values_a: jax.Array, ids_a: jax.Array,
               values_b: jax.Array, ids_b: jax.Array, k: int):
    """Merge two top-k lists into one, re-tie-breaking by ascending id.

    Sort key packs (-score, id) lexicographically via sort over negated
    score with a stable secondary sort on id (jnp.lexsort semantics).
    """
    v = jnp.concatenate([values_a, values_b])
    i = jnp.concatenate([ids_a, ids_b])
    # primary: score desc; secondary: id asc. lax.sort is stable, so sort
    # by id first, then by negated score.
    order_id = jnp.argsort(i, stable=True)
    v2, i2 = v[order_id], i[order_id]
    order_s = jnp.argsort(-v2, stable=True)
    v3, i3 = v2[order_s], i2[order_s]
    return v3[:k], i3[:k]
