"""The plain reference and its lower-precision control.

The reference imports nothing of the program. ``topk`` and the cosine
oracle are copied from ``chip_smoke.py`` (PR 21); ``bm25_exact`` lives
beside the generator in ``corpus.py``. The controls compute the same
answers in bfloat16, the next precision below the float32 that both
configurations state: they stand in the program's place to show that
the comparison in ``judge`` fails a lower-precision path.
"""

from __future__ import annotations

from typing import Sequence

import ml_dtypes
import numpy as np

from benchmark.corpus import B, K1

BF16 = ml_dtypes.bfloat16


def topk(scores: np.ndarray, k: int) -> np.ndarray:
    """Exact top-k docids of positive scores, (score desc, docid asc):
    Lucene's order, lowest docid among ties."""
    pos = np.nonzero(scores > 0)[0]
    if len(pos) > 4 * k:
        kth = np.partition(scores[pos], len(pos) - k)[len(pos) - k]
        pos = pos[scores[pos] >= kth]
    return pos[np.lexsort((pos, -scores[pos]))][:k]


def bf16(x) -> np.ndarray:
    """Round to bfloat16 (nearest even) and hand back float32."""
    return np.asarray(x, np.float32).astype(BF16).astype(np.float32)


def bm25_bf16(corpus, terms: Sequence[int]) -> np.ndarray:
    """BM25 of every doc with each operation rounded to bfloat16: the
    control for the float32 rail."""
    lens = corpus["lens"].astype(np.float32)
    gs, d_all, tf_all = (corpus["group_start"], corpus["doc_ids"],
                         corpus["tf"])
    n = len(lens)
    avg = bf16(lens.mean())
    norm = bf16(K1 * bf16(bf16(1.0 - B) + bf16(B * bf16(lens / avg))))
    scores = np.zeros(n, np.float32)
    for t in terms:
        d = d_all[gs[t]:gs[t + 1]]
        f = bf16(tf_all[gs[t]:gs[t + 1]])
        df = len(d)
        if df == 0:
            continue
        idf = bf16(np.log1p((n - df + 0.5) / (df + 0.5)))
        scores[d] = bf16(scores[d] + bf16(idf * bf16(f / bf16(f + norm[d]))))
    return scores


def _unit(x: np.ndarray) -> np.ndarray:
    n = np.linalg.norm(x, axis=1, keepdims=True)
    return x / np.where(n > 0, n, 1.0)


def _dots(vecs: np.ndarray, q: np.ndarray, dtype, rows: int) -> np.ndarray:
    """unit(vecs) @ q.T, [n_docs, n_queries] float32, on JAX's default
    device in blocks of ``rows`` docs: operands in ``dtype``, products
    accumulated in float32 at full precision."""
    import jax
    import jax.numpy as jnp
    qd = jnp.asarray(q, dtype)
    out = np.empty((len(vecs), len(q)), np.float32)
    for lo in range(0, len(vecs), rows):
        v = jnp.asarray(_unit(vecs[lo:lo + rows]), dtype)
        out[lo:lo + rows] = np.asarray(jnp.dot(
            v, qd.T, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
    return out


def cosine_exact(vecs: np.ndarray, queries: np.ndarray,
                 candidates: int = 256, rows: int = 1 << 18):
    """Exact cosine scores (1 + cos) / 2, one float64 array over the docs
    per query. A float32 pass over every doc (on the device, in blocks)
    finds each query's ``candidates`` best; those are then scored again
    in float64 on the host. Float32 errs by ~1e-7 where the 10th and the
    256th best of 2M random docs lie ~1e-2 apart, so the exact top-k is
    always among the candidates, and every score outside them is off by
    float32 rounding alone."""
    q64 = _unit(np.asarray(queries, np.float64))
    dots = _dots(vecs, q64.astype(np.float32), np.float32, rows)
    c = min(candidates, len(vecs))
    for j in range(len(q64)):
        s = (1.0 + dots[:, j].astype(np.float64)) / 2.0
        cand = np.argpartition(-dots[:, j], c - 1)[:c]
        s[cand] = (1.0 + _unit(vecs[cand].astype(np.float64)) @ q64[j]) / 2
        yield s


def cosine_bf16(vecs: np.ndarray, queries: np.ndarray,
                rows: int = 1 << 18):
    """The control: unit vectors and queries in bfloat16, their dot
    products accumulated in float32 (as the chip's matrix unit does) and
    every result rounded to bfloat16; one array per query."""
    dots = _dots(vecs, _unit(np.asarray(queries, np.float32)),
                 np.dtype(BF16), rows)
    for j in range(dots.shape[1]):
        yield bf16(bf16(1.0 + bf16(dots[:, j])) / 2.0)


def judge(ids: Sequence[str], got: Sequence[float], scores: np.ndarray,
          k: int) -> dict:
    """Compare one served answer with the reference.

    ``ids``/``got`` are the answer's hits in order, ``scores`` the
    reference's score of every doc. Two numbers, each the worst over the
    answer's ranks, relative to the reference's score:

    - ``score_gap``: how far a returned score lies from the reference's
      score of that same doc;
    - ``rank_gap``: how far the doc at rank i scores below the
      reference's i-th best. A missing, unknown, repeated or surplus hit
      reads 1.

    Equal scores in another order read 0; a lower precision, a wrong doc
    or a lost answer does not."""
    truth = topk(scores, k)
    best = scores[truth]
    seen = set()
    score_gap = 0.0
    mine = np.zeros(max(len(ids), len(truth)), np.float64)
    for i, (s_id, s) in enumerate(zip(ids, got)):
        try:
            d = int(s_id)
        except (TypeError, ValueError):
            d = -1
        if d < 0 or d >= len(scores) or d in seen:
            score_gap = max(score_gap, 1.0)
            continue
        seen.add(d)
        ref = scores[d]
        mine[i] = ref
        score_gap = max(score_gap, abs(float(s) - ref) / max(abs(ref),
                                                             1e-30))
    want = np.zeros_like(mine)
    want[:len(best)] = best
    rank_gap = 0.0
    for i in range(len(mine)):
        if want[i] > 0:
            rank_gap = max(rank_gap, (want[i] - mine[i]) / want[i])
        elif i < len(ids):
            rank_gap = 1.0       # a hit where the reference has none
    return {"score_gap": float(score_gap), "rank_gap": float(rank_gap)}
