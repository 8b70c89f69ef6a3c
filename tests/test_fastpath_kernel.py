"""ops/fastpath.py kernel invariants, pinned STRICTLY at the unit level
(the HTTP-level equivalence test allows last-ulp summation-order swaps
between the fast and dense paths; these tests allow none):

1. bit-exact agreement with ops/bm25.bm25_sorted_topk on identical
   inputs (same sort-based arithmetic, so no tolerance),
2. stable tie-break — exact-score ties at the k boundary select the
   LOWEST docids (the Lucene / exact-truth contract; TPU top_k alone
   does not guarantee this),
3. exact totals and mask-row isolation,
4. each posting's document length read as a block row (``block_lens``)
   gives the packed result of gathering it by docid, bit for bit.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticsearch_tpu.ops import fastpath as fp
from elasticsearch_tpu.ops.bm25 import _SENTINEL, bm25_sorted_topk
from elasticsearch_tpu.ops.fastpath import F_SLOTS, bm25_topk_total_batch
from elasticsearch_tpu.ops.plan import unpack_ids

ND = 4096
TB = 120
B = 8
K = 64


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    bd = np.sort(rng.integers(0, ND, (TB, B)).astype(np.int32), axis=1)
    bt = rng.integers(0, 4, (TB, B)).astype(np.float32)
    lens = rng.integers(5, 60, ND).astype(np.float32)
    live = np.ones(ND, bool)
    return bd, bt, lens, live


def run_batch(bd, bt, sels, wss, lens, masks, mask_ids, k=K):
    packed = np.asarray(bm25_topk_total_batch(
        bd, bt, np.stack(sels), np.stack(wss), lens[bd], masks,
        np.asarray(mask_ids, np.int32), np.float32(30.0), 1.2, 0.75, k))
    out = []
    for q in range(len(sels)):
        vals = packed[q, :k]
        ids = unpack_ids(packed[q, k:2 * k])
        total = int(unpack_ids(packed[q, 2 * k:])[0])
        out.append((vals, ids, total))
    return out


def _f64_expected(bd, bt, lens, sel, ws, k):
    """Exact float64 reference: per-doc sums + (score desc, docid asc)
    top-k — the truth the kernel's exactness contract is measured
    against (bench.py cpu_exact_truth shape)."""
    scores = np.zeros(ND, np.float64)
    for b, w in zip(sel, ws):
        if b >= bd.shape[0] or w == 0.0:
            continue
        for d, tf in zip(bd[b], bt[b]):
            if tf > 0:
                norm = 1.2 * (1 - 0.75 + 0.75 * float(lens[d]) / 30.0)
                scores[d] += float(w) * tf / (tf + norm)
    matched = np.nonzero(scores > 0)[0]
    order = matched[np.lexsort((matched, -scores[matched]))][:k]
    return order, scores


def test_exact_vs_f64_reference(data):
    """The kernel must reproduce the float64 exact top-k — same doc
    set, same (score desc, docid asc) order, scores to f32 accuracy.
    (Cross-kernel bit equality is NOT the invariant: lax.sort is
    unstable on equal keys, so two compilations may sum a doc's
    contributions in different orders.)"""
    bd, bt, lens, live = data
    rng = np.random.default_rng(5)
    sels, wss = [], []
    for _ in range(4):
        nsel = int(rng.integers(2, 12))
        sel = np.full(16, TB, np.int32)      # pad = zero block (TB)
        ws = np.zeros(16, np.float32)
        sel[:nsel] = rng.choice(TB, nsel, replace=False)
        ws[:nsel] = rng.uniform(0.3, 2.5, nsel).astype(np.float32)
        sels.append(sel)
        wss.append(ws)
    masks = jnp.stack([jnp.asarray(live)] * F_SLOTS)
    results = run_batch(bd, bt, sels, wss, lens, masks, [0, 0, 0, 0])
    for (vals, ids, total), sel, ws in zip(results, sels, wss):
        expected, scores = _f64_expected(bd, bt, lens, sel, ws, K)
        fin = np.isfinite(vals)
        got = ids[fin]
        # host-side tie ordering (the serving layer's lexsort)
        got = got[np.lexsort((got, -vals[fin]))]
        assert np.array_equal(np.sort(got), np.sort(expected))
        assert total == int((scores > 0).sum())
        np.testing.assert_allclose(
            np.sort(vals[fin])[::-1], np.sort(scores[expected])[::-1],
            rtol=2e-6)
        # the reference single-query kernel agrees on the same contract
        rv, ri = bm25_sorted_topk(bd, bt, sel, ws, lens,
                                  jnp.asarray(live), np.float32(30.0),
                                  1.2, 0.75, K)
        rfin = np.isfinite(np.asarray(rv))
        assert np.array_equal(np.sort(np.asarray(ri)[rfin]),
                              np.sort(expected))


def test_stable_tiebreak_lowest_docids_win():
    """Many docs tie bit-exactly at the kth score: the winners must be
    the lowest docids (truth/Lucene order), not top_k's whim."""
    nd = 2048
    # one term, one tf, one length → every matched doc scores the SAME
    docs = np.arange(0, 2000, dtype=np.int32)
    tb = len(docs) // B
    bd = docs.reshape(tb, B)
    bt = np.ones((tb, B), np.float32)
    bd = np.concatenate([bd, np.zeros((1, B), np.int32)])     # zero block
    bt = np.concatenate([bt, np.zeros((1, B), np.float32)])
    lens = np.full(nd, 30.0, np.float32)
    k = 100
    sel = np.full(256, tb, np.int32)
    ws = np.zeros(256, np.float32)
    sel[:tb] = np.arange(tb)
    ws[:tb] = 1.0
    masks = jnp.stack([jnp.ones(nd, bool)] * F_SLOTS)
    (vals, ids, total), = run_batch(bd, bt, [sel], [ws], lens, masks,
                                    [0], k=k)
    assert total == 2000
    assert np.array_equal(np.sort(ids), np.arange(k, dtype=np.int32))
    assert np.allclose(vals, vals[0])


def test_mask_rows_isolate_queries(data):
    bd, bt, lens, live = data
    sel = np.full(16, TB, np.int32)
    ws = np.zeros(16, np.float32)
    sel[:4] = [3, 9, 20, 31]
    ws[:4] = 1.0
    # row 1 masks out the low half of the doc space
    m1 = live.copy()
    m1[: ND // 2] = False
    masks = jnp.stack([jnp.asarray(live), jnp.asarray(m1)]
                      + [jnp.asarray(live)] * (F_SLOTS - 2))
    (v0, i0, t0), (v1, i1, t1) = run_batch(
        bd, bt, [sel, sel], [ws, ws], lens, masks, [0, 1])
    assert t1 < t0
    assert (i1[np.isfinite(v1)] >= ND // 2).all()
    # the unfiltered row is unaffected by its neighbor's mask
    rv, ri = bm25_sorted_topk(bd, bt, sel, ws, lens, jnp.asarray(live),
                              np.float32(30.0), 1.2, 0.75, K)
    fin = np.isfinite(np.asarray(rv))
    assert np.array_equal(i0[fin], np.asarray(ri)[fin])


def test_empty_and_overfull():
    nd = 512
    bd = np.zeros((2, B), np.int32)
    bt = np.zeros((2, B), np.float32)
    lens = np.full(nd, 10.0, np.float32)
    masks = jnp.stack([jnp.ones(nd, bool)] * F_SLOTS)
    sel = np.full(8, 1, np.int32)     # zero block only
    ws = np.zeros(8, np.float32)
    (vals, ids, total), = run_batch(bd, bt, [sel], [ws], lens, masks,
                                    [0], k=16)
    assert total == 0
    assert not np.isfinite(vals).any()
    assert (ids == _SENTINEL).all()


def test_profile_breakdown_stages():
    """profile:true returns per-stage timing distinguishing device from
    host work, a real collector entry, rewrite_time, and a fetch
    section (VERDICT r2 item 9; ref QueryProfiler.java:38)."""
    import tempfile

    from elasticsearch_tpu.node import Node
    with tempfile.TemporaryDirectory() as tmp:
        node = Node(data_path=tmp)
        try:
            c = node.rest_controller
            for i in range(20):
                c.dispatch("PUT", f"/idx/_doc/{i}", {},
                           {"title": f"fox doc {i}", "rank": i})
            c.dispatch("POST", "/idx/_refresh", {}, None)
            status, r = c.dispatch("POST", "/idx/_search", {}, {
                "query": {"match": {"title": "fox"}},
                "profile": True, "size": 5})
            assert status == 200
            shard = r["profile"]["shards"][0]
            q = shard["searches"][0]["query"][0]
            bd = q["breakdown"]
            assert q["time_in_nanos"] > 0
            assert bd["device_time_in_nanos"] >= 0
            assert bd["host_time_in_nanos"] > 0
            # at least one real execution stage was recorded
            assert any(k in bd for k in ("launch", "score", "topk"))
            coll = shard["searches"][0]["collector"][0]
            assert coll["name"].endswith("TopDocsCollector")
            assert coll["reason"] == "search_top_hits"
            assert shard["searches"][0]["rewrite_time"] >= 0
            assert shard["fetch"]["time_in_nanos"] > 0
        finally:
            node.close()


def _padded_corpus(seed):
    """Block postings over N_DOCS real docs in an ND-wide padded doc
    space, as a registered segment holds them: ``live`` is False only
    on the padded docids, and the only lanes that point there carry
    tf 0 (block tails)."""
    n_docs = ND - 96
    rng = np.random.default_rng(seed)
    bd = np.sort(rng.integers(0, n_docs, (TB, B)).astype(np.int32), axis=1)
    bt = rng.integers(1, 4, (TB, B)).astype(np.float32)
    tail = rng.random(TB) < 0.3
    bd[tail, -2:] = ND - 1
    bt[tail, -2:] = 0.0
    lens = rng.integers(5, 60, ND).astype(np.float32)
    live = np.arange(ND) < n_docs
    return bd, bt, lens, live


def _plain_cohort(seed):
    """Four queries drawn from ``seed``, the second selecting one block
    twice (a duplicated term), then a padded row and a row the host
    zeroed for an unknown filter term: both all zero block (TB)."""
    rng = np.random.default_rng(100 + seed)
    sels, wss = [], []
    for qi in range(4):
        nsel = int(rng.integers(2, 12))
        sel = np.full(16, TB, np.int32)
        ws = np.zeros(16, np.float32)
        sel[:nsel] = rng.choice(TB, nsel, replace=False)
        ws[:nsel] = rng.uniform(0.3, 2.5, nsel).astype(np.float32)
        if qi == 1:
            sel[nsel], ws[nsel] = sel[0], ws[0]
        sels.append(sel)
        wss.append(ws)
    for _ in range(2):
        sels.append(np.full(16, TB, np.int32))
        wss.append(np.zeros(16, np.float32))
    return np.stack(sels), np.stack(wss)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_unmasked_launch_matches_the_plain_stack_bit_for_bit(seed):
    """masks=None, mask_ids=None (a cohort with no filter row) gives
    the packed result of the masked program over a stack of live
    columns, bit for bit: every lane the mask would drop already has
    tf 0."""
    bd, bt, lens, live = _padded_corpus(seed)
    sels, wss = _plain_cohort(seed)
    bd = np.concatenate([bd, np.zeros((1, B), np.int32)])   # zero block
    bt = np.concatenate([bt, np.zeros((1, B), np.float32)])
    masks = jnp.stack([jnp.asarray(live)] * F_SLOTS)
    args = (bd, bt, sels, wss, lens[bd])
    masked = np.asarray(bm25_topk_total_batch(
        *args, masks, np.zeros(len(sels), np.int32), np.float32(30.0),
        1.2, 0.75, K))
    plain = np.asarray(bm25_topk_total_batch(
        *args, None, None, np.float32(30.0), 1.2, 0.75, K))
    assert np.array_equal(masked.view(np.uint32), plain.view(np.uint32))
    # the dropped rows answer nothing, the others something
    totals = unpack_ids(plain[:, 2 * K])
    assert (totals[:4] > 0).all() and (totals[4:] == 0).all()


def _doc_len_gather(bd, bt, doc_lens, sel_blocks):
    """A query's postings as the kernels read them before ``block_lens``:
    docids and tfs as block rows, each posting's length gathered from
    the per-doc ``doc_lens`` by its docid."""
    dt = fp._score_dtype()
    d = jnp.take(bd, sel_blocks, axis=0)
    return (d, jnp.take(bt, sel_blocks, axis=0).astype(dt),
            jnp.take(doc_lens, d).astype(dt))


@partial(jax.jit, static_argnames=("k",))
def _v1_reference(bd, bt, sels, wss, doc_lens, masks, mask_ids, avg, k):
    def one(s, w, mid):
        return fp._topk_total(_doc_len_gather(bd, bt, doc_lens, s), w,
                              fp._live_col(masks, mid), avg, 1.2, 0.75, k)
    return fp._pack(*jax.vmap(one)(sels, wss, mask_ids))


@partial(jax.jit, static_argnames=("n_slots", "k"))
def _v2m_reference(bd, bt, sels, wss, doc_lens, masks, mask_ids, avg,
                   n_slots, k):
    def one(s, w, mid):
        return fp._keyed_contribs(_doc_len_gather(bd, bt, doc_lens, s), w,
                                  fp._live_col(masks, mid), avg, 1.2, 0.75)
    keys, cons = jax.vmap(one)(sels, wss, mask_ids)
    return fp._merge_topk_total(keys, cons, n_slots, k)


def _slotted_cohort(seed, nb, n_slots=16):
    """Six queries at bucket ``nb``: each used slot holds one random
    block (ascending, its tf-0 tail keyed last) and then zero blocks,
    so the slot layout holds for v2m as for v1; one query repeats a
    block (a duplicated term), the last selects nothing."""
    rng = np.random.default_rng(200 + seed)
    slot = nb // n_slots
    sels = np.full((6, nb), TB, np.int32)
    wss = np.zeros((6, nb), np.float32)
    for qi in range(5):
        used = int(rng.integers(2, n_slots + 1))
        blocks = rng.choice(TB, used, replace=False)
        if qi == 1:
            blocks[-1] = blocks[0]
        sels[qi, ::slot][:used] = blocks
        wss[qi, ::slot][:used] = rng.uniform(0.3, 2.5, used)
    return sels, wss


@pytest.mark.parametrize("rail", ["f32", "f64"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("nb", [16, 32])
@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("kernel", ["v2m", "v1"])
def test_block_lens_match_the_doc_length_gather_bit_for_bit(
        kernel, masked, nb, seed, rail):
    """v2m and v1 reading each posting's length as a block row of
    ``block_lens = doc_lens[block_docids]`` pack the same bits as the
    same kernel gathering ``doc_lens`` by docid: every lane sees the
    same length and runs the same arithmetic. A masked cohort carries a
    filter row for some queries."""
    bd, bt, lens, live = _padded_corpus(seed)
    bd = np.concatenate([bd, np.zeros((1, B), np.int32)])   # zero block
    bt = np.concatenate([bt, np.zeros((1, B), np.float32)])
    sels, wss = _slotted_cohort(seed, nb)
    if masked:
        rng = np.random.default_rng(300 + seed)
        masks = np.stack([live] * F_SLOTS)
        masks[1] &= rng.random(ND) < 0.5
        masks, mids = jnp.asarray(masks), jnp.asarray([0, 1, 1, 0, 1, 0],
                                                      jnp.int32)
    else:
        masks = mids = None
    with jax.enable_x64(rail == "f64"):
        wd = np.float64 if rail == "f64" else np.float32
        args = (bd, bt, sels, wss.astype(wd))
        tail = (masks, mids, wd(30.0))
        if kernel == "v2m":
            got = fp.bm25_topk_total_merge_batch(
                *args, jnp.asarray(lens[bd]), *tail, 16, 1.2, 0.75, K)
            want = _v2m_reference(*args, lens, *tail, n_slots=16, k=K)
        else:
            got = fp.bm25_topk_total_batch(
                *args, jnp.asarray(lens[bd]), *tail, 1.2, 0.75, K)
            want = _v1_reference(*args, lens, *tail, k=K)
        got, want = np.asarray(got), np.asarray(want)
    assert np.array_equal(got.view(np.uint32), want.view(np.uint32))
    totals = unpack_ids(got[:, 2 * K])
    assert (totals[:5] > 0).all() and totals[5] == 0


@pytest.mark.parametrize("rail", ["f32", "f64"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_essential_phase1_block_lens_match_the_doc_length_gather(seed,
                                                                 rail):
    """The essential lane's phase 1 gives the same candidates, essential
    scores and overflow bound, bit for bit, from block-row lengths as
    from lengths gathered by docid."""
    bd, bt, lens, live = _padded_corpus(seed)
    bd = np.concatenate([bd, np.zeros((1, B), np.int32)])
    bt = np.concatenate([bt, np.zeros((1, B), np.float32)])
    sels, wss = _slotted_cohort(seed, 32)

    def phase1(postings_of, sels, wss, bounds):
        def one(s, w, nb):
            return fp._essential_phase1(postings_of(s), w, jnp.asarray(live),
                                        nb, 30.0, 1.2, 0.75)
        return jax.jit(jax.vmap(one))(sels, wss, bounds)

    with jax.enable_x64(rail == "f64"):
        wd = np.float64 if rail == "f64" else np.float32
        bounds = np.linspace(0.0, 2.0, len(sels)).astype(wd)
        got = phase1(partial(fp._postings, bd, bt, jnp.asarray(lens[bd])),
                     sels, wss.astype(wd), bounds)
        want = phase1(partial(_doc_len_gather, bd, bt, lens),
                      sels, wss.astype(wd), bounds)
        got = [np.asarray(x) for x in got]
        want = [np.asarray(x) for x in want]
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        assert np.array_equal(g.view(np.uint8), w.view(np.uint8))
    assert np.isfinite(got[1]).any()


def test_block_lens_must_be_laid_out_as_the_tfs():
    """A per-doc length vector where the kernels take the block table
    is refused at trace time, not broadcast into wrong scores."""
    bd, bt, lens, _live = _padded_corpus(0)
    sels, wss = _slotted_cohort(0, 16)
    with pytest.raises(ValueError, match="block_lens"):
        fp.bm25_topk_total_batch(bd, bt, sels, wss, lens, None, None,
                                 np.float32(30.0), 1.2, 0.75, K)
