"""θ-cached essential lane (ops/fastpath.bm25_essential_topk_batch):
certified outputs identical to the full exact v1 kernel over all of the
query's terms, honest ok=0 when the certificate can't close."""

import numpy as np
import pytest

import jax

from elasticsearch_tpu.ops import fastpath as fp

BLOCK = 128
K1, B = 1.2, 0.75


def build_segment(rng, n_docs=600, n_hot=2, n_rare=3):
    """Hot terms (high df, low idf — the NE side of a MaxScore split)
    plus rare terms; block layout like index/segment.py."""
    blocks_d, blocks_t = [], []
    tbs, nb, dfs = [], [], []
    next_block = 0
    terms = []
    for i in range(n_hot + n_rare):
        df = int(rng.integers(int(n_docs * 0.6), n_docs)) if i < n_hot \
            else int(rng.integers(8, 40))
        docs = np.sort(rng.choice(n_docs, size=df,
                                  replace=False)).astype(np.int32)
        tfs = rng.integers(1, 6, size=df).astype(np.float32)
        nblk = (df + BLOCK - 1) // BLOCK
        tbs.append(next_block)
        nb.append(nblk)
        dfs.append(df)
        next_block += nblk
        pad = nblk * BLOCK - df
        blocks_d.append(np.concatenate(
            [docs, np.zeros(pad, np.int32)]).reshape(nblk, BLOCK))
        blocks_t.append(np.concatenate(
            [tfs, np.zeros(pad, np.float32)]).reshape(nblk, BLOCK))
        terms.append((docs, tfs))
    blocks_d.append(np.zeros((1, BLOCK), np.int32))
    blocks_t.append(np.zeros((1, BLOCK), np.float32))
    bd = np.concatenate(blocks_d)
    bt = np.concatenate(blocks_t)
    lens = rng.integers(5, 80, size=n_docs).astype(np.float32)
    return dict(bd=bd, bt=bt, tbs=np.asarray(tbs), nb=np.asarray(nb),
                dfs=np.asarray(dfs), zero_block=bd.shape[0] - 1,
                lens=lens, avg=float(lens.mean()), terms=terms,
                flat_d=bd.reshape(-1), flat_t=bt.reshape(-1),
                n_docs=n_docs, n_hot=n_hot)


def idf_of(seg, t):
    n = seg["n_docs"]
    df = seg["dfs"][t]
    return float(np.log1p((n - df + 0.5) / (df + 0.5)))


def _bucket_for(seg, terms):
    need = int(sum(seg["nb"][t] for t in terms))
    nbk = 64
    while nbk < need:
        nbk *= 2
    return nbk


def full_v1(seg, ess_and_ne, k, masks=None, mask_id=0):
    """Reference: the exact full kernel over ALL the query's terms."""
    q = 1
    nbk = _bucket_for(seg, ess_and_ne)
    sel = np.full((q, nbk), seg["zero_block"], np.int32)
    ws = np.zeros((q, nbk), np.float64)
    pos = 0
    for t in ess_and_ne:
        cnt = int(seg["nb"][t])
        start = int(seg["tbs"][t])
        sel[0, pos:pos + cnt] = np.arange(start, start + cnt)
        ws[0, pos:pos + cnt] = idf_of(seg, t)
        pos += cnt
    if masks is None:
        masks = np.ones((fp.F_SLOTS, seg["n_docs"]), bool)
    out = np.asarray(fp.bm25_topk_total_batch(
        seg["bd"], seg["bt"], sel, ws, seg["lens"][seg["bd"]], masks,
        np.full(q, mask_id, np.int32), np.float64(seg["avg"]),
        K1, B, k))
    vals = out[0, :k]
    ids = out[0, k:2 * k].astype(np.int32)
    order = np.lexsort((ids, -vals))
    return vals[order], ids[order], int(out[0, 2 * k:].astype(np.int32)[0])


def run_essential(seg, ess, ne, ne_bound, k, masks=None, mask_id=0):
    """The essential lane's packed output for one essential/NE split."""
    q = 1
    nbk = _bucket_for(seg, ess)
    sel = np.full((q, nbk), seg["zero_block"], np.int32)
    ws = np.zeros((q, nbk), np.float64)
    pos = 0
    for t in ess:
        cnt = int(seg["nb"][t])
        start = int(seg["tbs"][t])
        sel[0, pos:pos + cnt] = np.arange(start, start + cnt)
        ws[0, pos:pos + cnt] = idf_of(seg, t)
        pos += cnt
    ne_start = np.zeros((q, fp.NE_SLOTS), np.int32)
    ne_len = np.zeros((q, fp.NE_SLOTS), np.int32)
    ne_idf = np.zeros((q, fp.NE_SLOTS), np.float64)
    for i, t in enumerate(ne):
        ne_start[0, i] = int(seg["tbs"][t]) * BLOCK
        ne_len[0, i] = int(seg["dfs"][t])
        ne_idf[0, i] = idf_of(seg, t)
    nbound = np.full(q, ne_bound, np.float64)
    if masks is None:
        masks = np.ones((fp.F_SLOTS, seg["n_docs"]), bool)
    mids = np.full(q, mask_id, np.int32)
    return np.asarray(fp.bm25_essential_topk_batch(
        seg["bd"], seg["bt"], seg["flat_d"], seg["flat_t"], sel, ws,
        seg["lens"][seg["bd"]], seg["lens"], masks, mids, ne_start,
        ne_len, ne_idf, nbound,
        np.float64(seg["avg"]), K1, B, k))


def unpack(out, k):
    vals = out[0, :k]
    ids = out[0, k:2 * k].astype(np.int32)
    ok = int(out[0, 2 * k:].astype(np.int32)[0])
    return vals, ids, ok


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_essential_matches_full(seed):
    rng = np.random.default_rng(seed)
    seg = build_segment(rng)
    k = 10
    query = [2, 3, 0]       # two rare + one hot
    ess, ne = [2, 3], [0]
    # a true Σ maxc_ne bound for term 0
    docs, tfs = seg["terms"][0]
    norm_min = K1 * (1 - B + B * seg["lens"][docs].min() / seg["avg"])
    bound = idf_of(seg, 0) * float(
        (tfs / (tfs + norm_min)).max()) + 1e-9
    fv, fi, _ftot = full_v1(seg, query, k)
    ev, ei, eok = unpack(run_essential(seg, ess, ne, bound, k), k)
    assert eok == 1, "the certificate closes on a small essential union"
    np.testing.assert_array_equal(ei, fi)
    np.testing.assert_allclose(ev, fv, rtol=0, atol=0)


def test_essential_unused_slots_are_inert():
    rng = np.random.default_rng(7)
    seg = build_segment(rng)
    k = 5
    # no NE terms at all: the lane degenerates to the essential union,
    # which is the full kernel over the same terms
    ev, ei, eok = unpack(run_essential(seg, [2, 3], [], 0.0, k), k)
    fv, fi, _ftot = full_v1(seg, [2, 3], k)
    assert eok == 1
    np.testing.assert_array_equal(ei, fi)
    np.testing.assert_allclose(ev, fv, rtol=0, atol=0)


def test_essential_respects_filter_mask():
    rng = np.random.default_rng(11)
    seg = build_segment(rng)
    k = 5
    masks = np.ones((fp.F_SLOTS, seg["n_docs"]), bool)
    masks[3] = False
    masks[3, : seg["n_docs"] // 2] = True      # keep low half only
    bound = idf_of(seg, 0) * 1.0 + 1e-9
    ev, ei, eok = unpack(run_essential(seg, [2, 3], [0], bound, k,
                                       masks=masks, mask_id=3), k)
    fv, fi, _ftot = full_v1(seg, [2, 3, 0], k, masks=masks, mask_id=3)
    assert eok == 1
    np.testing.assert_array_equal(ei, fi)
    np.testing.assert_allclose(ev, fv, rtol=0, atol=0)
    finite = np.isfinite(ev)
    assert np.all(ei[finite] < seg["n_docs"] // 2)


def test_essential_certificate_refuses_when_bound_wide():
    """A huge Σ maxc_ne makes overflow_bound beat the kth — the lane
    must refuse (ok=0) instead of certifying a possibly-wrong top-k.
    The essential union must exceed CAND docs (otherwise every match is
    a candidate and the certificate closes trivially — correctly)."""
    rng = np.random.default_rng(13)
    nd = int(fp.CAND * 1.5)
    seg = build_segment(rng, n_docs=nd, n_hot=2, n_rare=1)
    # make hot term 0's df exceed CAND so phase 1 overflows (the
    # adaptive c = min(CAND, lanes-1) must saturate at CAND)
    while seg["dfs"][0] <= fp.CAND:
        seg = build_segment(np.random.default_rng(
            int(rng.integers(1 << 30))), n_docs=nd, n_hot=2, n_rare=1)
    k = 10
    _ev, _ei, eok = unpack(run_essential(seg, [0], [1], 1e6, k), k)
    assert eok == 0
