"""v2m serving kernel (the v1 exact kernel with its sort replaced by the
bitonic merge of per-term slots) vs the exact v1 kernel, and the v2m and
v1 launch sites of the fast path."""

import numpy as np
import pytest

import jax.numpy as jnp

from elasticsearch_tpu.ops import fastpath as fp
from elasticsearch_tpu.ops.plan import unpack_ids

BLOCK = 128


def build_segment(rng, n_docs, n_terms, df_range=(40, 400)):
    """Block-layout postings like index/segment.py builds them."""
    tbs, nb = [], []
    blocks_d, blocks_t = [], []
    next_block = 0
    for t in range(n_terms):
        df = int(rng.integers(*df_range))
        docs = np.sort(rng.choice(n_docs, size=df, replace=False)
                       ).astype(np.int32)
        tfs = rng.integers(1, 5, size=df).astype(np.float32)
        nblk = (df + BLOCK - 1) // BLOCK
        tbs.append(next_block)
        nb.append(nblk)
        next_block += nblk
        pad = nblk * BLOCK - df
        d = np.concatenate([docs, np.zeros(pad, np.int32)])
        f = np.concatenate([tfs, np.zeros(pad, np.float32)])
        blocks_d.append(d.reshape(nblk, BLOCK))
        blocks_t.append(f.reshape(nblk, BLOCK))
    # reserved zero block
    blocks_d.append(np.zeros((1, BLOCK), np.int32))
    blocks_t.append(np.zeros((1, BLOCK), np.float32))
    bd = np.concatenate(blocks_d)
    bt = np.concatenate(blocks_t)
    lens = rng.integers(5, 80, size=n_docs).astype(np.float32)
    return dict(bd=bd, bt=bt, tbs=np.asarray(tbs), nb=np.asarray(nb),
                zero_block=bd.shape[0] - 1, lens=lens,
                avg=float(lens.mean()))


def slotted_sel(seg, term_ids, idf, n_slots, nb_bucket):
    """Each term-instance run starts on a slot boundary."""
    slot_blocks = nb_bucket // n_slots
    sel = np.full(nb_bucket, seg["zero_block"], np.int32)
    ws = np.zeros(nb_bucket, np.float32)
    pos = 0
    for t in term_ids:
        cnt = int(seg["nb"][t])
        start = int(seg["tbs"][t])
        need = -(-cnt // slot_blocks) * slot_blocks
        assert pos + need <= nb_bucket
        sel[pos:pos + cnt] = np.arange(start, start + cnt)
        ws[pos:pos + cnt] = np.float32(idf[t])
        pos += need
    return sel, ws


def flat_sel(seg, term_ids, idf, nb_bucket):
    sel = np.full(nb_bucket, seg["zero_block"], np.int32)
    ws = np.zeros(nb_bucket, np.float64)
    pos = 0
    for t in term_ids:
        cnt = int(seg["nb"][t])
        start = int(seg["tbs"][t])
        sel[pos:pos + cnt] = np.arange(start, start + cnt)
        ws[pos:pos + cnt] = idf[t]
        pos += cnt
    return sel, ws


def run_v1(seg, queries, n_docs, k=50, nb_bucket=64):
    """The exact v1 kernel over the flat layout of ``queries``."""
    import jax
    wd = np.float64 if jax.config.jax_enable_x64 else np.float32
    idf = np.log1p(n_docs / (seg["nb"] * BLOCK))
    sel = np.zeros((len(queries), nb_bucket), np.int32)
    ws = np.zeros((len(queries), nb_bucket), np.float64)
    for qi, terms in enumerate(queries):
        sel[qi], ws[qi] = flat_sel(seg, terms, idf, nb_bucket)
    return np.asarray(fp.bm25_topk_total_batch(
        seg["bd"], seg["bt"], jnp.asarray(sel), jnp.asarray(ws.astype(wd)),
        seg["lens"][seg["bd"]],
        jnp.asarray(np.ones((fp.F_SLOTS, n_docs), bool)),
        jnp.asarray(np.zeros(len(queries), np.int32)), wd(seg["avg"]),
        1.2, 0.75, k))


def run_v2m(seg, queries, n_docs, k=50, n_slots=8, nb_bucket=64):
    """The v2m kernel over the slotted layout of ``queries``."""
    import jax
    wd = np.float64 if jax.config.jax_enable_x64 else np.float32
    idf = np.log1p(n_docs / (seg["nb"] * BLOCK))
    sel = np.zeros((len(queries), nb_bucket), np.int32)
    ws = np.zeros((len(queries), nb_bucket), wd)
    for qi, terms in enumerate(queries):
        sel[qi], ws[qi] = slotted_sel(seg, terms, idf, n_slots,
                                      nb_bucket)
    return np.asarray(fp.bm25_topk_total_merge_batch(
        seg["bd"], seg["bt"], jnp.asarray(sel), jnp.asarray(ws),
        seg["lens"][seg["bd"]],
        jnp.asarray(np.ones((fp.F_SLOTS, n_docs), bool)),
        jnp.asarray(np.zeros(len(queries), np.int32)), wd(seg["avg"]),
        n_slots, 1.2, 0.75, k))


def unpack1(row, k):
    return row[:k], unpack_ids(row[k:2 * k]), int(row[2 * k])


def _norm_hits(vals, ids, k):
    """Canonical (score desc, docid asc) order for comparison — v1
    and v2m leave device tie order arbitrary (the host re-sorts)."""
    fin = np.isfinite(vals)
    v, d = vals[fin], ids[fin]
    order = np.lexsort((d, -v))
    return v[order], d[order]


def _mass_ties_segment():
    """Degenerate corpus: one term matches every doc with tf 1 over a
    uniform doc length, so every doc ties at the same score."""
    n_docs = 8192
    docs = np.arange(n_docs, dtype=np.int32)
    nblk = n_docs // BLOCK
    bd = np.concatenate([docs.reshape(nblk, BLOCK),
                         np.zeros((1, BLOCK), np.int32)])
    bt = np.concatenate([np.ones((nblk, BLOCK), np.float32),
                         np.zeros((1, BLOCK), np.float32)])
    return dict(bd=bd, bt=bt, tbs=np.asarray([0]), nb=np.asarray([nblk]),
                zero_block=nblk, lens=np.full(n_docs, 10.0, np.float32),
                avg=10.0)


def _v2m_case(case):
    if case == "mass_ties":
        return 8192, _mass_ties_segment(), [[0]]
    if case == "dup_terms":
        rng = np.random.default_rng(3)
        return 1000, build_segment(rng, 1000, n_terms=6), \
            [[2, 2, 5], [0, 1, 2, 3, 4, 5]]
    rng = np.random.default_rng(case)
    seg = build_segment(rng, 2000, n_terms=12)
    queries = [list(rng.choice(12, size=int(rng.integers(1, 6)),
                               replace=False))
               for _ in range(4)]
    return 2000, seg, queries


@pytest.mark.parametrize("case", [0, 1, 2, "dup_terms", "mass_ties"])
def test_v2m_matches_v1(case):
    """The merge kernel (v2m) answers as the monolithic-sort kernel (v1):
    ids and exact totals equal, scores to rtol 1e-6 in contract order.
    On the mass-ties corpus both return the lowest docids and the exact
    total."""
    n_docs, seg, queries = _v2m_case(case)
    k = 50
    out1 = run_v1(seg, queries, n_docs, k=k)
    outm = run_v2m(seg, queries, n_docs, k=k)
    for qi in range(len(queries)):
        v1, d1, t1 = unpack1(out1[qi], k)
        vm, dm, tm = unpack1(outm[qi], k)
        assert t1 == tm, (qi, t1, tm)
        nv1, nd1 = _norm_hits(v1, d1, k)
        nvm, ndm = _norm_hits(vm, dm, k)
        np.testing.assert_array_equal(nd1, ndm)
        np.testing.assert_allclose(nv1, nvm, rtol=1e-6)
    if case == "mass_ties":
        _, dm, tm = unpack1(outm[0], k)
        assert tm == n_docs
        np.testing.assert_array_equal(_norm_hits(outm[0][:k], dm, k)[1],
                                      np.arange(k))


def test_v2_bucket_slot_fit_routing():
    """Slot-fit math: Σ ceil(blocks/slot) <= N_SLOTS picks the smallest
    bucket; misfits return None (served by the warmed v1 shape)."""
    from elasticsearch_tpu.search.fastpath import FastPathServer
    srv = FastPathServer.__new__(FastPathServer)
    srv.nb_buckets = (1024, 4096)
    nbs = np.zeros(40, np.int64)
    reg = {"nb": nbs}
    # 4 tiny terms: 4 slots of 64 at bucket 1024
    nbs[:4] = 10
    assert srv._v2_bucket(reg, [0, 1, 2, 3]) == 1024
    # one 300-block term: ceil(300/64)=5 slots -> still bucket 1024
    nbs[4] = 300
    assert srv._v2_bucket(reg, [4]) == 1024
    # 16 terms of 300 blocks: 5 slots each at 1024 (80>16); at 4096
    # slot=256 -> 2 slots each (32>16) -> misfit
    nbs[5:21] = 300
    assert srv._v2_bucket(reg, list(range(5, 21))) is None
    # 16 terms of <=256 blocks fit bucket 4096 exactly (1 slot each)
    nbs[21:37] = 256
    assert srv._v2_bucket(reg, list(range(21, 37))) == 4096
    # 17 instances can never fit
    assert srv._v2_bucket(reg, [0] * 17) is None
    # all-unknown terms -> None (no device work)
    assert srv._v2_bucket(reg, [-1, -1]) is None


def test_v2_slotted_assembly_runs_stay_sorted():
    """Each term-instance run starts at a slot boundary and padding
    lanes key to SENT — every slot must be ascending (the merge
    precondition)."""
    rng = np.random.default_rng(9)
    seg = build_segment(rng, 1500, n_terms=5, df_range=(100, 500))
    idf = np.log1p(1500 / (seg["nb"] * BLOCK))
    n_slots, nb_bucket = 8, 64
    sel, ws = slotted_sel(seg, [0, 3, 4], idf, n_slots, nb_bucket)
    d = seg["bd"][sel]              # [NB, B]
    tf = seg["bt"][sel]
    keys = np.where(tf > 0, d, 0x7FFFFFFF).reshape(n_slots, -1)
    for s in range(n_slots):
        assert np.all(np.diff(keys[s].astype(np.int64)) >= 0), s


PAD_DOCS = 96     # padded docids past the segment's last real doc


def _padded(seg, n_docs):
    """Doc lengths and the live column over a padded doc space: live is
    False only on the padded docids, which no posting with tf > 0 holds
    (a registered segment has no deletions)."""
    lens = np.concatenate([seg["lens"], np.ones(PAD_DOCS, np.float32)])
    return lens, np.arange(n_docs + PAD_DOCS) < n_docs


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_v2m_unmasked_launch_matches_the_plain_stack_bit_for_bit(seed):
    """masks=None, mask_ids=None (a cohort with no filter row) gives
    the packed result of the masked v2m program over a stack of live
    columns, bit for bit. The cohort holds the seed's queries, one with
    a duplicated term, a padded row and a row the host zeroed for an
    unknown filter term."""
    import jax
    n_docs, seg, queries = _v2m_case(seed)
    queries = queries + [[queries[0][0]] * 2 + [queries[1][0]]]
    wd = np.float64 if jax.config.jax_enable_x64 else np.float32
    idf = np.log1p(n_docs / (seg["nb"] * BLOCK))
    q_n, nb_bucket, n_slots = len(queries) + 2, 64, 8
    sel = np.full((q_n, nb_bucket), seg["zero_block"], np.int32)
    ws = np.zeros((q_n, nb_bucket), wd)
    for qi, terms in enumerate(queries):
        sel[qi], ws[qi] = slotted_sel(seg, terms, idf, n_slots,
                                      nb_bucket)
    lens, live = _padded(seg, n_docs)
    args = (seg["bd"], seg["bt"], jnp.asarray(sel), jnp.asarray(ws),
            lens[seg["bd"]])
    tail = (wd(seg["avg"]), n_slots, 1.2, 0.75, 50)
    masked = np.asarray(fp.bm25_topk_total_merge_batch(
        *args, jnp.stack([jnp.asarray(live)] * fp.F_SLOTS),
        jnp.zeros(q_n, jnp.int32), *tail))
    plain = np.asarray(fp.bm25_topk_total_merge_batch(
        *args, None, None, *tail))
    assert np.array_equal(masked.view(np.uint32), plain.view(np.uint32))
    totals = plain[:, 100].astype(np.int64)
    assert (totals[:-2] > 0).all() and (totals[-2:] == 0).all()


def _f64_topk(seg, terms, idf, lens, avg, keep, k):
    """Exact float64 BM25 top-k of one query (score desc, docid asc)
    over the docs ``keep`` admits, and its total."""
    scores = np.zeros(len(lens), np.float64)
    for t in terms:
        s, n = int(seg["tbs"][t]), int(seg["nb"][t])
        d = seg["bd"][s:s + n].reshape(-1)
        tf = seg["bt"][s:s + n].reshape(-1).astype(np.float64)
        hit = tf > 0
        norm = 1.2 * (1 - 0.75 + 0.75 * lens[d[hit]].astype(np.float64)
                      / avg)
        np.add.at(scores, d[hit],
                  float(idf[t]) * tf[hit] / (tf[hit] + norm))
    scores[~keep] = 0.0
    matched = np.nonzero(scores > 0)[0]
    order = matched[np.lexsort((matched, -scores[matched]))][:k]
    return order, scores[order], len(matched)


def _registration(seg, n_docs):
    """A FastPathServer and the registration state its v2m and v1
    launch sites read, over ``seg``; answers land in ``answers``."""
    from types import SimpleNamespace

    from elasticsearch_tpu.search.fastpath import FastPathServer
    srv = FastPathServer(None, SimpleNamespace(lib=None, h=None),
                         nb_buckets=(64,), q_batch=8, max_k=50)
    lens, live = _padded(seg, n_docs)
    idf = np.log1p(n_docs / (seg["nb"] * BLOCK)).astype(np.float32)
    dp = SimpleNamespace(block_docids=jnp.asarray(seg["bd"]),
                         block_tfs=jnp.asarray(seg["bt"]),
                         doc_lens=jnp.asarray(lens),
                         avg_len=np.float32(seg["avg"]),
                         zero_block=seg["zero_block"])
    reg = {"dp": dp, "k1": 1.2, "b": 0.75, "idf": idf,
           "block_lens": jnp.asarray(lens[seg["bd"]]),
           "nb": seg["nb"].astype(np.int64),
           "starts": seg["tbs"].astype(np.int64),
           "post_start": (seg["tbs"] * BLOCK).astype(np.int32),
           "post_len": np.zeros(len(idf), np.int32),
           "plain_masks": jnp.stack([jnp.asarray(live)] * fp.F_SLOTS),
           "filter_live": {}, "rmesh": None}
    answers = {}

    def respond(reg, tok, v, d, k, total, took_ms, *rest):
        answers[tok] = (v, d, total)
    srv._respond_hits = respond
    return srv, reg, answers, lens, live, idf


@pytest.mark.parametrize("lane", ["v2m", "v1"])
def test_plain_cohorts_launch_unmasked_and_filtered_ones_masked(
        lane, monkeypatch):
    """At the v2m and v1 launch sites a cohort with no filter row runs
    the kernel with masks=None and counts in ``unmasked_cohorts``; a
    cohort holding one filtered query among plain ones runs the masked
    program with that query's row. Every answer is the float64
    reference's."""
    import time
    rng = np.random.default_rng(4)
    n_docs = 2000
    seg = build_segment(rng, n_docs, n_terms=12)
    srv, reg, answers, lens, live, idf = _registration(seg, n_docs)
    launched = []
    for name in ("bm25_topk_total_merge_batch", "bm25_topk_total_batch"):
        def spy(*args, _real=getattr(fp, name), _name=name):
            launched.append((_name, args[5], args[6]))
            return _real(*args)
        monkeypatch.setattr(fp, name, spy)
    filt = (7,)
    keep = live & (np.arange(len(live)) % 2 == 0)
    reg["filter_live"][filt] = jnp.asarray(keep)
    queries = [list(rng.choice(12, size=int(rng.integers(1, 5)),
                               replace=False)) for _ in range(4)]
    plain = [(tok, 50, q, ()) for tok, q in enumerate(queries)]
    mixed = [(10 + tok, 50, q, filt if tok == 2 else ())
             for tok, q in enumerate(queries)]
    arrived = {it[0]: time.monotonic_ns() for it in plain + mixed}
    launch = (srv._launch_group_v2 if lane == "v2m"
              else srv._launch_group)
    for cohort in (plain, mixed):
        stack, rows = srv._resolve_mask_rows(reg, {it[3] for it in cohort})
        launch(reg, 64, cohort, arrived, stack, rows)
    kernel = ("bm25_topk_total_merge_batch" if lane == "v2m"
              else "bm25_topk_total_batch")
    (k0, m0, ids0), (k1, m1, ids1) = launched
    assert k0 == k1 == kernel
    assert m0 is None and ids0 is None
    assert m1 is reg["mask_stack"]
    assert np.asarray(ids1).tolist() == [0, 0, 1] + [0] * 5
    assert srv.stats["cohorts"] == 2
    assert srv.stats["unmasked_cohorts"] == 1
    for tok, k, terms, f in plain + mixed:
        want_ids, want_v, want_total = _f64_topk(
            seg, terms, idf, lens, float(seg["avg"]),
            keep if f else live, k)
        v, d, total = answers[tok]
        assert total == want_total, (tok, total, want_total)
        np.testing.assert_array_equal(d, want_ids)
        np.testing.assert_allclose(v, want_v, rtol=2e-6)
