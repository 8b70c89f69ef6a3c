"""An index of four primary shards, rehearsed on the CPU's four host
devices: the BM25 cell's configuration as 4 x 5,000 docs, served by the
one-program scatter-gather over the mesh.

The run is correct with every request answered by one mesh launch and
none falling back to the per-shard loop. On the same queries the judge
fails the bfloat16 control, and fails the served answers against a
reference that scores with index-wide statistics: it holds the
per-shard semantics of ``query_then_fetch``. With the exchange between
the shards left out, half of the answers left out, or an answer altered,
the run is not correct.
"""

import itertools
import time

import numpy as np
import pytest

from benchmark import harness, oracle
from benchmark.corpus import bm25_exact
from benchmark.tests.tiny import SHARDED

SEED = 2305843009213693951
CELL, OVERRIDES = SHARDED


def _run():
    return harness.run_cell(CELL, SEED, 2.0, False, time.monotonic(),
                            dict(OVERRIDES))


@pytest.fixture(scope="module")
def sound():
    """(result, the run's context, the judge's arguments)."""
    seen = {}
    judge, context = harness.judge, harness.context

    def judging(*a):
        seen["judge"] = a
        return judge(*a)

    def keeping(*a):
        seen["ctx"] = context(*a)
        return seen["ctx"]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "judge", judging)
        mp.setattr(harness, "context", keeping)
        r = _run()
    return r, seen["ctx"], seen["judge"]


def test_every_request_is_one_mesh_launch(sound):
    r, ctx, _ = sound
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    answered = ctx.attempted - ctx.failed
    assert ctx.delta("mesh.dispatch.shard") == answered
    assert ctx.delta("mesh_searches") == answered
    assert ctx.delta("mesh.fallback") == 0


def _index_wide(data, field):
    """The shards' postings as one index of all their docs (global
    docids), so that ``bm25_exact`` scores with index-wide df, doc count
    and average length."""
    cs = [d[field] for d in data]
    n, vocab = len(cs[0]["lens"]), len(cs[0]["df"])
    term = np.concatenate([np.repeat(np.arange(vocab), c["df"]) for c in cs])
    doc = np.concatenate([c["doc_ids"].astype(np.int64) + s * n
                          for s, c in enumerate(cs)])
    order = np.lexsort((doc, term))
    df = sum(c["df"] for c in cs)
    gs = np.zeros(vocab + 1, np.int64)
    np.cumsum(df, out=gs[1:])
    return {"lens": np.concatenate([c["lens"] for c in cs]),
            "group_start": gs, "doc_ids": doc[order],
            "tf": np.concatenate([c["tf"] for c in cs])[order]}


def _worst(got, refs, k):
    gaps = [oracle.judge(ids, scores, ref, k)
            for (ids, scores), ref in zip(got, refs)]
    return {key: max(g[key] for g in gaps)
            for key in ("score_gap", "rank_gap")}


def test_the_judge_holds_per_shard_statistics(sound):
    _, _, (body, data, params, qs, sample, res, config, traffic, *_) = sound
    limits = config["limits"][traffic["body"]]
    k = params["size"]
    have, got, lost = harness.answers(res, sample)
    assert have and not lost
    asked = [qs[i] for i in have]
    refs = list(body.reference(data, asked, params))

    def failed(gap):
        return any(gap[key] > limits[key] for key in limits)
    assert not failed(_worst(got, refs, k))
    # the bfloat16 control, in the program's place
    ctrl = []
    for low in body.control(data, asked, params):
        top = oracle.topk(low, k)
        ctrl.append(([str(d) for d in top], low[top]))
    assert failed(_worst(ctrl, refs, k))
    # the served answers against index-wide statistics
    whole = _index_wide(data, params["field"])
    assert failed(_worst(got, (bm25_exact(whole, q) for q in asked), k))


def _exchange_left_out(fn):
    """The coordinator sees shard 0's hits alone."""
    def wrapped(self, *a):
        docs, total = fn(self, *a)
        return [d for d in docs if d[0] == 0], total
    return wrapped


def _half_left_out(fn):
    """Every other query gets an empty answer."""
    turn = itertools.count()

    def wrapped(self, *a):
        docs, total = fn(self, *a)
        return (docs if next(turn) % 2 else []), total
    return wrapped


def _altered(fn):
    """The best hit of every answer names the next doc of its shard."""
    def wrapped(self, *a):
        docs, total = fn(self, *a)
        if docs:
            shard, seg, docid, score = docs[0]
            docs = [(shard, seg, docid + 1, score)] + docs[1:]
        return docs, total
    return wrapped


@pytest.mark.parametrize("fault", [_exchange_left_out, _half_left_out,
                                   _altered],
                         ids=["exchange_left_out", "half_left_out",
                              "answer_altered"])
def test_broken_mesh_path_is_not_correct(monkeypatch, fault):
    from elasticsearch_tpu.parallel.mesh_executor import MeshSearchBackend
    monkeypatch.setattr(MeshSearchBackend, "_unpack_docs",
                        fault(MeshSearchBackend._unpack_docs))
    r = _run()
    assert not r["correct"], r["checks"]
