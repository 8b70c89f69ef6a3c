"""A whole run on the CPU, past the harness's look for a chip: sound, it
is correct; with the served path broken underneath, it is not.

Each fault is planted where the answers are produced: the fast path's
hand-off of a query's hits to the C++ front (match), and the kNN
batcher's final answer (knn). A search cell has no training state and,
on one chip, no exchange between chips, so the faults it can have are
half of a batch left out and an answer altered.
"""

import itertools
import time

import numpy as np
import pytest

from benchmark import harness
from benchmark.tests.tiny import TINY

SEED = 3141592653589


def _half_left_out(fn):
    """Every other query of the stream gets an empty answer."""
    turn = itertools.count()

    def wrapped(self, *a):
        return fn(self, *a) if next(turn) % 2 else _empty(fn, self, *a)
    return wrapped


def _empty(fn, self, *a):
    if fn.__name__ == "_respond_hits":        # (reg, tok, v, d, k, ...)
        reg, tok, v, d, *rest = a
        return fn(self, reg, tok, v[:0], d[:0], *rest)
    scores, ids = fn(self, *a)                # _finish -> (scores, ids)
    return scores[:0], ids[:0]


def _altered(fn):
    """The best hit of every answer names the next doc instead."""
    def wrapped(self, *a):
        if fn.__name__ == "_respond_hits":
            reg, tok, v, d, *rest = a
            d = np.ascontiguousarray(d.copy())
            if len(d):
                d[0] = (d[0] + 1) % reg["segment"].n_docs
            return fn(self, reg, tok, v, d, *rest)
        scores, ids = fn(self, *a)
        ids = ids.copy()
        if len(ids):
            ids[0] = ids[0] + 1
        return scores, ids
    return wrapped


FAULTS = {"half_left_out": _half_left_out, "answer_altered": _altered}


def _plant(monkeypatch, cell, fault):
    from elasticsearch_tpu.search.batching import KnnBatcher
    from elasticsearch_tpu.search.fastpath import FastPathServer
    cls, name = ((KnnBatcher, "_finish") if "knn" in cell
                 else (FastPathServer, "_respond_hits"))
    monkeypatch.setattr(cls, name, FAULTS[fault](getattr(cls, name)))


def _run(cell):
    return harness.run_cell(cell, SEED, 2.0, False, time.monotonic(),
                            dict(TINY[cell]))


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    # every end-to-end metric of the cell reads a number
    assert set(r["metrics"]) == {
        m["name"] for m in harness.cell(cell)[3]["end_to_end"]}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", sorted(TINY))
def test_broken_path_is_not_correct(monkeypatch, cell, fault):
    _plant(monkeypatch, cell, fault)
    r = _run(cell)
    assert not r["correct"], r["checks"]
