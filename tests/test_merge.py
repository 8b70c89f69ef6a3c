"""The bitonic merge network itself (pallas interpret mode, small
shapes) — the serving path on CPU takes the lax.sort shortcut, so this
is the network's correctness coverage off-TPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticsearch_tpu.ops.merge import merge_sorted_slots

SENT = 0x7FFFFFFF


def make_inputs(Q, P, n_slots, seed=0, n_docs=100_000):
    rng = np.random.default_rng(seed)
    L = P // n_slots
    keys = np.full((Q, n_slots, L), SENT, np.int32)
    vals = np.zeros((Q, n_slots, L), np.float32)
    for q in range(Q):
        for s in range(n_slots):
            fill = int(rng.integers(0, L + 1))
            ks = np.sort(rng.choice(n_docs, size=fill, replace=False))
            keys[q, s, :fill] = ks
            vals[q, s, :fill] = (rng.random(fill) + 0.1).astype(
                np.float32)
    return keys, vals


@pytest.mark.parametrize("n_slots,P,chunk", [
    (2, 1 << 11, 1 << 10),
    (4, 1 << 12, 1 << 10),
    (8, 1 << 13, 1 << 11),
    (16, 1 << 14, 1 << 12),
    (8, 1 << 13, 1 << 13),    # single chunk (no XLA stages)
    (8, 1 << 13, 1 << 9),     # many XLA stages
])
def test_merge_network_matches_sort(n_slots, P, chunk):
    Q = 2
    keys, vals = make_inputs(Q, P, n_slots, seed=n_slots + P)
    L = P // n_slots

    # eager, not jitted: pallas interpret mode INSIDE jit mis-executes
    # on the multi-device CPU test mesh (upstream sharp edge); the
    # compiled TPU path and the serving CPU path (lax.sort shortcut)
    # are unaffected
    mk, mv = merge_sorted_slots(jnp.asarray(keys), jnp.asarray(vals),
                                chunk=chunk, force_pallas=True)
    sk, sv = jax.lax.sort((keys.reshape(Q, P), vals.reshape(Q, P)),
                          dimension=1, num_keys=1)
    mk, mv, sk, sv = map(np.asarray, (mk, mv, sk, sv))
    np.testing.assert_array_equal(mk, sk)
    for q in range(Q):
        a = sorted(zip(sk[q].tolist(), sv[q].tolist()))
        b = sorted(zip(mk[q].tolist(), mv[q].tolist()))
        assert a == b


@pytest.mark.parametrize("n_slots,P,chunk,n_docs", [
    (4, 1 << 12, 1 << 10, 1100),    # few docs: keys repeat across slots
    (8, 1 << 13, 1 << 11, 1200),
    (16, 1 << 14, 1 << 12, 40_000),
    (8, 1 << 13, 1 << 9, 1100),     # many XLA stages
])
def test_merge_permutation_is_payload_independent(n_slots, P, chunk,
                                                  n_docs):
    """Merging (keys, lane index) and (keys, float32 values) applies one
    permutation: the values carried through the network are, bit for
    bit, the values gathered back through the merged lane index — also
    where one doc's key sits in several slots (one doc in several term
    runs), whose order the segmented sum depends on."""
    Q = 2
    keys, vals = make_inputs(Q, P, n_slots, seed=n_slots + P,
                             n_docs=n_docs)
    flat = keys.reshape(Q, P)
    assert any(len(np.unique(r[r != SENT])) < int(np.sum(r != SENT))
               for r in flat), "no key repeats across slots"
    lane = np.broadcast_to(np.arange(P, dtype=np.int32),
                           (Q, P)).reshape(keys.shape)
    lk, perm = merge_sorted_slots(jnp.asarray(keys), jnp.asarray(lane),
                                  chunk=chunk, force_pallas=True)
    vk, mv = merge_sorted_slots(jnp.asarray(keys), jnp.asarray(vals),
                                chunk=chunk, force_pallas=True)
    np.testing.assert_array_equal(np.asarray(lk), np.asarray(vk))
    gathered = np.take_along_axis(vals.reshape(Q, P), np.asarray(perm),
                                  axis=1)
    np.testing.assert_array_equal(gathered.view(np.uint32),
                                  np.asarray(mv).view(np.uint32))


def test_merge_all_sentinel_slots():
    Q, n_slots, L = 1, 4, 512
    keys = np.full((Q, n_slots, L), SENT, np.int32)
    vals = np.zeros((Q, n_slots, L), np.float32)
    mk, mv = merge_sorted_slots(jnp.asarray(keys), jnp.asarray(vals),
                                chunk=1 << 10, force_pallas=True)
    assert np.all(np.asarray(mk) == SENT)
