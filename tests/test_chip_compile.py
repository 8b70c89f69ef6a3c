"""Main-path kernels compile for a TPU v5e that is described, not
attached (on-chip-measurement guide §2): what the chip's compiler would
refuse fails here at no chip time. Shapes are the serving widths of the
2M-doc corpus (``chip_smoke.py``): ~600k postings blocks, 2,000,896
padded docs, cohorts of 32, top-1000, 768-d bf16 vectors.

Nothing runs, so these say nothing about results or speed.
"""

import os

import jax
import jax.numpy as jnp
import pytest

TB, B, ND, Q, K = 600_001, 128, 2_000_896, 32, 1000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


_V2M = {}


def _v2m(sharding, nb, masked=True):
    """The v2m program for a cohort of Q at ``nb`` blocks, with the mask
    stack or without it (a cohort with no filter row), compiled once per
    module: each takes ~40 s here."""
    from elasticsearch_tpu.ops import fastpath
    if (nb, masked) not in _V2M:
        S = lambda shape, dt: _shape(sharding, shape, dt)  # noqa: E731
        mask = ((S((fastpath.F_SLOTS, ND), jnp.bool_), S((Q,), jnp.int32))
                if masked else (None, None))
        _V2M[nb, masked] = \
            fastpath.bm25_topk_total_merge_batch.__wrapped_jit__.lower(
                S((TB, B), jnp.int32), S((TB, B), jnp.float32),
                S((Q, nb), jnp.int32), S((Q, nb), jnp.float32),
                S((TB, B), jnp.float32), *mask, S((), jnp.float32),
                n_slots=16, k1=1.2, b=0.75, k=K).compile()
    return _V2M[nb, masked]


def _bytes_accessed(compiled):
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return cost["bytes accessed"]


def test_v2m_merge_kernel_compiles_to_a_tpu_custom_call(one_chip,
                                                        monkeypatch):
    from elasticsearch_tpu.ops import merge
    # _interpret() reads jax.devices(), which is the CPU here
    monkeypatch.setattr(merge, "_interpret", lambda: False)
    compiled = _v2m(one_chip, 1024)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_v2m_carries_contributions_through_the_merge(one_chip,
                                                     monkeypatch):
    """At NB 2048 the compiler counts ~59.7e9 bytes accessed when the
    merge carries lane indices and the contributions are gathered back
    through them, ~19.9e9 when the float32 contributions ride the merge.
    The bound sits between the two; the temp bound keeps the merge's
    inputs out of batch-minor (4x padded) layouts (~0.57e9 bytes there,
    ~0.17e9 without)."""
    from elasticsearch_tpu.ops import fastpath, merge
    monkeypatch.setattr(merge, "_interpret", lambda: False)
    assert fastpath.merge_payload() == "contrib"
    compiled = _v2m(one_chip, 2048)
    assert _bytes_accessed(compiled) < 40e9
    assert compiled.memory_analysis().temp_size_in_bytes < 3e8


def test_v2m_without_a_filter_row_reads_no_mask(one_chip, monkeypatch):
    """A cohort with no filter row launches v2m with masks=None: the
    program takes no pred[F_SLOTS, ND] stack and gathers no pred at all.
    At NB 2048 the compiler counts ~19.9e9 bytes accessed with the mask
    (the stack's rows copied out per query, then gathered per posting)
    and ~5.6e9 without it: the mask accounts for ~14.3e9. The bound
    asks for 10e9 of that drop."""
    from elasticsearch_tpu.ops import merge
    monkeypatch.setattr(merge, "_interpret", lambda: False)
    masked, plain = _v2m(one_chip, 2048), _v2m(one_chip, 2048, False)
    assert "pred[32,2000896]" in masked.as_text()
    text = plain.as_text()
    assert "pred[32,2000896]" not in text
    assert not [ln for ln in text.splitlines()
                if " gather(" in ln and "= pred[" in ln]
    assert _bytes_accessed(plain) < _bytes_accessed(masked) - 10e9


def test_v2m_reads_posting_lengths_as_block_rows(one_chip, monkeypatch):
    """v2m reads each posting's document length as a block row of the
    [TB, B] table, as it reads the tfs: the program holds no per-doc
    f32[ND] length vector to gather from by docid. At NB 2048 without
    the mask the compiler counts ~5.6e9 bytes accessed, against ~40.4e9
    when the lengths were gathered by docid; the bound sits between."""
    from elasticsearch_tpu.ops import merge
    monkeypatch.setattr(merge, "_interpret", lambda: False)
    plain = _v2m(one_chip, 2048, False)
    assert f"f32[{ND}]" not in plain.as_text()
    assert _bytes_accessed(plain) < 20e9


def test_knn_nominate_compiles_over_a_bf16_slab(one_chip):
    from elasticsearch_tpu.ops import vector
    S = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    compiled = vector.knn_nominate_batch.__wrapped_jit__.lower(
        S((Q, 768), jnp.float32), S((ND, 768), jnp.bfloat16),
        S((ND,), jnp.float32), S((ND,), jnp.bool_), S((ND,), jnp.bool_),
        similarity="cosine", cut=128).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_plan_segsum_compiles_under_vmap(one_chip):
    """The plan kernel's segmented sum, batched as the PlanBatcher runs
    it: a segmented associative_scan here took ~80 s per call site on
    the TPU compiler; the compensated cumsum takes seconds."""
    from elasticsearch_tpu.ops import plan
    S = lambda shape, dt: _shape(one_chip, shape, dt)  # noqa: E731
    p = 4096 * B
    jax.jit(jax.vmap(plan._segsum)).lower(
        S((8, p), jnp.float32), S((8, p), jnp.bool_)).compile()
