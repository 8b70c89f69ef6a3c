"""Exact kNN's work: every launch streams the whole vector slab once and
multiplies it with the launch's real queries.

bytes = launches x N x D x 2 (bfloat16 slab), flops = 2 x Q x N x D over
the Q queries carried (padding rows are the implementation's waste).
"""

PREFIXES = ("jit_knn_nominate_batch",)

SLAB_BYTES_PER_VALUE = 2


def work(launches: int, queries: int, n_docs: int, dims: int) -> tuple:
    return (2.0 * queries * n_docs * dims,
            float(launches) * n_docs * dims * SLAB_BYTES_PER_VALUE)
