"""The BM25 top-k algorithm's work, whatever kernel implements it.

Each real posting of a query's terms is read once: docid and tf (8 bytes)
and a gather of the doc's length (4 bytes), about 8 operations to score
and accumulate it; the answer writes k (docid, score) pairs of 8 bytes.
Padded lanes, sorts and merges are the implementation's, not the work.
"""

# XLA program names (``jit_<function>``) of the fast path's scoring
# kernels (ops/fastpath.py; the Pallas merge of ops/merge.py runs inside)
PREFIXES = ("jit_bm25_",)

BYTES_PER_POSTING = 12
FLOPS_PER_POSTING = 8
BYTES_PER_HIT = 8


def work(postings: int, queries: int, k: int) -> tuple:
    """(flops, bytes) of ``queries`` top-``k`` queries reading
    ``postings`` postings in all."""
    return (FLOPS_PER_POSTING * postings,
            BYTES_PER_POSTING * postings + BYTES_PER_HIT * k * queries)
