"""Tiny sizes of each cell for the CPU rehearsals."""

_TEXT = {"title": {"type": "text", "vocab": 100000, "zipf": 1.07,
                   "avg_len": 40, "burst": 0.35, "blocks": 60000},
         "tag": {"type": "keyword", "values": 500, "skew": 2}}

TINY = {
    "msmarco-bm25-top1000-open": {"docs": 20000, "fields": _TEXT,
                                  "rate": 15, "check_sample": 12},
    "msmarco-knn768-top10-open": {"docs": 20000, "rate": 15,
                                  "check_sample": 12},
}

# the open BM25 cell driven as a closed loop: the harness's other loop
CLOSED = ("msmarco-bm25-top1000-open",
          dict(TINY["msmarco-bm25-top1000-open"], loop="closed",
               connections=8, max_rate=30, check_within=20))
