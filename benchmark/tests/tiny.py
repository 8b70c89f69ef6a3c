"""Tiny sizes of each cell for the CPU rehearsals."""

_TEXT = {"title": {"type": "text", "vocab": 100000, "zipf": 1.07,
                   "avg_len": 40, "burst": 0.35, "blocks": 60000},
         "tag": {"type": "keyword", "values": 500, "skew": 2}}

TINY = {
    "msmarco-bm25-top1000-open": {"docs": 20000, "fields": _TEXT,
                                  "rate": 15, "check_sample": 12},
    "msmarco-knn768-top10-open": {"docs": 20000, "rate": 15,
                                  "check_sample": 12},
    "msmarco-bm25-top1000-saturate": {"docs": 20000, "fields": _TEXT,
                                      "connections": 8, "max_rate": 30,
                                      "check_within": 20,
                                      "check_sample": 12},
}

# the open BM25 cell's configuration as 4 primary shards of 5,000 docs on
# the CPU's four host devices, served by the mesh program
SHARDED = ("msmarco-bm25-top1000-open",
           dict(TINY["msmarco-bm25-top1000-open"], chips=4, fast_path=False,
                settings={"number_of_shards": 4, "number_of_replicas": 0},
                fields=dict(_TEXT, title=dict(_TEXT["title"],
                                              blocks=32000))))
