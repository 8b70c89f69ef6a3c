"""Same-session cohort-width A/B harness (bench.py is the
deliverable). Device speed can drift between sessions, so cohort widths
are compared WITHIN one process.

Runs the REST serving phase for each cohort width against the SAME
corpus in one process and prints a comparison table.

    python bench_ab.py                # default matrix
    BENCH_AB="32,64" python bench_ab.py
"""

import json
import os
import tempfile
import time

import numpy as np

import bench


def main():
    widths = [int(q) for q in os.environ.get("BENCH_AB", "32,64").split(",")]

    rng = np.random.default_rng(12345)
    corpus = bench.build_corpus(rng)
    queries = bench.make_queries(rng, corpus["df"])
    truth = bench.cpu_exact_truth(corpus, queries)

    results = []
    for q in widths:
        os.environ["BENCH_FAST_QBATCH"] = str(q)
        t0 = time.time()
        with tempfile.TemporaryDirectory() as tmpdir:
            (qps, p50, p99, recall, warm_recall, avg_batch, bool_qps,
             extra) = bench.run_rest_path(corpus, queries, truth,
                                          tmpdir)
        results.append({
            "q_batch": q, "match_qps": round(qps, 1),
            "p50_ms": round(p50, 1), "recall": round(recall, 4),
            "bool_qps": round(bool_qps, 1),
            "avg_cohort": round(avg_batch, 1),
            "wall_s": round(time.time() - t0, 1),
        })
        bench.log(f"A/B q_batch {q}: match {qps:.1f} qps "
                  f"(p50 {p50:.0f} ms), bool {bool_qps:.1f} qps, "
                  f"recall {recall:.4f}")
    print(json.dumps({"ab": results}))


if __name__ == "__main__":
    main()
