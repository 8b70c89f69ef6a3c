"""Same-session serving-kernel A/B harness (not run by the driver —
bench.py is the deliverable). Device speed can drift between sessions,
so kernel configs are compared WITHIN one process.

Runs the REST serving phase for each (kernel, cohort-width) config
against the SAME corpus in one process and prints a comparison table.

    python bench_ab.py                # default matrix
    BENCH_AB="v1@32,v2m@64" python bench_ab.py
"""

import json
import os
import tempfile
import time

import numpy as np

import bench


def main():
    configs = []
    for spec in os.environ.get("BENCH_AB", "v1@32,v2m@32,v2m@64").split(","):
        kernel, _, q = spec.strip().partition("@")
        configs.append((kernel, int(q or 32)))

    rng = np.random.default_rng(12345)
    corpus = bench.build_corpus(rng)
    queries = bench.make_queries(rng, corpus["df"])
    truth = bench.cpu_exact_truth(corpus, queries)

    results = []
    for kernel, q in configs:
        os.environ["BENCH_FAST_QBATCH"] = str(q)
        t0 = time.time()
        with tempfile.TemporaryDirectory() as tmpdir:
            (qps, p50, p99, recall, warm_recall, avg_batch, bool_qps,
             extra) = bench.run_rest_path(corpus, queries, truth,
                                          tmpdir, kernel)
        results.append({
            "kernel": kernel, "q_batch": q, "match_qps": round(qps, 1),
            "p50_ms": round(p50, 1), "recall": round(recall, 4),
            "bool_qps": round(bool_qps, 1),
            "avg_cohort": round(avg_batch, 1),
            "wall_s": round(time.time() - t0, 1),
        })
        bench.log(f"A/B {kernel}@{q}: match {qps:.1f} qps "
                  f"(p50 {p50:.0f} ms), bool {bool_qps:.1f} qps, "
                  f"recall {recall:.4f}")
    print(json.dumps({"ab": results}))


if __name__ == "__main__":
    main()
