"""Read the control: the reference in bfloat16, put in the program's place.

    python3 benchmark/control.py --workload <name> --seeds 1,2,3 \
        [--seconds <run_seconds>]

For each seed it makes the cell's data and the very sample of queries a
run of that seed checks, answers them with the body kind's ``control``
(the reference computed in bfloat16, the next precision below the
configuration's float32), judges those answers against the float64
reference as a run judges the program's, and prints the worst
``score_gap`` and ``rank_gap`` beside the limits. The upper readings of
the limits come from here (PERF.md). The benchmark's runs never call it.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchmark import harness, oracle  # noqa: E402


def readings(name: str, seed: int, seconds: float, overrides=None) -> dict:
    _, config, traffic, _ = harness.cell(name, overrides)
    body = harness.module("bodies", traffic["body"])
    params = traffic["params"]
    k = params["size"]
    data = harness.make_data(config, seed)
    got = harness.inputs(traffic, body, data, params, seed, seconds)
    qs = [got.qs[i] for i in got.sample]
    ctrl, prog = [], []
    for ref, low in zip(body.reference(data, qs, params),
                        body.control(data, qs, params)):
        top = oracle.topk(low, k)
        ctrl.append(oracle.judge([str(d) for d in top], low[top], ref, k))
        # the reference in its own place reads 0: the comparison is sound
        mine = oracle.topk(ref, k)
        prog.append(oracle.judge([str(d) for d in mine], ref[mine], ref, k))
    limits = config["limits"][traffic["body"]]
    return {"seed": seed, "answers": len(ctrl),
            "control": {key: max(c[key] for c in ctrl)
                        for key in ("score_gap", "rank_gap")},
            "reference": {key: max(c[key] for c in prog)
                          for key in ("score_gap", "rank_gap")},
            "limits": limits}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    seconds = args.seconds or harness.load_json(
        harness.ROOT, "BENCHMARK.json")["run_seconds"]
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(args.workload, seed, seconds)),
              flush=True)


if __name__ == "__main__":
    main()
