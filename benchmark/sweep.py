"""Find a cell's knee: one set-up, then one window per offered load.

    python3 benchmark/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 200,400,800      # open loop, queries/s
        --conns 64,128,256       # closed loop, keep-alive connections
        [--dump-trace <file>]    # also trace one window and dump it

Prints one JSON line per step. A tool for whoever sets a cell's rate;
the driver's runs never call it. The knee is the highest offered rate at
which the completions keep up with the offers and the generator's
lateness stays flat.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
# nowhere outside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402


def step(port, index, body, params, data, traffic, seconds, seed, name,
         node):
    due = harness.schedule(traffic, seconds, harness.rng(seed, name))
    n = len(due) if due is not None else int(traffic["max_rate"] * seconds)
    qs = body.queries(data, params, harness.rng(seed, name + ":q"), n)
    before = harness.counters(node, port)
    res = harness.drive(port, index, body, params, qs, traffic, seconds,
                        np.zeros(n, np.uint8), due)
    after = harness.counters(node, port)
    idx = harness.in_window(res, due, seconds)
    ok = res.shaped[idx]
    start = due[idx] if due is not None else res.send_s[idx]
    lat = (res.done_s[idx] - start)[ok]
    out = {"step": name, "sent": res.sent, "ok": int(ok.sum()),
           "failed": int((~ok).sum()),
           "completed_in_window_per_s": float(np.sum(
               res.shaped & (res.done_s >= 0) & (res.done_s <= seconds))
               / seconds),
           "last_done_after_window_s": float(res.done_s.max() - seconds),
           "p50_ms": float(np.percentile(lat, 50) * 1e3) if len(lat) else None,
           "p99_ms": float(np.percentile(lat, 99) * 1e3) if len(lat) else None}
    if due is not None:
        late = res.send_s[idx] - due[idx]
        out["late_p99_ms"] = float(np.percentile(late, 99) * 1e3)
        out["late_max_ms"] = float(late.max() * 1e3)
    d = {k: after[k] - before[k] for k in after}
    out["counters"] = d
    if d.get("cohorts"):
        out["cohort_fill"] = d["fast_queries"] / d["cohorts"]
    if d.get("knn_launches"):
        out["knn_batch_fill"] = d["knn_batched_queries"] / d["knn_launches"]
    return out, res


def dump(planes, lo, hi, path):
    """A summary of the trace and a small slice of it (20 ms) for the
    reduction's test."""
    summary = []
    for p in planes:
        lines = {}
        for name, ev in p["lines"].items():
            names = {}
            for e in ev:
                names[e[0]] = names.get(e[0], 0) + e[2]
            lines[name] = {"events": len(ev), "top": sorted(
                names.items(), key=lambda kv: -kv[1])[:8]}
        summary.append({"plane": p["name"], "lines": lines})
    mid = (lo + hi) // 2
    cut = [{"name": p["name"], "lines": {
        k: [e for e in v if e[1] < mid + 20_000_000 and e[1] + e[2] > mid]
        for k, v in p["lines"].items()}} for p in planes]
    cut = [{"name": p["name"], "lines": {k: v for k, v in p["lines"].items()
                                         if v}} for p in cut]
    with open(path, "w") as fh:
        json.dump({"summary": summary, "window": [mid, mid + 20_000_000],
                   "slice": cut}, fh)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--conns", default="")
    ap.add_argument("--dump-trace", default="")
    args = ap.parse_args()
    harness.use_compile_cache()
    _, config, traffic, _ = harness.cell(args.workload)
    body = harness.module("bodies", traffic["body"])
    params = traffic["params"]
    data = harness.make_data(config, args.seed)
    index = config["index"]
    with harness.serving(config, data, body, params) as (node, port):
        print(json.dumps({"setup_s": time.monotonic() - T_START}),
              flush=True)
        steps = [dict(traffic, loop="open", rate=float(r))
                 for r in args.rates.split(",") if r]
        steps += [dict(traffic, loop="closed", connections=int(c),
                       max_rate=traffic.get("max_rate", 4000))
                  for c in args.conns.split(",") if c]
        for tr in steps:
            name = (f"rate={tr['rate']}" if tr["loop"] == "open"
                    else f"conns={tr['connections']}")
            out, _ = step(port, index, body, params, data, tr,
                          args.seconds, args.seed, name, node)
            print(json.dumps(out), flush=True)
        if args.dump_trace:
            tr = steps[0]
            with trace_mod.capture(harness.TRACE_DIR) as mark:
                out, res = step(port, index, body, params, data, tr,
                                args.seconds, args.seed, "traced", node)
            print(json.dumps(out), flush=True)
            planes = trace_mod.load(harness.TRACE_DIR)
            lo = res.t0_ns + trace_mod.clock_offset(planes, mark["mono_ns"])
            hi = lo + int(args.seconds * 1e9)
            red = trace_mod.reduce(planes, lo, hi)
            print(json.dumps({"trace": {k: v for k, v in (red or {}).items()
                                        if k != "programs_s"},
                              "programs_s": (red or {}).get("programs_s")}),
                  flush=True)
            os.makedirs(os.path.dirname(args.dump_trace) or ".",
                        exist_ok=True)
            dump(planes, lo, hi, args.dump_trace)


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)
