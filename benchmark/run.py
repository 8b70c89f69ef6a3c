"""Run one cell of BENCHMARK.json once on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device`` and, traced, ``breakdown``; ``checks`` comes last. Without a
TPU, or with fewer chips than the cell asks for, it exits 1 and prints
no result: there is no CPU fallback.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes
# nowhere outside its checkout and its own temporary directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness
    w = harness.cell(args.workload)[0]
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < w["chips"]:
        print(f"benchmark: {args.workload} needs {w['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 1
    import elasticsearch_tpu.node  # noqa: F401 — no program, no run
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START)
    for key, c in result["checks"].items():
        print(f"check {key}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int):
            print(e.code, file=sys.stderr)
    except BaseException:               # noqa: BLE001 — reported, exit 1
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the node's native threads stop in node.close(); _exit skips an
    # interpreter teardown that must not turn a result into an abort
    os._exit(code)
