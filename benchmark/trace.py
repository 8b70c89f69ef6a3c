"""From a profiler trace to device metrics.

``capture`` records the measured window with ``jax.profiler``; ``load``
reads the ``.xplane.pb`` into plain lists (planes → lines → events of
[name, start_ns, duration_ns]) and ``reduce`` turns them into the device's
busy time (the union of the intervals in which an operation ran), the
device time of each XLA program, and the longest idle gaps, each named
by what the host threads were doing in it. ``load`` and ``reduce`` are
pure so that ``tests/test_trace.py`` can check them on a recorded trace.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil
import time
from typing import List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
CLOCK_MARK = "benchmark.clock"
_SUFFIX = re.compile(r"\(\d+\)$")


@contextlib.contextmanager
def capture(log_dir: str):
    """Trace the body with the Python tracer off (it would slow every
    server thread) and the host's own runtime events on. Yields a dict
    that receives ``mono_ns``: the monotonic times of the clock marks
    before and after the body."""
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    out = {"mono_ns": []}

    def mark():
        with jax.profiler.TraceAnnotation(CLOCK_MARK):
            out["mono_ns"].append(time.monotonic_ns())
    try:
        mark()
        yield out
        mark()
    finally:
        jax.profiler.stop_trace()


def load(log_dir: str) -> List[dict]:
    """Planes of the newest trace under ``log_dir``: device planes with
    their op and program lines, host planes with every line."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    planes = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            # host threads may share a name ("python"): merge them
            lines.setdefault(line.name, []).extend(
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events)
        planes.append({"name": plane.name, "lines": lines})
    return planes


def clock_offset(planes: List[dict], mono_ns: List[int]) -> int:
    """Trace time minus monotonic time, from the last clock mark."""
    starts = sorted(start for p in planes
                    if not p["name"].startswith(DEVICE_PREFIX)
                    for events in p["lines"].values()
                    for name, start, _ in events if name == CLOCK_MARK)
    if not starts:
        raise ValueError("the trace holds no clock mark")
    return starts[-1] - mono_ns[-1]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(events, lo: int, hi: int):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def program_name(name: str) -> str:
    return _SUFFIX.sub("", name).strip()


def reduce(planes: List[dict], lo: int, hi: int,
           top: int = 10) -> Optional[dict]:
    """Device metrics over the window [lo, hi) in trace nanoseconds.
    None when the trace holds no device plane. Busy time is averaged
    over every device plane and program time summed over them; the idle
    gaps are those of the first device plane."""
    devices = [p for p in planes if p["name"].startswith(DEVICE_PREFIX)]
    if not devices:
        return None
    busy_ns, programs = [], {}
    first_union = None
    for p in devices:
        lines = p["lines"]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        u = _union([(a, b) for _, a, b in _clip(ops, lo, hi)])
        busy_ns.append(sum(b - a for a, b in u))
        if first_union is None:
            first_union = u
        for name, a, b in _clip(lines.get(MODULES_LINE, []), lo, hi):
            key = program_name(name)
            programs[key] = programs.get(key, 0) + (b - a)
    gaps, prev = [], lo
    for a, b in first_union + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    host = [e for p in planes if not p["name"].startswith(DEVICE_PREFIX)
            for events in p["lines"].values() for e in events]
    window_s = (hi - lo) / 1e9
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": window_s,
        "programs_s": {k: v / 1e9 for k, v in programs.items()},
        "device_ops": sorted(([k, v / 1e9] for k, v in programs.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": [[f"{host_activity(host, a, b)} at +"
                       f"{(a - lo) / 1e6:.3f} ms", (b - a) / 1e9]
                      for a, b in gaps],
    }


def host_activity(host_events, a: int, b: int) -> str:
    """What the host threads did in [a, b): the event that overlaps the
    gap most among those no longer than four gaps (a longer one that
    wraps the gap is a thread waiting, not the work that held the device
    back), else the shortest event that overlaps it."""
    gap = b - a
    near, wraps = {}, None
    for name, s, d in host_events:
        ov = min(s + d, b) - max(s, a)
        if ov <= 0 or name == CLOCK_MARK:
            continue
        if d <= 4 * gap:
            near[name] = max(near.get(name, 0), ov)
        elif wraps is None or d < wraps[1]:
            wraps = (name, d)
    if near:
        return max(near.items(), key=lambda kv: kv[1])[0]
    return wraps[0] if wraps else "host idle"


def program_seconds(red: dict, prefixes) -> float:
    """Device seconds of the programs whose names start with a prefix."""
    return sum(v for k, v in red["programs_s"].items()
               if k.startswith(tuple(prefixes)))
