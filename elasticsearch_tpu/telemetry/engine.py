"""Engine-level device observability: the compile tracker.

Shape discipline is the make-or-break TPU concern (SURVEY.md §7 "hard
parts" #2): every jit entry point compiles once PER SHAPE, and the whole
engine design (DOC_PAD, power-of-two block buckets in ``ops/device.py``,
the NB bucket ladder in ``search/fastpath.py``) exists to bound the
number of distinct shapes. Until now nothing could *see* a violation — a
recompile storm (one kernel, ever-new shape keys) looked exactly like a
slow device.

``tracked_jit`` replaces a bare ``jax.jit`` on the ops/ entry points: it
derives a **shape-bucket key** from the call (array args → shape+dtype,
static args → value) and records the wall time of each first execution
per key — compile + first dispatch — into the process-global ``TRACKER``.
The table is process-global on purpose: the XLA compile cache it mirrors
is process-global too (one jit cache serves every node a test boots in
this process).

Surfaces: ``GET /_kernels`` (per-kernel table: shapes seen, compiles,
cumulative ms, last-compile trigger), the ``engine.compile`` block of
``GET /_nodes/stats``, and ``engine.compile.count`` /
``engine.compile.ms`` metrics on every live ``MetricsRegistry``
registered as a sink (each node's ``Telemetry`` registers its own, so a
recompile storm shows up in per-node metrics even though the jit cache
is shared).

Timing uses the real wall clock (``time.perf_counter``), NOT the
injectable telemetry clock: XLA compiles happen in real time even under
the deterministic harness, and compile counts — the replay-relevant
signal — are deterministic for a deterministic workload anyway.

Hot-path cost per tracked call: one tuple build over the args + one
lock-guarded dict probe (~µs), against launches that cost ms.
"""

from __future__ import annotations

import functools
import inspect
import json
import logging
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

# per-request kernel attribution seam (stdlib-only module, no cycle):
# tracked_jit stamps kernel names into an active `profile: true`
# recorder via profile.note_kernel
from elasticsearch_tpu.search import profile as _profile
from elasticsearch_tpu.telemetry import flightrecorder as _flight
from elasticsearch_tpu.telemetry.tracing import host_span

_prof_tls = _profile._tls
_flight_tls = _flight._tls

logger = logging.getLogger("elasticsearch_tpu.telemetry.engine")

__all__ = ["CompileTracker", "PersistentKernelCache", "TRACKER",
           "tracked_jit"]


# -- shape keys -------------------------------------------------------------

def _dyn_desc(value) -> tuple:
    """Describe a dynamic (traced) argument the way jit's cache keys it:
    arrays by shape+dtype, containers element-wise, scalars collapse to
    one marker (python scalars are weakly typed — their VALUE never
    triggers a recompile)."""
    shape = getattr(value, "shape", None)
    if shape is not None:
        return (tuple(int(s) for s in shape),
                str(getattr(value, "dtype", "?")))
    if isinstance(value, (tuple, list)):
        return ("seq", tuple(_dyn_desc(v) for v in value))
    if value is None or isinstance(value, (bool, int, float, complex)):
        # the TYPE still keys (a python int traces weak-i32, a float
        # weak-f32 — flipping between them recompiles), only the VALUE
        # doesn't
        return ("scalar", type(value).__name__)
    return (type(value).__name__,)


def _static_desc(value) -> Any:
    """Statics key by value (jit hashes them); unhashable statics fall
    back to identity — the same object is the same compile."""
    try:
        hash(value)
        return value
    except TypeError:
        return f"<{type(value).__name__}#{id(value):x}>"


def _component(pname: str, value, is_static: bool) -> tuple:
    if is_static:
        return (pname, "static", _static_desc(value))
    return (pname,) + _dyn_desc(value)


def _fmt_component(comp: tuple) -> str:
    pname = comp[0]
    if len(comp) >= 2 and comp[1] == "static":
        return f"{pname}={comp[2]!r}"
    if len(comp) == 3 and isinstance(comp[1], tuple):
        dims = "x".join(str(d) for d in comp[1])
        return f"{pname}[{dims}]{comp[2]}"
    if len(comp) == 3 and comp[1] == "scalar":
        return f"{pname}:{comp[2]}"
    return f"{pname}:{comp[1]}"


def format_key(key: tuple) -> str:
    """Human-readable shape-bucket key for the ``_kernels`` table —
    arrays and statics only (scalar VALUES can't trigger recompiles;
    a scalar TYPE flip still shows up in the last-compile trigger)."""
    return " ".join(_fmt_component(c) for c in key
                    if not (len(c) >= 2 and c[1] == "scalar"))


def _diff_trigger(prev: Optional[tuple], key: tuple) -> str:
    """What changed vs the previous compile of this kernel — the
    'last-compile trigger' column. Detects the storm signature (the
    same arg flapping through ever-new shapes) at a glance."""
    if prev is None:
        return "cold"
    changed = []
    for a, b in zip(prev, key):
        if a != b:
            changed.append(f"{_fmt_component(a)} -> {_fmt_component(b)}")
    if len(prev) != len(key):
        changed.append(f"arity {len(prev)} -> {len(key)}")
    return "; ".join(changed) if changed else "new shape"


# -- persistent key store ---------------------------------------------------

_ADDR_RE = None


def serialize_key(key: tuple) -> str:
    """Stable textual form of a shape-bucket key — the persistent-cache
    lookup key. Shape/dtype components repr deterministically, but a
    STATIC component can be a function (``<function f at 0x7f..>``) or
    an unhashable fallback (``<list#7f..>``) whose repr embeds a
    per-process address — strip hex addresses so the same kernel keys
    identically across sessions (qualname collisions are acceptable:
    the store is telemetry-grade)."""
    global _ADDR_RE
    if _ADDR_RE is None:
        import re
        _ADDR_RE = re.compile(r"(0x|#)[0-9a-f]+")
    return _ADDR_RE.sub(r"\1", repr(key))


class PersistentKernelCache:
    """On-disk record of shape-bucket keys compiled on this machine,
    mirroring JAX's persistent compilation cache at the TRACKER's key
    granularity. A first-execution whose key is already in the store is
    a warm load (the serialized executable deserializes instead of
    recompiling) and is classified as a ``cache_hit`` rather than a
    compile; the stored cold-compile ms quantifies the seconds saved.

    The store is telemetry-grade: it can drift from jax's own cache
    (e.g. the cache dir was cleared) — a stale entry then reports a
    slow "hit". The jit layer stays correct either way.
    """

    FILENAME = "kernel_keys.json"

    def __init__(self, path: str):
        self.path = path
        self._file = os.path.join(path, self.FILENAME)
        self._lock = threading.Lock()
        self._keys: Dict[str, Dict[str, float]] = {}
        self.hits = 0
        self.misses = 0
        self.saved_ms = 0.0
        try:
            os.makedirs(path, exist_ok=True)
            if os.path.exists(self._file):
                with open(self._file) as fh:
                    loaded = json.load(fh)
                if isinstance(loaded, dict):
                    self._keys = {k: dict(v) for k, v in loaded.items()
                                  if isinstance(v, dict)}
        except Exception:   # noqa: BLE001 — a broken store is a cold one
            logger.exception("persistent kernel cache unreadable: %s",
                             self._file)
            self._keys = {}

    def lookup(self, kernel: str, key: tuple) -> Optional[float]:
        """Previous cold-compile ms when ``key`` is known, else None."""
        with self._lock:
            return self._keys.get(kernel, {}).get(serialize_key(key))

    def record(self, kernel: str, key: tuple, ms: float) -> None:
        with self._lock:
            self._keys.setdefault(kernel, {})[serialize_key(key)] = \
                round(float(ms), 3)
            snapshot = {k: dict(v) for k, v in self._keys.items()}
        try:
            tmp = self._file + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(snapshot, fh)
            os.replace(tmp, self._file)
        except Exception:   # noqa: BLE001 — persistence is best-effort
            logger.exception("persistent kernel cache write failed")

    def on_hit(self, prev_ms: float, actual_ms: float) -> None:
        with self._lock:
            self.hits += 1
            self.saved_ms += max(0.0, prev_ms - actual_ms)

    def on_miss(self) -> None:
        with self._lock:
            self.misses += 1

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "path": self.path,
                "entries": sum(len(v) for v in self._keys.values()),
                "hits": self.hits,
                "misses": self.misses,
                "saved_ms": round(self.saved_ms, 3),
            }


# -- the tracker ------------------------------------------------------------

class _Kernel:
    __slots__ = ("name", "calls", "compiles", "cache_hits", "cum_ms",
                 "shapes", "last_key", "last_ms", "last_trigger")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.compiles = 0
        self.cache_hits = 0     # first executions served warm from the
        self.cum_ms = 0.0       # persistent compile cache
        # key -> first-execution ms (None while the timing is in flight)
        self.shapes: Dict[tuple, Optional[float]] = {}
        self.last_key: Optional[tuple] = None
        self.last_ms: Optional[float] = None
        self.last_trigger: Optional[str] = None


class CompileTracker:
    """Thread-safe per-kernel compile table + metric-sink fan-out."""

    MAX_SHAPES_LISTED = 16   # per-kernel cap in to_dict (table stays small)

    def __init__(self):
        self._lock = threading.Lock()
        self._kernels: Dict[str, _Kernel] = {}
        # live metric registries (each node's Telemetry adds its own);
        # weak so closed nodes never pin their registries process-wide
        self._sinks: "weakref.WeakSet" = weakref.WeakSet()
        # optional machine-level key store (PersistentKernelCache):
        # first executions whose key it already holds classify as warm
        # cache hits instead of compiles
        self.persistent: Optional[PersistentKernelCache] = None

    def add_sink(self, metrics) -> None:
        self._sinks.add(metrics)

    def attach_persistent(self, cache: PersistentKernelCache) -> None:
        """First caller wins (mirrors jax's own one-cache-dir rule)."""
        with self._lock:
            if self.persistent is None:
                self.persistent = cache

    def persistent_stats(self) -> Dict[str, Any]:
        """The ``persistent_cache`` block of ``GET /_kernels``."""
        p = self.persistent
        out: Dict[str, Any] = {"enabled": p is not None}
        if p is not None:
            out.update(p.stats())
        try:
            import jax
            out["jax_cache_dir"] = jax.config.jax_compilation_cache_dir
        except Exception:   # noqa: BLE001 — stats never break a caller
            pass
        return out

    # -- record path (called by tracked_jit wrappers) ----------------------

    def on_call(self, kernel: str, key: tuple) -> bool:
        """Count a call; True when ``key`` is new for ``kernel`` (the
        caller then times the execution and reports on_compile)."""
        with self._lock:
            k = self._kernels.get(kernel)
            if k is None:
                k = self._kernels[kernel] = _Kernel(kernel)
            k.calls += 1
            if key in k.shapes:
                return False
            k.shapes[key] = None    # reserve: concurrent racers record once
            return True

    def on_error(self, kernel: str, key: tuple) -> None:
        """First execution for a reserved key raised: un-reserve it so a
        later successful retry is timed and counted as the compile it
        is (a still-None reservation would otherwise hide it forever)."""
        with self._lock:
            k = self._kernels.get(kernel)
            if k is not None and k.shapes.get(key, 0) is None:
                del k.shapes[key]

    def on_compile(self, kernel: str, key: tuple, ms: float) -> str:
        """Record a first-execution-per-key; returns the classification
        (``"compile"`` cold, ``"cache_hit"`` warm persistent-cache
        load) so the caller can attribute it per request."""
        pers = self.persistent
        prev_ms = pers.lookup(kernel, key) if pers is not None else None
        with self._lock:
            k = self._kernels[kernel]
            trigger = _diff_trigger(k.last_key, key)
            k.shapes[key] = ms
            if prev_ms is not None:
                # the machine compiled this shape bucket before: jax's
                # persistent cache deserializes instead of recompiling —
                # a warm load, not a compile
                k.cache_hits += 1
            else:
                k.compiles += 1
                k.cum_ms += ms
            k.last_key, k.last_ms, k.last_trigger = key, ms, trigger
            sinks = [s for s in self._sinks]
        if pers is not None:
            if prev_ms is not None:
                pers.on_hit(prev_ms, ms)
            else:
                pers.on_miss()
                pers.record(kernel, key, ms)
        if prev_ms is not None:
            return "cache_hit"
        for m in sinks:
            try:
                m.inc("engine.compile.count")
                m.inc("engine.compile.ms", ms)
            except Exception:   # noqa: BLE001 — a dying registry never
                pass            # breaks a kernel launch
        return "compile"

    # -- read path ---------------------------------------------------------

    def totals(self) -> Dict[str, Any]:
        """The ``engine.compile`` rollup for ``_nodes/stats``."""
        with self._lock:
            kernels = list(self._kernels.values())
            return {
                "count": sum(k.compiles for k in kernels),
                "ms": round(sum(k.cum_ms for k in kernels), 3),
                "calls": sum(k.calls for k in kernels),
                "cache_hits": sum(k.cache_hits for k in kernels),
                "kernels": len(kernels),
            }

    def total_compiles(self) -> int:
        with self._lock:
            return sum(k.compiles for k in self._kernels.values())

    def compiles_of(self, kernel: str) -> int:
        with self._lock:
            k = self._kernels.get(kernel)
            return k.compiles if k is not None else 0

    def to_dict(self) -> Dict[str, Any]:
        """The ``GET /_kernels`` table: per kernel, shapes seen /
        compiles / cumulative ms / last-compile trigger. A kernel whose
        ``compiles`` keeps pace with ``calls`` across ever-new shape
        keys IS a recompile storm — the table makes it legible."""
        with self._lock:
            out: Dict[str, Any] = {}
            for name in sorted(self._kernels):
                k = self._kernels[name]
                shapes = [
                    {"key": format_key(key),
                     "ms": round(ms, 3) if ms is not None else None}
                    for key, ms in list(k.shapes.items())
                    [-self.MAX_SHAPES_LISTED:]]
                out[name] = {
                    "calls": k.calls,
                    "compiles": k.compiles,
                    "cache_hits": k.cache_hits,
                    "shapes_seen": len(k.shapes),
                    "cum_ms": round(k.cum_ms, 3),
                    "last_compile": {
                        "key": (format_key(k.last_key)
                                if k.last_key is not None else None),
                        "ms": (round(k.last_ms, 3)
                               if k.last_ms is not None else None),
                        "trigger": k.last_trigger,
                    },
                    "shapes": shapes,
                }
            return out

    def reset(self) -> None:
        """Test hook. The jit caches survive a reset, so re-seen shapes
        re-record as (instant) compiles — fine for delta assertions."""
        with self._lock:
            self._kernels.clear()


# THE tracker — process-global, like the XLA jit cache it mirrors.
TRACKER = CompileTracker()


# -- the decorator ----------------------------------------------------------

_trace_state_clean: Optional[Callable[[], bool]] = None


def _resolve_trace_clean() -> Callable[[], bool]:
    """``True`` when not under an outer jit trace — a tracked kernel
    called at trace time is part of the OUTER kernel's compile, not a
    device launch of its own."""
    global _trace_state_clean
    if _trace_state_clean is None:
        try:
            import jax
            _trace_state_clean = jax.core.trace_state_clean
        except Exception:   # noqa: BLE001 — very old/new jax: track all
            _trace_state_clean = lambda: True   # noqa: E731
    return _trace_state_clean


def tracked_jit(name: Optional[str] = None, *,
                static_argnames: Tuple[str, ...] = (), **jit_kwargs):
    """``jax.jit`` + first-execution-per-shape recording into TRACKER.

    Drop-in for ``@partial(jax.jit, static_argnames=...)`` on ops/
    entry points::

        @tracked_jit("bm25_topk_total_batch",
                     static_argnames=("k1", "b", "k"))
        def bm25_topk_total_batch(...): ...

    The wrapper derives the shape-bucket key from the call signature
    (array args by shape+dtype, statics by value), consults the global
    TRACKER, and times the first execution per key. Calls made while an
    outer jit is tracing pass straight through untracked.
    """
    def deco(fn):
        import jax
        jitted = jax.jit(fn, static_argnames=static_argnames,
                         **jit_kwargs)
        kname = name or fn.__name__.lstrip("_")
        # the host span around every tracked call: dispatch, plus the
        # compile on a shape's first execution
        span_name = "launch:" + kname
        try:
            params: List[str] = list(inspect.signature(fn).parameters)
        except (TypeError, ValueError):
            params = []
        statics = frozenset(
            (static_argnames,) if isinstance(static_argnames, str)
            else static_argnames)
        trace_clean = _resolve_trace_clean()

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not trace_clean():
                return jitted(*args, **kwargs)
            parts = [_component(p, a, p in statics)
                     for p, a in zip(params, args)]
            if len(args) > len(params):     # *args overflow: positional
                parts.extend(_component(f"arg{i}", a, False)
                             for i, a in enumerate(args[len(params):]))
            for p in sorted(kwargs):
                parts.append(_component(p, kwargs[p], p in statics))
            key = tuple(parts)
            # always-on flight recording: the ambient per-node ring
            # (telemetry/flightrecorder.py) gets one launch event per
            # trace-clean call — kernel id, bucketed shape, dispatch
            # nanos on ITS clock, plus the batcher's cohort annotation
            # when one is active (one TLS getattr when no recorder)
            fr = getattr(_flight_tls, "rec", None)
            if not TRACKER.on_call(kname, key):
                tfr = fr.clock() if fr is not None else 0.0
                with host_span(span_name):
                    out = jitted(*args, **kwargs)
                if fr is not None:
                    info = getattr(_flight_tls, "launch_info", None) or {}
                    fr.record_launch(
                        kname, format_key(key),
                        dispatch_ns=int((fr.clock() - tfr) * 1e9),
                        **info)
                # per-request attribution: a `profile: true` recorder
                # active on this thread gets the kernel name for every
                # tracked launch (one TLS getattr when profiling is off)
                if getattr(_prof_tls, "rec", None) is not None:
                    _profile.note_kernel(kname, "cached", 0.0)
                return out
            t0 = time.perf_counter()
            try:
                with host_span(span_name):
                    out = jitted(*args, **kwargs)
            except BaseException:
                TRACKER.on_error(kname, key)
                raise
            ms = (time.perf_counter() - t0) * 1000.0
            kind = TRACKER.on_compile(kname, key, ms)
            if fr is not None:
                # first execution per shape: record the launch without
                # dispatch latency — compile time is the TRACKER's
                # story, and it would poison the regime EMA
                info = getattr(_flight_tls, "launch_info", None) or {}
                fr.record_launch(kname, format_key(key), dispatch_ns=0,
                                 **info)
            if getattr(_prof_tls, "rec", None) is not None:
                _profile.note_kernel(kname, kind, ms)
            return out

        wrapper.kernel_name = kname
        wrapper.__wrapped_jit__ = jitted
        return wrapper

    if callable(name):      # bare @tracked_jit
        fn, name = name, None
        return deco(fn)
    return deco
