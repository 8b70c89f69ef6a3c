"""ctypes binding of the benchmark's load generator (loadgen.c).

The library builds on first use into ``benchmark/loadgen/build/`` under a
name keyed on a hash of its source, so a changed source builds anew and a
checkout builds it once.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
from dataclasses import dataclass
from typing import List, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "loadgen.c")
_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    with open(_SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    out_dir = os.path.join(_HERE, "build")
    so = os.path.join(out_dir, f"libloadgen-{digest}.so")
    if not os.path.exists(so):
        os.makedirs(out_dir, exist_ok=True)
        tmp = f"{so}.{os.getpid()}.tmp"
        subprocess.run(["cc", "-O2", "-shared", "-fPIC", _SRC, "-o", tmp],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, so)
    lib = ctypes.CDLL(so)
    p64 = ctypes.POINTER(ctypes.c_int64)
    lib.lg_run.restype = ctypes.c_longlong
    lib.lg_run.argtypes = [
        ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p, p64,
        ctypes.c_longlong, p64, ctypes.c_int, ctypes.c_double,
        ctypes.c_double, ctypes.POINTER(ctypes.c_uint8), ctypes.c_char_p,
        ctypes.c_int64, p64, p64, p64, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_uint8), p64]
    _lib = lib
    return lib


@dataclass
class Result:
    """Times are seconds after the loop's start ``t0_ns`` (monotonic
    clock); -1 where there is none."""
    t0_ns: int
    sent: int               # requests that went out
    send_s: np.ndarray
    done_s: np.ndarray
    status: np.ndarray      # HTTP status; -1 connection failed, 0 never came
    shaped: np.ndarray      # 200 with a search-shaped body
    kept: dict              # request index -> response body (bytes)


def _ptr(a, t):
    return a.ctypes.data_as(ctypes.POINTER(t))


def run(port: int, path: str, bodies: List[bytes],
        due_s: Optional[np.ndarray], conns: int, seconds: float,
        drain_s: float, keep: np.ndarray, keep_bytes: int) -> Result:
    """Send ``bodies`` (request i carries body i): an open loop when
    ``due_s`` gives each request's due time, a closed loop over ``conns``
    connections for ``seconds`` otherwise. Blocks without the GIL."""
    lib = _load()
    n = len(bodies)
    offs = np.zeros(n + 1, np.int64)
    np.cumsum([len(b) for b in bodies], out=offs[1:])
    blob = b"".join(bodies)
    due = (None if due_s is None
           else np.ascontiguousarray(np.round(due_s * 1e9), np.int64))
    keep = np.ascontiguousarray(keep, np.uint8)
    keep_buf = ctypes.create_string_buffer(max(1, keep_bytes))
    keep_off = np.full(2 * n, -1, np.int64)
    send = np.empty(n, np.int64)
    done = np.empty(n, np.int64)
    status = np.empty(n, np.int32)
    shaped = np.empty(n, np.uint8)
    t0 = np.zeros(1, np.int64)
    sent = lib.lg_run(
        port, path.encode(), blob, _ptr(offs, ctypes.c_int64), n,
        None if due is None else _ptr(due, ctypes.c_int64), conns,
        float(seconds), float(drain_s), _ptr(keep, ctypes.c_uint8),
        keep_buf, keep_bytes, _ptr(keep_off, ctypes.c_int64),
        _ptr(send, ctypes.c_int64), _ptr(done, ctypes.c_int64),
        _ptr(status, ctypes.c_int32), _ptr(shaped, ctypes.c_uint8),
        _ptr(t0, ctypes.c_int64))
    if sent < 0:
        raise ConnectionError(f"load generator could not connect to "
                              f"127.0.0.1:{port}")
    raw = keep_buf.raw
    kept = {}
    for i in np.nonzero(keep_off[1::2] >= 0)[0]:
        o, ln = keep_off[2 * i], keep_off[2 * i + 1]
        kept[int(i)] = raw[o:o + ln]
    to_s = lambda a: np.where(a >= 0, a / 1e9, -1.0)   # noqa: E731
    return Result(int(t0[0]), int(sent), to_s(send), to_s(done), status,
                  shaped.astype(bool), kept)
