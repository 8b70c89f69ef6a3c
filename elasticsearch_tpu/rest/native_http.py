"""Native HTTP front: ctypes bindings for native/src/estpu_http.cpp.

The serving-front architecture (ref: Netty4HttpServerTransport — an epoll
event loop off the application threads):

- a C++ epoll thread owns accept/read/parse/write (zero GIL),
- hot `_search` bodies are parsed + tokenized in C++ and drained by the
  fast-path engine (search/fastpath.py) as per-cohort term-id batches,
- every other route lands on the fallback queue, served by the Python
  worker threads below through the SAME RestController.dispatch as the
  pure-Python server — the whole ~310-route table keeps working,
- fast-path responses are serialized in C++ from (docid, score) arrays.

Degrades gracefully: if g++ or the .so is unavailable, Node.start falls
back to the stdlib server (rest/http_server.py).
"""

from __future__ import annotations

import ctypes
import json
import threading
import time
from typing import Optional
from urllib.parse import parse_qsl, urlsplit

from elasticsearch_tpu.telemetry.tracing import host_span

_lib = None
_lib_lock = threading.Lock()
_build_failed = False

MAX_TERMS = 16    # keep in sync with estpu_http.cpp
MAX_FILTERS = 8


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _build_failed:
            return _lib
        from elasticsearch_tpu.native import build_keyed
        so = build_keyed("estpu_http",
                         ["estpu_http.cpp", "estpu_tokenize.h"],
                         ["-O2", "-pthread"])
        if so is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(so)
        c = ctypes
        H = c.c_int64
        lib.es_http_start.restype = c.c_int
        lib.es_http_start.argtypes = [c.c_int, c.POINTER(H)]
        lib.es_http_stop.restype = None
        lib.es_http_stop.argtypes = [H]
        lib.es_fast_register.restype = c.c_int
        lib.es_fast_register.argtypes = [
            H, c.c_int32, c.c_char_p, c.c_char_p, c.c_char_p,
            c.POINTER(c.c_int64), c.c_int32, c.c_char_p,
            c.POINTER(c.c_int64), c.c_int32, c.c_int32, c.c_int32]
        lib.es_fast_unregister.restype = None
        lib.es_fast_unregister.argtypes = [H]
        lib.es_fast_poll.restype = c.c_int
        lib.es_fast_poll.argtypes = [
            H, c.POINTER(c.c_uint64), c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.POINTER(c.c_int32),
            c.POINTER(c.c_int32), c.POINTER(c.c_int64), c.c_int, c.c_int]
        lib.es_fast_pending.restype = c.c_int
        lib.es_fast_pending.argtypes = [H]
        lib.es_fast_respond.restype = c.c_int
        lib.es_fast_respond.argtypes = [
            H, c.c_uint64, c.c_char_p, c.c_void_p, c.c_void_p, c.c_int,
            c.c_longlong, c.c_char_p, c.c_int]
        lib.es_fast_bounce.restype = c.c_int
        lib.es_fast_bounce.argtypes = [H, c.c_uint64]
        lib.es_fallback_next.restype = c.c_int
        lib.es_fallback_next.argtypes = [
            H, c.POINTER(c.c_uint64), c.c_char_p,
            c.POINTER(c.c_char_p), c.POINTER(c.c_int64),
            c.POINTER(c.c_char_p), c.POINTER(c.c_int64),
            c.POINTER(c.c_char_p), c.POINTER(c.c_int64),
            c.POINTER(c.c_int64), c.c_int]
        lib.es_respond.restype = c.c_int
        lib.es_respond.argtypes = [H, c.c_uint64, c.c_int, c.c_char_p,
                                   c.c_char_p, c.c_int64, c.c_int,
                                   c.c_char_p]
        lib.es_http_set_ipfilter.restype = c.c_int
        lib.es_http_set_ipfilter.argtypes = [H, c.c_char_p, c.c_char_p]
        lib.es_http_stats.restype = None
        lib.es_http_stats.argtypes = [H, c.POINTER(c.c_longlong)]
        lib.es_loadgen.restype = c.c_longlong
        lib.es_loadgen.argtypes = [
            c.c_int, c.c_char_p, c.c_char_p, c.POINTER(c.c_int64),
            c.c_int, c.c_int, c.c_longlong, c.c_int,
            c.POINTER(c.c_double), c.POINTER(c.c_double)]
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


class NativeHttpFront:
    """Owns one C++ server instance (an opaque handle — any number of
    nodes per process run their own front) + the Python fallback
    workers."""

    def __init__(self, controller, n_fallback_threads: int = 2,
                 metrics=None):
        self.controller = controller
        # the node's MetricsRegistry (`http.fallback.queue_wait`)
        self.metrics = metrics
        self.lib = get_lib()
        self.h = None           # C++ Server* handle
        self.port = None
        self._threads = []
        self._running = False
        self.n_fallback = n_fallback_threads
        self.fastpath = None   # attached by Node.start

    @classmethod
    def try_acquire(cls, controller, metrics=None):
        return (cls(controller, metrics=metrics) if get_lib() is not None
                else None)

    def start(self, port: int) -> int:
        h = ctypes.c_int64()
        bound = self.lib.es_http_start(port, ctypes.byref(h))
        if bound < 0:
            # estpu: allow[ESTPU-ERR01] bind failure keeps socket OSError semantics; callers fall back to the Python front
            raise OSError(f"native http front failed to bind port {port}")
        self.h = h
        self.port = bound
        self._running = True
        for i in range(self.n_fallback):
            t = threading.Thread(target=self._fallback_loop,
                                 name=f"http-fallback-{i}", daemon=True)
            t.start()
            self._threads.append(t)
        return bound

    def stop(self):
        self._running = False
        clean = True
        if self.fastpath is not None:
            clean = self.fastpath.stop()
            self.fastpath = None
        for t in self._threads:
            # workers check _running every poll timeout; the C++ server
            # must outlive any thread that may still touch the handle
            t.join(timeout=5.0)
            clean = clean and not t.is_alive()
        self._threads = []
        if self.h is not None:
            if clean:
                self.lib.es_http_stop(self.h)
            # a straggler thread (e.g. mid-XLA-compile) still holds the
            # handle: leak the C++ server rather than free under it
            self.h = None
            self.port = None

    def set_ipfilter(self, allow_csv: str, deny_csv: str) -> int:
        return self.lib.es_http_set_ipfilter(self.h, allow_csv.encode(),
                                             deny_csv.encode())

    def stats(self) -> dict:
        buf = (ctypes.c_longlong * 8)()
        self.lib.es_http_stats(self.h, buf)
        return {"requests": buf[0], "fast": buf[1], "fallback": buf[2],
                "open_connections": buf[3], "ip_rejected": buf[4]}

    # ------------------------------------------------------------ fallback
    def _fallback_loop(self):
        c = ctypes
        token = c.c_uint64()
        method = c.create_string_buffer(16)
        path_p = c.c_char_p()
        path_len = c.c_int64()
        hdr_p = c.c_char_p()
        hdr_len = c.c_int64()
        body_p = c.c_char_p()
        body_len = c.c_int64()
        stamp = c.c_int64()
        waits = (self.metrics.histogram("http.fallback.queue_wait")
                 if self.metrics is not None else None)
        while self._running:
            got = self.lib.es_fallback_next(
                self.h, c.byref(token), method, c.byref(path_p),
                c.byref(path_len), c.byref(hdr_p), c.byref(hdr_len),
                c.byref(body_p), c.byref(body_len), c.byref(stamp), 200)
            if not got:
                continue
            if waits is not None:
                # from the front queueing the request to this worker
                waits.observe((time.monotonic_ns() - stamp.value) / 1e6)
            try:
                self._serve_one(token.value,
                                method.value.decode("latin-1"),
                                c.string_at(path_p, path_len.value),
                                c.string_at(hdr_p, hdr_len.value),
                                c.string_at(body_p, body_len.value))
            except Exception as e:  # noqa: BLE001 — never kill the worker
                try:
                    err = json.dumps({"error": {
                        "type": "internal_server_error",
                        "reason": str(e)}, "status": 500}).encode()
                    self.lib.es_respond(self.h, token.value, 500,
                                        b"application/json", err,
                                        len(err), 0, b"")
                except Exception:
                    pass

    def _serve_one(self, token: int, method: str, raw_path: bytes,
                   raw_headers: bytes, raw_body: bytes):
        with host_span("http.serve"):
            with host_span("http.parse"):
                url, params, headers, lower, body, err = _parse(
                    raw_path, raw_headers, raw_body)
            if err is not None:
                self._send(token, 400, {"error": {
                    "type": "parsing_exception",
                    "reason": f"Failed to parse request body: {err}"},
                    "status": 400}, method)
                return
            if "trace.id" in lower:
                # an externally-propagated trace context (another node's
                # coordinator, a client-side tracer) joins this request's
                # spans to the caller's trace — the REST-boundary root
                # span parents to it via the ambient context, so
                # cross-process profile ↔ trace navigation works through
                # the native front too (fast-path requests never reach
                # Python and stay untraced by design)
                from elasticsearch_tpu.telemetry import context as _telectx
                cm = _telectx.incoming({"trace.id": lower["trace.id"],
                                        "span.id": lower.get("span.id")})
            else:
                from contextlib import nullcontext
                cm = nullcontext()
            with cm:
                status, payload = self.controller.dispatch(
                    method, url.path, params, body, headers=headers)
            self._send(token, status, payload, method,
                       cbor_ok="cbor" in lower.get("accept", "").lower())

    def _send(self, token: int, status: int, payload, method: str,
              cbor_ok: bool = False):
        # mirrors rest/http_server.py _Handler._send
        with host_span("http.encode"):
            extra = b""
            if isinstance(payload, dict) and "_headers" in payload:
                payload = dict(payload)
                extra = "".join(f"{k}: {v}\r\n" for k, v in
                                payload.pop("_headers").items()).encode()
            if isinstance(payload, dict) and "_cat" in payload \
                    and len(payload) == 1:
                data = (payload["_cat"] + "\n").encode()
                ctype = b"text/plain; charset=UTF-8"
            elif cbor_ok:
                from elasticsearch_tpu.common import cbor
                data = cbor.dumps(payload)
                ctype = b"application/cbor"
            else:
                data = json.dumps(payload).encode()
                ctype = b"application/json; charset=UTF-8"
            self.lib.es_respond(self.h, token, status, ctype, data,
                                len(data), 1 if method == "HEAD" else 0,
                                extra)


def _parse(raw_path: bytes, raw_headers: bytes, raw_body: bytes):
    """(url, query params, headers, lower-cased headers, decoded body,
    None), or the body's decode error in the last place."""
    url = urlsplit(raw_path.decode("utf-8", "replace"))
    params = dict(parse_qsl(url.query))
    headers = {}
    for line in raw_headers.decode("latin-1").split("\r\n"):
        name, sep, val = line.partition(":")
        if sep:
            headers[name.strip()] = val.strip()
    lower = {k.lower(): v for k, v in headers.items()}
    content_type = lower.get("content-type", "").lower()
    body = None
    if raw_body:
        if ("x-ndjson" in content_type
                or url.path.rstrip("/").rsplit("/", 1)[-1]
                in ("_bulk", "_msearch")):
            body = raw_body.decode("utf-8")
        elif "cbor" in content_type:
            # binary XContent, same negotiation as the stdlib front
            # (rest/http_server.py — JDBC/ODBC binary_format)
            from elasticsearch_tpu.common import cbor
            try:
                body = cbor.loads(raw_body)
            except (ValueError, TypeError) as e:
                return url, params, headers, lower, None, e
        else:
            try:
                body = json.loads(raw_body)
            except json.JSONDecodeError as e:
                return url, params, headers, lower, None, e
    return url, params, headers, lower, body, None
