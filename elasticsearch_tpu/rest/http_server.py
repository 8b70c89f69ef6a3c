"""HTTP transport: sockets → RestController.

The Netty4HttpServerTransport analogue (ref: modules/transport-netty4/.../
Netty4HttpServerTransport.java), minimal: a threading HTTP server that
parses query params + JSON/NDJSON bodies and delegates to the controller.
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qsl, urlsplit


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    controller = None

    def log_message(self, fmt, *args):  # quiet by default
        pass

    def _handle(self, method: str):
        url = urlsplit(self.path)
        params = dict(parse_qsl(url.query))
        length = int(self.headers.get("Content-Length") or 0)
        raw = self.rfile.read(length) if length else b""
        content_type = (self.headers.get("Content-Type") or "").lower()
        body = None
        if raw:
            if "x-ndjson" in content_type or url.path.rstrip("/").rsplit(
                    "/", 1)[-1] in ("_bulk", "_msearch"):
                body = raw.decode("utf-8")
            elif "cbor" in content_type:
                # binary XContent (ref: CborXContent — the JDBC/ODBC
                # clients' binary_format communication)
                from elasticsearch_tpu.common import cbor
                try:
                    body = cbor.loads(raw)
                except (ValueError, TypeError) as e:
                    self._send(400, {"error": {
                        "type": "parsing_exception",
                        "reason": f"Failed to parse request body: {e}"},
                        "status": 400})
                    return
            else:
                try:
                    body = json.loads(raw)
                except json.JSONDecodeError as e:
                    self._send(400, {"error": {
                        "type": "parsing_exception",
                        "reason": f"Failed to parse request body: {e}"},
                        "status": 400})
                    return
        status, payload = self.controller.dispatch(
            method, url.path, params, body, headers=dict(self.headers))
        accept = (self.headers.get("Accept") or "").lower()
        self._send(status, payload, head_only=(method == "HEAD"),
                   cbor_ok="cbor" in accept)

    def _send(self, status: int, payload, head_only: bool = False,
              cbor_ok: bool = False):
        extra_headers = {}
        if isinstance(payload, dict) and "_headers" in payload:
            payload = dict(payload)
            extra_headers = payload.pop("_headers")
        if isinstance(payload, dict) and "_cat" in payload and len(payload) == 1:
            data = (payload["_cat"] + "\n").encode()
            ctype = "text/plain; charset=UTF-8"
        elif cbor_ok:
            from elasticsearch_tpu.common import cbor
            data = cbor.dumps(payload)
            ctype = "application/cbor"
        else:
            data = json.dumps(payload).encode()
            ctype = "application/json; charset=UTF-8"
        self.send_response(status)
        for hk, hv in extra_headers.items():
            self.send_header(hk, hv)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.send_header("X-elastic-product", "Elasticsearch")
        self.end_headers()
        if not head_only:
            self.wfile.write(data)

    def do_GET(self):
        self._handle("GET")

    def do_POST(self):
        self._handle("POST")

    def do_PUT(self):
        self._handle("PUT")

    def do_DELETE(self):
        self._handle("DELETE")

    def do_HEAD(self):
        self._handle("HEAD")


class HttpServer:
    """``ssl_config`` enables HTTPS (ref: xpack.security.http.ssl.* —
    SecurityNetty4HttpServerTransport wrapping the pipeline in an
    SslHandler): {"certificate": pem_path, "key": pem_path,
    "client_auth": "none"|"optional"|"required",
    "certificate_authorities": pem_path}."""

    def __init__(self, controller, host: str = "127.0.0.1", port: int = 9200,
                 ssl_config=None, ip_filter=None):
        handler = type("BoundHandler", (_Handler,), {"controller": controller})
        self.ssl_enabled = bool(ssl_config)
        # accept-time IP filtering (ref: x-pack IPFilter — allow wins,
        # an allow-list alone implies deny-everything-else); same
        # semantics as the native front (estpu_http.cpp ip_allowed)
        self._ip_allow, self._ip_deny = self._parse_ip_filter(ip_filter)
        if ssl_config:
            from elasticsearch_tpu.common.tls import (handshake,
                                                      server_context)
            ctx = server_context(ssl_config)

            class _TlsServer(ThreadingHTTPServer):
                # per-CONNECTION handshake in the handler thread with a
                # bounded timeout: a stalled client must never block the
                # accept loop (wrapping the LISTENING socket would run
                # the handshake inline in serve_forever)
                def process_request_thread(self, request, client_address):
                    try:
                        request = handshake(request, ctx)
                    except OSError:
                        self.shutdown_request(request)
                        return
                    super().process_request_thread(request, client_address)

            self._server = _TlsServer((host, port), handler)
        else:
            self._server = ThreadingHTTPServer((host, port), handler)
        if self._ip_allow or self._ip_deny:
            allow, deny = self._ip_allow, self._ip_deny
            outer = self._server

            def verify_request(request, client_address,
                               _orig=outer.verify_request):
                import ipaddress
                try:
                    addr = ipaddress.ip_address(client_address[0])
                except ValueError:
                    return False
                if any(addr in net for net in allow):
                    return True
                if any(addr in net for net in deny):
                    return False
                return not allow
            outer.verify_request = verify_request
        self.port = self._server.server_address[1]
        self._thread = None

    @staticmethod
    def _parse_ip_filter(ip_filter):
        import ipaddress
        allow, deny = [], []
        if ip_filter:
            for spec_csv, out in ((ip_filter[0], allow),
                                  (ip_filter[1], deny)):
                for spec in (spec_csv or "").split(","):
                    spec = spec.strip()
                    if spec:
                        out.append(ipaddress.ip_network(
                            spec if "/" in spec else spec + "/32",
                            strict=False))
        return allow, deny

    def start(self):
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="http-server", daemon=True)
        self._thread.start()

    def stop(self):
        self._server.shutdown()
        self._server.server_close()
