"""Puller init-container client (server/pull.py): the classes of retry.

The init container must retry while the store is coming up (connection
refused, 5xx) but exit non-zero immediately on a definitive 4xx so bad
model references surface in pod status instead of spinning for 90 min.
"""

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from ollama_operator_tpu.server.pull import pull, resolve_host


def _serve(handler_cls):
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), handler_cls)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd


class TestResolveHost:
    def test_forms(self):
        assert resolve_host("store.ns") == "http://store.ns:11434"
        assert resolve_host("store:80") == "http://store:80"
        assert resolve_host("http://x:1234/") == "http://x:1234"
        assert resolve_host("") == "http://127.0.0.1:11434"


class TestPull:
    def test_404_fails_fast_without_retry(self):
        calls = []

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                calls.append(1)
                body = b'{"error":"model not found"}'
                self.send_response(404)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = _serve(H)
        try:
            rc = pull("nope", f"127.0.0.1:{httpd.server_address[1]}",
                      retries=50, retry_delay=0.01)
            assert rc == 1
            assert len(calls) == 1  # no retries on 4xx
        finally:
            httpd.shutdown()

    def test_5xx_retries_then_succeeds(self):
        calls = []

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                calls.append(1)
                if len(calls) < 3:
                    self.send_response(503)
                    self.send_header("Content-Length", "0")
                    self.end_headers()
                    return
                body = json.dumps({"status": "success"}).encode() + b"\n"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = _serve(H)
        try:
            rc = pull("m", f"127.0.0.1:{httpd.server_address[1]}",
                      retries=10, retry_delay=0.01)
            assert rc == 0 and len(calls) == 3
        finally:
            httpd.shutdown()

    def test_connection_refused_retries_until_cap(self):
        rc = pull("m", "127.0.0.1:1", retries=3, retry_delay=0.01)
        assert rc == 1

    def test_error_event_in_stream_fails(self):
        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = b'{"error": "blob digest mismatch"}\n'
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        httpd = _serve(H)
        try:
            assert pull("m", f"127.0.0.1:{httpd.server_address[1]}",
                        retries=1) == 1
        finally:
            httpd.shutdown()
