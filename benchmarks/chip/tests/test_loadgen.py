"""The load generator times each request from its due time, so a stall
of the server shows in every request due behind it."""

from __future__ import annotations

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

from benchmarks.chip.run import LoadGen

BODY = json.dumps({"head": {"vars": ["x"]}, "results": {"bindings": [
    {"x": {"type": "uri", "value": "ub:A"}}]}}).encode()


class _Stalling(BaseHTTPRequestHandler):
    stall_s = 1.0
    calls = 0

    def do_POST(self):  # noqa: N802
        self.rfile.read(int(self.headers["Content-Length"]))
        type(self).calls += 1
        if type(self).calls == 2:  # the first window request
            time.sleep(self.stall_s)
        self.send_response(200)
        self.send_header("Content-Length", str(len(BODY)))
        self.end_headers()
        self.wfile.write(BODY)

    def log_message(self, *a):
        pass


def test_stall_shows_in_latency_from_due_time():
    # a one-thread server: requests queue behind the stalled one
    srv = HTTPServer(("127.0.0.1", 0), _Stalling)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        url = f"http://127.0.0.1:{srv.server_address[1]}/sparql"
        window = [[0.1 * i, "Q", "SELECT ?x WHERE { ?x a ub:A }"]
                  for i in range(5)]
        gen = LoadGen(url, "d", {"warmup": [["Q", window[0][2]]],
                                 "window": window}, timeout_s=10)
        gen.wait(timeout=30)
    finally:
        srv.shutdown()
        srv.server_close()
    reqs = gen.results
    assert [r[4] for r in reqs] == [200] * 5
    lat = [r[3] - r[1] for r in reqs]
    # every request due during the stall waits for its end
    for i, x in enumerate(lat):
        assert x >= _Stalling.stall_s - 0.1 * i - 0.05, lat
    # the generator itself sent on time
    assert max(r[2] - r[1] for r in reqs) < 0.2
    assert reqs[0][5] == 1 and len(reqs[0][6]) == 64
