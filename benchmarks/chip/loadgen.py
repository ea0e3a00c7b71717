"""Open-loop HTTP load generator, run as a child of ``run.py``.

It never imports JAX, so its threads share no interpreter lock with the
server and the chip belongs to the server's process.  It reads one JSON
object on standard input::

    {"url": "http://127.0.0.1:PORT/sparql", "dataset": "lubm",
     "timeout_s": 90, "threads": 256, "lead_s": 0.5,
     "warmup": [[template, query], ...],
     "window": [[due_s, template, query], ...]}

sends the warm-up requests one after another (closed loop), then prints
``{"event": "window", "t0": T0}`` with ``T0`` on ``time.monotonic()`` (one
clock for every process of the machine) and sends each window request at
``T0 + due_s`` whether or not earlier ones have finished.  A request's
latency runs from its due time to the last byte of its response, so a
stall anywhere, the generator's own lateness included, shows in it.  When
every window request has its response or has failed, it prints
``{"event": "results", "requests": [...]}`` and exits.  Each request is
``[template, due_s, sent_s, done_s, status, rows, digest, error]`` with
times relative to ``T0``; ``status`` 0 means no HTTP response came.
"""

from __future__ import annotations

import hashlib
import http.client
import json
import sys
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

from answers import digest_response


def post(url: str, dataset: str, query: str, timeout_s: float):
    """One ``POST /sparql``; returns ``(status, body, error)``."""
    u = urllib.parse.urlsplit(url)
    body = urllib.parse.urlencode({"query": query, "dataset": dataset})
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout_s)
    try:
        conn.request("POST", u.path, body=body, headers={
            "Content-Type": "application/x-www-form-urlencoded"})
        resp = conn.getresponse()
        return resp.status, resp.read(), ""
    except (OSError, http.client.HTTPException) as e:
        return 0, b"", f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def main() -> int:
    job = json.loads(sys.stdin.read())
    url, dataset, timeout_s = job["url"], job["dataset"], job["timeout_s"]
    for _name, query in job["warmup"]:
        post(url, dataset, query, timeout_s)

    window = job["window"]
    out: list = [None] * len(window)
    bodies: dict[str, bytes] = {}
    lock = threading.Lock()
    t0 = time.monotonic() + job["lead_s"]
    print(json.dumps({"event": "window", "t0": t0}), flush=True)

    def fire(i: int) -> None:
        due, name, query = window[i]
        sent = time.monotonic() - t0
        status, body, err = post(url, dataset, query, timeout_s)
        done = time.monotonic() - t0
        key = hashlib.sha256(body).hexdigest() if status == 200 else ""
        if status not in (0, 200):
            err = body[:300].decode(errors="replace")
        if key:
            with lock:
                bodies.setdefault(key, body)
        out[i] = [name, due, sent, done, status, key, err]

    with ThreadPoolExecutor(max_workers=job["threads"]) as pool:
        for i, (due, _name, _query) in enumerate(window):
            delay = t0 + due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            pool.submit(fire, i)

    # the window is over: reduce each distinct body to its answer digest
    digests = {}
    for key, body in bodies.items():
        try:
            digests[key] = digest_response(body)
        except (ValueError, KeyError, TypeError) as e:
            digests[key] = (-1, f"unreadable: {type(e).__name__}: {e}")
    reqs = []
    for name, due, sent, done, status, key, err in out:
        rows, digest = digests.get(key, (0, ""))
        reqs.append([name, due, sent, done, status, rows, digest, err])
    print(json.dumps({"event": "results", "requests": reqs}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
