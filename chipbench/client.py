"""The open-loop HTTP client: a process of its own, so that its Python
does not take the interpreter lock of the server under test.

It reads one JSON object from standard input,

    {"port": 8080, "requests": [{"due_s": 0.0, "route": "/v2/answer",
     "payload": {...}}, ...], "workers": 64, "timeout_s": 120.0}

prints ``READY <wall second the window starts>`` and sends each request
at its due time whatever became of the earlier ones.  A request's latency
runs from the instant it was due to the last byte of its response, so a
stall is charged to every request it delays.  The last line it prints is
one JSON object: per request the latency, how late it was sent, the HTTP
status, the error if any and the body.  It imports nothing
but the standard library and never touches JAX.
"""

from __future__ import annotations

import http.client
import json
import sys
import threading
import time


LEAD_S = 0.5  # from READY to the window's start: time for the threads to start


def drive(spec: dict, announce=None) -> dict:
    """Send ``spec["requests"]`` on schedule; returns the results."""
    requests = spec["requests"]
    port = int(spec["port"])
    timeout_s = float(spec.get("timeout_s", 120.0))
    results: list[dict | None] = [None] * len(requests)
    next_index = [0]
    lock = threading.Lock()
    start_wall = time.time() + LEAD_S
    start = time.monotonic() + (start_wall - time.time())
    if announce is not None:
        announce(start_wall)

    def worker() -> None:
        conn = None
        while True:
            with lock:
                i = next_index[0]
                if i >= len(requests):
                    break
                next_index[0] = i + 1
            req = requests[i]
            due = start + float(req["due_s"])
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            sent = time.monotonic()
            headers = {"Content-Type": "application/json"}
            out = {"late_ms": (sent - due) * 1e3, "status": 0}
            try:
                if conn is None:
                    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout_s)
                conn.request("POST", req["route"], json.dumps(req["payload"]), headers)
                resp = conn.getresponse()
                body = resp.read()
                out["status"] = resp.status
                out["body"] = body.decode("utf-8", "replace")
            except (OSError, http.client.HTTPException) as exc:
                out["error"] = f"{type(exc).__name__}: {exc}"[:200]
                if conn is not None:
                    conn.close()
                conn = None
            done = time.monotonic()
            out["latency_ms"] = (done - due) * 1e3
            out["done_s"] = done - start
            results[i] = out
        if conn is not None:
            conn.close()

    threads = [
        threading.Thread(target=worker, daemon=True, name=f"client-{n}")
        for n in range(min(int(spec.get("workers", 64)), max(1, len(requests))))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"start_wall": start_wall, "results": results}


def main() -> None:
    spec = json.loads(sys.stdin.read())

    def announce(start_wall: float) -> None:
        print(f"READY {start_wall!r}", flush=True)

    print(json.dumps(drive(spec, announce)), flush=True)


if __name__ == "__main__":
    main()
