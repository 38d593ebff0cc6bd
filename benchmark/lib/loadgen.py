"""The load generator: a JAX-free child process of the harness.

Reads a schedule (``lib/traffic.schedule``) from a JSON file, offers it to
the server over HTTP as streamed generate requests, stamps every token
with ``time.monotonic()`` as the client receives it, and writes one record
per request to a JSON file. The arithmetic (the open loop, SSE token
timing) follows ``tools/serve_loadgen.py``, with two changes: a request is
timed from when it was DUE, not from when it was sent, and everything it
offers comes from the schedule, nothing from a clock or an unseeded draw.

``CLOCK_MONOTONIC`` is one clock for every process of a machine, so the
harness (which opens and closes the window) and this child agree on time.

Usage: ``python loadgen.py SCHEDULE.json URL T0 OUT.json``; ``T0`` is the
monotonic time at which the lead-in starts.
"""
from __future__ import annotations

import http.client
import json
import os
import socket
import sys
import threading
import time
from urllib.parse import urlparse


class Client:
    def __init__(self, url: str):
        u = urlparse(url)
        self.host, self.port, self.path = u.hostname, u.port, u.path
        self.stop = threading.Event()
        self._live = set()
        self._lock = threading.Lock()

    def abort_all(self):
        """Stop issuing and cut every open stream (the server frees the
        slot when the client goes away)."""
        self.stop.set()
        with self._lock:
            socks = list(self._live)
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass

    @staticmethod
    def record(req: dict, due) -> dict:
        return {"k": req["k"], "due": due,
                "prompt_tokens": req["prompt_tokens"],
                "max_tokens": req["max_tokens"], "sent": None, "token_t": [],
                "tokens": [], "finish": None, "error": None,
                "cached_tokens": None}

    def run(self, req: dict, rec: dict) -> dict:
        """One streamed generation, written into the request's record as
        it goes."""
        sock = None
        body = json.dumps({"prompt": req["prompt"],
                           "max_tokens": req["max_tokens"],
                           "temperature": 0.0, "stream": True}).encode()
        conn = http.client.HTTPConnection(self.host, self.port, timeout=120)
        try:
            conn.connect()
            sock = conn.sock
            with self._lock:
                self._live.add(sock)
            rec["sent"] = time.monotonic()
            conn.request("POST", self.path, body=body,
                         headers={"Content-Type": "application/json",
                                  "Connection": "close"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"http {resp.status}: {resp.read(200)!r}"
                return rec
            for raw in resp:
                if not raw.startswith(b"data: "):
                    continue
                now = time.monotonic()
                ev = json.loads(raw[6:])
                if "token" in ev:
                    rec["token_t"].append(now)
                    rec["tokens"].append(ev["token"])
                elif ev.get("done"):
                    rec["finish"] = ev.get("finish_reason")
                    rec["cached_tokens"] = ev.get("cached_tokens")
                    break
                elif "error" in ev:
                    rec["error"] = ev["error"]
                    break
            if rec["finish"] is None and rec["error"] is None:
                rec["error"] = "aborted" if self.stop.is_set() \
                    else "stream ended without a done event"
        except (OSError, http.client.HTTPException, ValueError) as e:
            rec["error"] = "aborted" if self.stop.is_set() \
                else f"{type(e).__name__}: {e}"
        finally:
            with self._lock:
                self._live.discard(sock)
            conn.close()
        return rec


def open_loop(client, sched, t0, records):
    """Send each request when it is due, whatever the earlier ones do.
    When the window has closed, wait until every request has its first
    token or has missed the limit, then cut the streams still running."""
    threads = []
    for req in sched["requests"]:
        delay = t0 + req["due"] - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = client.record(req, t0 + req["due"])
        records.append(rec)
        th = threading.Thread(target=client.run, args=(req, rec),
                              daemon=True)
        th.start()
        threads.append(th)
    close = t0 + sched["lead_in_s"] + sched["seconds"]
    _sleep_until(close)
    while time.monotonic() < close + sched["ttft_limit_s"] and any(
            not r["token_t"] and r["error"] is None for r in records):
        time.sleep(0.05)
    client.abort_all()
    for th in threads:
        th.join(10)


def _sleep_until(t):
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(0.05, left))


def main(argv):
    sched_path, url, t0, out_path = argv
    with open(sched_path) as f:
        sched = json.load(f)
    client = Client(url)
    records = []
    open_loop(client, sched, float(t0), records)
    with open(out_path + ".tmp", "w") as f:
        json.dump(records, f)
    os.replace(out_path + ".tmp", out_path)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
