"""Load generator: drives ``POST /v1/completions`` over loopback HTTP.

    python bench/loadgen.py      (spec on stdin, records on stdout)

A child process of the benchmark.  It imports nothing but the standard
library -- never JAX -- so it shares neither the server's interpreter lock
nor the chip.  Protocol, one JSON object per line:

1. stdin: the spec ``{"host", "port", "loop", "clients", "t_start",
   "requests": [...]}`` (requests as ``bench/traffic.py`` makes them; an
   open loop's ``due`` is seconds after ``t_start``, a ``perf_counter``
   reading, which on Linux is one clock for every process).
2. stdin: ``{"stop": t1, "wait_first_s": s}`` when the window closes.  No
   request is sent after it.  Requests due by ``t1`` that have no first
   token yet are waited for up to ``wait_first_s`` more; then every open
   stream is closed, which the server maps to a cancel.
3. stdout: one line with every request's record, times absolute.

A closed-loop request is due when its client's previous one completed (or
at ``t_start``); an open-loop one at ``t_start + due``.  Latency counts
from the due time, so a late send shows as latency; ``sent - due`` is how
late the generator ran.
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time


class Stream:
    """One request on its own connection; fills its record as it reads."""

    def __init__(self, spec, req, due):
        self.spec, self.req = spec, req
        self.rec = {"id": req["id"], "client": req.get("client"),
                    "due": due, "sent": None, "first": None,
                    "tokens_t": [], "done": None, "status": "pending",
                    "result": None}
        self.conn = None
        self.closed = False

    def run(self):
        rec, req = self.rec, self.req
        body = {"prompt": req["prompt"], "max_new_tokens": req["max_new"],
                "temperature": req["temperature"], "top_k": req["top_k"],
                "plan": req["plan"], "stream": True}
        try:
            self.conn = http.client.HTTPConnection(
                self.spec["host"], self.spec["port"], timeout=600)
            rec["sent"] = time.perf_counter()
            self.conn.request("POST", "/v1/completions",
                              body=json.dumps(body))
            resp = self.conn.getresponse()
            if resp.status != 200:
                rec["status"] = f"http {resp.status}"
                return
            while True:
                line = resp.readline()
                now = time.perf_counter()
                if not line:
                    rec["status"] = rec["status"] if self.closed else \
                        "stream ended without a done line"
                    return
                ev = json.loads(line)
                if "delta" in ev:
                    rec["tokens_t"].append(now)
                    if rec["first"] is None:
                        rec["first"] = now
                elif ev.get("done"):
                    rec["done"] = now
                    rec["result"] = {k: ev["result"][k] for k in (
                        "tokens", "prompt_len", "finished_reason", "ttft_s",
                        "queue_delay_s", "served_plan")}
                    rec["status"] = "ok"
                    resp.read()         # the closing chunk: a clean close
                    return
                else:
                    rec["status"] = f"stream error: {ev}"
                    return
        except (OSError, http.client.HTTPException, ValueError) as e:
            if not self.closed:
                rec["status"] = f"error: {e!r}"
        finally:
            if self.closed and rec["status"] == "pending":
                rec["status"] = "closed at the window's end"
            if self.conn is not None:
                self.conn.close()

    def close(self):
        """Cut the connection (the server cancels the request)."""
        self.closed = True
        conn = self.conn
        if conn is not None and conn.sock is not None:
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass


class LoadGen:
    def __init__(self, spec):
        self.spec = spec
        self.streams = []
        self.lock = threading.Lock()
        self.stop_at = None             # set by the stop line
        self.stopped = threading.Event()
        self.threads = []

    def _launch(self, req, due):
        st = Stream(self.spec, req, due)
        with self.lock:
            self.streams.append(st)
        st.run()
        return st

    def _client(self, reqs):
        due = self.spec["t_start"]
        _sleep_until(due, self.stopped)
        for req in reqs:
            if self.stopped.is_set():
                return
            st = self._launch(req, due)
            due = st.rec["done"] or time.perf_counter()

    def _open(self, reqs):
        t0 = self.spec["t_start"]
        for req in reqs:
            due = t0 + req["due"]
            _sleep_until(due, None, self.stop_at_fn)
            stop = self.stop_at_fn()
            if stop is not None and due > stop:
                return
            th = threading.Thread(target=self._launch, args=(req, due),
                                  daemon=True)
            th.start()
            with self.lock:
                self.threads.append(th)

    def stop_at_fn(self):
        return self.stop_at

    def start(self):
        reqs = self.spec["requests"]
        if self.spec["loop"] == "closed":
            n = self.spec["clients"]
            for c in range(n):
                mine = [r for r in reqs if r["client"] == c]
                self.threads.append(threading.Thread(
                    target=self._client, args=(mine,), daemon=True))
        else:
            self.threads.append(threading.Thread(
                target=self._open, args=(reqs,), daemon=True))
        for th in list(self.threads):
            th.start()

    def finish(self, stop, wait_first_s):
        """Send nothing after ``stop``; wait for first tokens of requests
        due by then, at most ``wait_first_s``; close the rest."""
        self.stop_at = stop
        self.stopped.set()
        deadline = time.perf_counter() + wait_first_s
        while time.perf_counter() < deadline:
            with self.lock:
                waiting = [s for s in self.streams
                           if s.rec["due"] <= stop and s.rec["first"] is None
                           and s.rec["status"] == "pending"]
            if not waiting:
                break
            time.sleep(0.01)
        with self.lock:
            streams = list(self.streams)
        for st in streams:
            if st.rec["status"] == "pending":
                st.close()
        for th in list(self.threads):
            th.join(timeout=30)
        return [s.rec for s in streams]


def _sleep_until(t, stopped=None, stop_fn=None):
    while True:
        now = time.perf_counter()
        if now >= t or (stopped is not None and stopped.is_set()):
            return
        if stop_fn is not None and stop_fn() is not None:
            return
        time.sleep(min(t - now, 0.005))


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    gen = LoadGen(spec)
    gen.start()
    stop = json.loads(sys.stdin.readline())
    records = gen.finish(stop["stop"], stop["wait_first_s"])
    sys.stdout.write(json.dumps(records) + "\n")
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
