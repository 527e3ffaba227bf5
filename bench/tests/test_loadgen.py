"""The load generator times every request from its due time, over a
stand-in server that streams like ``ApiServer``."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from bench import run

TOKEN_S = 0.01


class _Stream(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"

    def log_message(self, *a):
        pass

    def _chunk(self, obj):
        data = (json.dumps(obj) + "\n").encode()
        self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")
        self.wfile.flush()

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        toks = []
        for i in range(body["max_new_tokens"]):
            time.sleep(TOKEN_S)
            toks.append(i)
            self._chunk({"delta": f"<{i}>"})
        self._chunk({"done": True, "result": {
            "tokens": toks, "prompt_len": len(body["prompt"]),
            "finished_reason": "length", "ttft_s": TOKEN_S,
            "queue_delay_s": 0.0, "served_plan": body["plan"]}})
        self.wfile.write(b"0\r\n\r\n")
        self.wfile.flush()


@pytest.fixture(scope="module")
def server():
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), _Stream)
    httpd.daemon_threads = True
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    yield httpd.server_address
    httpd.shutdown()
    httpd.server_close()


def _drive(server, loop, requests, run_s):
    gen = subprocess.Popen([sys.executable,
                            os.path.join(run.HERE, "loadgen.py")],
                           stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                           text=True)
    t_start = time.perf_counter() + 0.1
    gen.stdin.write(json.dumps({
        "host": server[0], "port": server[1], "loop": loop, "clients": 1,
        "t_start": t_start, "requests": requests}) + "\n")
    gen.stdin.flush()
    time.sleep(max(0.0, t_start + run_s - time.perf_counter()))
    gen.stdin.write(json.dumps({"stop": t_start + run_s,
                                "wait_first_s": 2.0}) + "\n")
    out, _ = gen.communicate(timeout=60)
    assert gen.returncode == 0
    return t_start, {r["id"]: r for r in json.loads(out)}


def _req(i, n, **kw):
    return dict({"id": i, "prompt": [1, 2, 3], "max_new": n, "plan": "base",
                 "temperature": 0.0, "top_k": 0}, **kw)


def test_open_loop_times_from_the_due_time(server):
    reqs = [_req(i, 5, due=0.1 * i) for i in range(4)]
    t_start, recs = _drive(server, "open", reqs, 1.0)
    assert sorted(recs) == [0, 1, 2, 3]
    for r in reqs:
        rec = recs[r["id"]]
        assert rec["due"] == pytest.approx(t_start + r["due"], abs=1e-9)
        assert rec["sent"] >= rec["due"]
        # late only by scheduling noise (the test machine may be busy)
        assert rec["sent"] - rec["due"] < 1.0
        assert rec["status"] == "ok"
        assert len(rec["tokens_t"]) == 5
        assert rec["first"] == rec["tokens_t"][0] > rec["sent"]
        assert rec["result"]["tokens"] == [0, 1, 2, 3, 4]


def test_open_loop_sends_nothing_after_the_window(server):
    reqs = [_req(0, 2, due=0.0), _req(1, 2, due=5.0)]
    _, recs = _drive(server, "open", reqs, 0.5)
    assert sorted(recs) == [0]


def test_closed_loop_next_request_is_due_at_the_last_completion(server):
    reqs = [_req(i, 3, client=0) for i in range(3)]
    t_start, recs = _drive(server, "closed", reqs, 0.5)
    assert recs[0]["due"] == pytest.approx(t_start, abs=1e-9)
    assert recs[1]["due"] == recs[0]["done"]
    assert recs[2]["due"] == recs[1]["done"]


def test_streams_open_at_the_end_are_closed(server):
    reqs = [_req(0, 500, client=0)]
    _, recs = _drive(server, "closed", reqs, 0.3)
    assert recs[0]["status"] == "closed at the window's end"
    assert 0 < len(recs[0]["tokens_t"]) < 500
