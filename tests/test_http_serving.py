"""HTTP serving front end + never-idle engine lifecycle (DESIGN.md §11).

Pins the ISSUE-10 contract:

* incremental retirement: a server that pumps ``submit/step`` and is
  never idle must not leak finished records or uid claims --
  ``pop_finished`` releases both per result (3-overlapping-waves
  regression);
* ``cancel`` aborts a request in any state (pending arrival, waiting,
  live) and releases its pages/uid;
* ``throughput()`` is 0.0 -- never NaN -- at zero wall time;
* admission policies (headroom/watermark/lookahead/greedy) change WHEN
  requests are admitted, never WHAT they generate;
* the HTTP layer end to end: N concurrent streamed/non-streamed
  connections byte-identical to solo ``Engine.serve()`` oracles (mixed
  plans + priorities), client-disconnect abort releases pages/uids, bad
  bodies get 400s, and ``/v1/stats`` stays finite mid-flight.
"""

import http.client
import json
import math
import socket
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.core import uniform_plan
from repro.serving import (ADMISSION_POLICIES, ApiServer, Engine, Request,
                           VirtualClock)
from repro.serving.detok import default_decode


def small_cfg():
    return get_config("olmo-1b").reduced().with_(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        d_ff=128, vocab_size=128, vocab_pad_multiple=16, dtype="float32")


def moe_cfg():
    return get_config("olmoe-1b-7b").reduced().with_(
        num_layers=2, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        num_experts=4, moe_top_k=2, moe_d_ff=64, vocab_size=128,
        vocab_pad_multiple=16, dtype="float32", moe_impl="gmm")


@pytest.fixture(scope="module")
def setup():
    cfg = small_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def moe_setup():
    cfg = moe_cfg()
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _prompt(vocab, n, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, n).astype(np.int32)


def _req(vocab, uid, n=7, max_new=5, seed=None, **kw):
    return Request(uid=uid, prompt=_prompt(vocab, n, uid if seed is None
                                           else seed),
                   max_new_tokens=max_new, **kw)


# --------------------------------------------------------------------- #
# Engine lifecycle (no HTTP): the bugs the server surfaced
# --------------------------------------------------------------------- #
class TestNeverIdleLifecycle:
    def test_three_overlapping_waves_never_idle(self, setup):
        """The headline leak: reset_stats() refuses unless idle() and
        clear_finished() was the only uid release, so an open-loop
        engine grew sched.finished forever.  Serve 3 waves through
        submit/step, each submitted while the previous is mid-flight
        (the engine is never idle), retiring incrementally -- records
        stay empty, uid claims release, uids become reusable."""
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=4, max_len=64,
                     clock=VirtualClock())
        vocab = cfg.vocab_size

        def wave(w):
            return [_req(vocab, uid=w * 3 + i, n=5 + 2 * i, max_new=5)
                    for i in range(3)]

        results = {}

        def pump_once():
            eng.step()
            for res in eng.pop_finished():
                results[res.uid] = res
            # incremental retirement: records never accumulate
            assert eng.sched.finished == []

        for r in wave(0):
            eng.submit(r)
        for w in (1, 2):
            pump_once()
            pump_once()
            assert not eng.idle(), "waves must overlap"
            for r in wave(w):
                eng.submit(r)
        guard = 0
        while not eng.idle():
            pump_once()
            guard += 1
            assert guard < 500
        assert sorted(results) == list(range(9))
        assert all(r.finished_reason in ("length", "eos")
                   for r in results.values())
        assert all(len(r.tokens) > 0 for r in results.values())
        # every uid claim released -> uid reuse works (the leak made
        # this permanently impossible without a full reset)
        assert eng.sched._uids == set()
        eng.submit(_req(vocab, uid=0))
        while not eng.idle():
            eng.step()
        assert [r.uid for r in eng.pop_finished()] == [0]

    def test_cancel_in_every_state(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=1, max_len=64,
                     cache_layout="paged", page_size=8,
                     clock=VirtualClock())
        vocab = cfg.vocab_size
        free0 = eng.kv.free_pages()
        # (1) pending: scheduled to arrive in the far future
        eng.submit(_req(vocab, uid=0), arrival_time=eng.clock.now() + 1e6)
        assert eng.cancel(0, reason="aborted_x")
        (res,) = eng.pop_finished()
        assert res.uid == 0 and res.finished_reason == "aborted_x"
        assert eng.idle()
        # (2) + (3) live and waiting: max_batch=1 forces a queue
        eng.submit(_req(vocab, uid=1))
        eng.submit(_req(vocab, uid=2))
        eng.step()
        assert len(eng.sched.waiting) == 1
        assert eng.cancel(2)        # waiting
        assert eng.cancel(1)        # live in a slot
        assert eng.idle()
        got = {r.uid: r.finished_reason for r in eng.pop_finished()}
        assert got == {1: "cancelled", 2: "cancelled"}
        assert eng.kv.free_pages() == free0     # live pages released
        assert eng.sched._uids == set()
        # (4) unknown or already-finished uids refuse
        assert not eng.cancel(99)
        assert not eng.cancel(1)

    def test_throughput_zero_wall_is_zero_not_nan(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=2, max_len=64,
                     clock=VirtualClock(tick=0.0))   # frozen time
        assert eng.throughput() == 0.0      # never served at all
        out = eng.serve([_req(cfg.vocab_size, uid=0, max_new=3)])
        assert out[0].tokens and eng.stats["wall_s"] == 0.0
        t = eng.throughput()
        assert t == 0.0 and not math.isnan(t)


class TestAdmissionPolicies:
    def test_invalid_policy_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="admission"):
            Engine(cfg, params, admission="bogus")

    def test_policies_need_on_demand_admission(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="preemption"):
            Engine(cfg, params, cache_layout="paged", preemption=False,
                   admission="watermark")
        # headroom (the default) is fine without preemption: whole-
        # lifetime reservation never over-admits, the gate is inert
        Engine(cfg, params, cache_layout="paged", preemption=False)

    def test_outputs_identical_across_policies(self, setup):
        """Admission gates change when requests enter the batch, never
        what they generate: a pressured pool serves token-identical
        results under all four policies (greedy may thrash -- preempt-
        and-recompute is exact, so even the no-gate baseline agrees)."""
        cfg, params = setup
        vocab = cfg.vocab_size
        outs, preempts = {}, {}
        for pol in ADMISSION_POLICIES:
            eng = Engine(cfg, params, max_batch=3, max_len=64,
                         cache_layout="paged", page_size=8, num_pages=7,
                         admission=pol, clock=VirtualClock())
            res = eng.serve([_req(vocab, uid=i, n=n, max_new=6)
                             for i, n in enumerate((5, 9, 13))],
                            max_steps=2000)
            outs[pol] = [(r.uid, r.tokens) for r in res]
            preempts[pol] = eng.stats["preemptions"]
        for pol in ADMISSION_POLICIES[1:]:
            assert outs[pol] == outs[ADMISSION_POLICIES[0]], pol


# --------------------------------------------------------------------- #
# HTTP layer
# --------------------------------------------------------------------- #
def _post(api, body, timeout=180):
    """One completion over a real socket; returns (status, events) where
    events is the parsed NDJSON line list (streamed) or [result]."""
    conn = http.client.HTTPConnection(api.host, api.port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
        if resp.status != 200:
            return resp.status, [json.loads(raw)]
        if isinstance(body, dict) and body.get("stream"):
            return 200, [json.loads(ln) for ln in raw.splitlines()]
        return 200, [json.loads(raw)]
    finally:
        conn.close()


class TestHttpApi:
    def test_concurrent_streams_match_solo_oracles(self, moe_setup):
        """The acceptance bar: N concurrent connections -- mixed
        streamed/blocking, mixed plans (base + a registered k=1 plan),
        mixed priorities -- produce token/text sequences byte-identical
        to solo Engine.serve(detok=True) oracles, and every streamed
        response's delta concatenation equals its final text."""
        cfg, params = moe_setup
        vocab = cfg.vocab_size
        specs = [  # (prompt_len, plan, priority, stream)
            (5, None, 0, True), (9, "k1", 0, False), (13, None, 1, True),
            (7, "k1", 1, True), (6, None, 0, False), (11, "k1", 0, True)]

        oracle = Engine(cfg, params, max_batch=1, max_len=64)
        oracle.add_plan("k1", uniform_plan(cfg, 1))
        expected = []
        for i, (n, plan, prio, _) in enumerate(specs):
            (r,) = oracle.serve(
                [Request(uid=0, prompt=_prompt(vocab, n, seed=i),
                         max_new_tokens=6, plan=plan, priority=prio)],
                detok=True)
            expected.append((r.tokens, r.text))

        eng = Engine(cfg, params, max_batch=4, max_len=64)
        eng.add_plan("k1", uniform_plan(cfg, 1))
        got = [None] * len(specs)

        def worker(i):
            n, plan, prio, stream = specs[i]
            body = {"prompt": _prompt(vocab, n, seed=i).tolist(),
                    "max_new_tokens": 6, "priority": prio, "stream": stream}
            if plan:
                body["plan"] = plan
            status, events = _post(api, body)
            got[i] = (status, events)

        with ApiServer(eng) as api:
            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(len(specs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            assert not any(t.is_alive() for t in threads)

        for i, (n, plan, prio, stream) in enumerate(specs):
            status, events = got[i]
            assert status == 200, events
            final = events[-1]
            res = final["result"] if stream else final
            assert (res["tokens"], res["text"]) == expected[i], \
                f"request {i} diverged from its solo oracle"
            assert res["served_plan"] == (plan or "base")
            assert res["finished_reason"] in ("length", "eos")
            if stream:
                assert final.get("done") is True
                deltas = [ev["delta"] for ev in events[:-1]]
                assert all("delta" in ev for ev in events[:-1])
                assert "".join(deltas) == res["text"]
                assert res["text"] == default_decode(res["tokens"])
        # server handed the engine back clean: no leaked records/claims
        assert eng.sched._uids == set() and eng.sched.finished == []

    def test_client_disconnect_releases_pages_and_uid(self, setup):
        """An abandoned stream must not wedge the engine: the failed
        delta write maps to Engine.cancel, releasing the slot, its KV
        pages, and (via retirement) the uid claim."""
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=2, max_len=256,
                     cache_layout="paged", page_size=8)
        free0 = eng.kv.free_pages()
        with ApiServer(eng) as api:
            body = json.dumps({"prompt": list(range(1, 6)),
                               "max_new_tokens": 200, "stream": True}).encode()
            s = socket.create_connection((api.host, api.port), timeout=60)
            s.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                      b"Host: t\r\nContent-Length: "
                      + str(len(body)).encode() + b"\r\n\r\n" + body)
            s.recv(4096)        # headers (and possibly the first deltas)
            s.close()           # walk away mid-stream
            deadline = time.monotonic() + 30
            clean = False
            while time.monotonic() < deadline and not clean:
                with api.lock:
                    clean = (not api._live and eng.sched.done()
                             and not eng.sched._uids
                             and eng.kv.free_pages() == free0)
                time.sleep(0.02)
            assert clean, "disconnect did not release pages/uid/records"

    def test_submit_not_starved_by_a_busy_pump(self, setup):
        """A submission queued for the engine lock goes in before the
        pump's next step: a pump running back-to-back steps would
        otherwise retake the lock first, and a new request would wait
        until the engine went idle."""
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=2, max_len=4096)
        steps = [0]
        step = eng.step

        def counted_step():
            steps[0] += 1
            return step()

        eng.step = counted_step
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            with ApiServer(eng) as api:
                api.submit({"prompt": [3, 1, 4], "max_new_tokens": 4000})
                deadline = time.monotonic() + 60
                while steps[0] < 5 and time.monotonic() < deadline:
                    time.sleep(0.01)
                waited = []
                for _ in range(10):
                    before = steps[0]
                    api.submit({"prompt": [2, 7, 1], "max_new_tokens": 1})
                    waited.append(steps[0] - before)
                    time.sleep(0.005)
                with api.lock:
                    busy = not eng.sched.done()
        finally:
            sys.setswitchinterval(switch)
        assert busy, ("the long request finished before the check", waited)
        # the step under way when the submission queued, and at most one
        # that began before it was counted as queued
        assert max(waited) <= 2, waited

    def test_stats_finite_and_health_midflight(self, setup):
        """/v1/stats must be valid strict JSON (no NaN/Infinity) at any
        moment, including while requests are live."""
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=2, max_len=256)

        def no_const(name):
            raise AssertionError(f"non-finite {name} in /v1/stats")

        def check_finite(x):
            if isinstance(x, dict):
                for v in x.values():
                    check_finite(v)
            elif isinstance(x, float):
                assert math.isfinite(x)

        done = threading.Event()

        def long_request():
            _post(api, {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 150,
                        "stream": True})
            done.set()

        with ApiServer(eng) as api:
            conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
            conn.request("GET", "/health")
            assert json.loads(conn.getresponse().read())["ok"] is True
            t = threading.Thread(target=long_request)
            t.start()
            saw_live = False
            while not done.is_set():
                conn.request("GET", "/v1/stats")
                stats = json.loads(conn.getresponse().read(),
                                   parse_constant=no_const)
                check_finite(stats)
                saw_live |= (stats["server"]["live_requests"] > 0
                             or stats["server"]["open_completions"] > 0)
                time.sleep(0.01)
            t.join(timeout=60)
            conn.request("GET", "/v1/stats")
            stats = json.loads(conn.getresponse().read(),
                               parse_constant=no_const)
            conn.close()
        assert saw_live, "never scraped stats with a request in flight"
        assert stats["server"]["open_completions"] == 0
        assert stats["engine"]["decode_tokens"] > 0
        assert stats["throughput_tok_per_s"] > 0

    def test_bad_requests_rejected(self, setup):
        cfg, params = setup
        eng = Engine(cfg, params, max_batch=2, max_len=64)
        with ApiServer(eng) as api:
            for body in ({},                                # no prompt
                         {"prompt": []},                    # empty
                         {"prompt": "abc"},                 # not ids
                         {"prompt": [1, 2], "nope": 1},     # unknown field
                         {"prompt": [1, 2], "eos_id": "x"},
                         [1, 2, 3]):                        # not an object
                status, (err,) = _post(api, body)
                assert status == 400 and "error" in err, body
            # syntactically broken JSON
            conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
            for method, path, body, want in (
                    ("POST", "/v1/completions", "{nope", 400),
                    ("GET", "/nope", None, 404),
                    ("POST", "/nope", "{nope", 404)):
                conn.request(method, path, body=body)
                resp = conn.getresponse()
                resp.read()     # drain: keep-alive needs a finished response
                assert resp.status == want, (method, path)
            conn.close()
            # semantic rejection rides the normal result path
            status, (res,) = _post(api, {"prompt": [1, 2, 3],
                                         "plan": "not-registered"})
            assert status == 200
            assert res["finished_reason"] == "rejected_unknown_plan"
        assert eng.sched._uids == set() and eng.sched.finished == []
