"""Who owns the KV pool.

The decode and chunk programs donate their cache argument, so XLA
updates the pool's pages in place.  These tests pin the contract:

* the engine hands its pool over: after a step, the pool it passed in
  is deleted;
* any other caller keeps its pool: ``ModelRunner.decode`` /
  ``chunk_prefill`` with the default run on a copy, leave the caller's
  arrays live, give the same logits when called again on the same pool,
  and run the one compiled program the engine runs (what a warm-up
  relies on);
* donation changes no served token;
* ``pool_copies`` counts the copies, and stays 0 while serving.
"""

import http.client
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.serving import ApiServer, Engine, Request
from repro.serving.runner import ModelRunner

B, C = 4, 4


def moe_cfg():
    return get_config("olmoe-1b-7b").reduced().with_(
        num_layers=3, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        num_experts=8, moe_top_k=4, moe_d_ff=64, vocab_size=128,
        vocab_pad_multiple=16, dtype="float32", moe_impl="gmm")


@pytest.fixture(scope="module")
def setup():
    cfg = moe_cfg()
    return cfg, models.init_params(jax.random.PRNGKey(0), cfg)


def _engine(setup, **kw):
    cfg, params = setup
    kw = {"max_batch": B, "max_len": 64, "prefill_chunk": C,
          "use_kernel": True, "use_moe_decode": True, **kw}
    return Engine(cfg, params, **kw)


def _requests(n=5):
    rng = np.random.default_rng(0)
    return [Request(uid=i, prompt=rng.integers(0, 128, 3 + 4 * i)
                    .astype(np.int32), max_new_tokens=5 + i)
            for i in range(n)]


def _step_args(eng, kind):
    """A decode or chunk call's arguments over the engine's own pool."""
    kv = eng.kv
    for s, p in enumerate((3, -1, 5, 2)):
        if p >= 0:
            assert kv.allocate(s, p + 1)
    if kind == "decode":
        return eng.runner.decode, (
            jnp.arange(B, dtype=jnp.int32), jnp.asarray([3, -1, 5, 2],
                                                        jnp.int32),
            kv.caches, kv.block_tables()), {
            "use_kernel": True, "kernel_blocks": 1, "moe_decode": True}
    pos = np.full((B, C), -1, np.int32)
    pos[0] = np.arange(C)
    return eng.runner.chunk_prefill, (
        jnp.ones((B, C), jnp.int32), jnp.asarray(pos),
        jnp.full(B, C - 1, jnp.int32), kv.caches, kv.block_tables()), {}


def test_engine_step_deletes_the_pool_it_handed_over(setup, monkeypatch):
    eng = _engine(setup)
    handed = {"decode": [], "chunk": []}
    for kind, name, at in (("decode", "decode", 2),
                           ("chunk", "chunk_prefill", 3)):
        real = getattr(eng.runner, name)

        def spy(*a, _real=real, _kind=kind, _at=at, **kw):
            handed[_kind].append(jax.tree.leaves(a[_at]))
            return _real(*a, **kw)
        monkeypatch.setattr(eng.runner, name, spy)
    eng.serve(_requests(3))
    assert handed["decode"] and handed["chunk"]
    for leaves in handed["decode"] + handed["chunk"]:
        assert all(x.is_deleted() for x in leaves)
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.kv.caches))
    assert eng.stats["pool_copies"] == 0


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_default_call_keeps_the_pool_and_runs_the_engines_program(setup,
                                                                   kind):
    eng = _engine(setup)
    fn, args, kw = _step_args(eng, kind)
    keys = set(eng.runner.compiled_specializations())
    first, _ = fn(*args, **kw)
    again, _ = fn(*args, **kw)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(again))
    assert not any(x.is_deleted() for x in jax.tree.leaves(eng.kv.caches))
    new = set(eng.runner.compiled_specializations()) - keys
    assert len(new) == 1
    assert eng.stats["pool_copies"] == 2
    # the engine's own call (the pool handed over) finds that program
    # compiled already: the warm-up's call compiled what serving runs
    program = eng.runner._jit[new.pop()]
    donated, _ = fn(*args, **kw, donate=True)
    np.testing.assert_array_equal(np.asarray(first), np.asarray(donated))
    assert all(x.is_deleted() for x in jax.tree.leaves(eng.kv.caches))
    assert program._cache_size() == 1
    assert eng.stats["pool_copies"] == 2


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_donation_changes_no_served_token(setup, monkeypatch, layout):
    kw = ({"cache_layout": "contiguous", "use_kernel": False}
          if layout == "contiguous" else {})
    donated = _engine(setup, **kw).serve(_requests())
    # the data flow before donation: every step keeps its input pool live
    # and writes a new one
    monkeypatch.setattr(ModelRunner, "_own",
                        lambda self, caches, donate: jax.tree.map(jnp.copy,
                                                                  caches))
    kept = _engine(setup, **kw).serve(_requests())
    assert [r.tokens for r in donated] == [r.tokens for r in kept]
    assert all(len(r.tokens) == 5 + r.uid for r in donated)


def _stream(api, prompt, max_new):
    conn = http.client.HTTPConnection(api.host, api.port, timeout=180)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(
            {"prompt": prompt.tolist(), "max_new_tokens": max_new,
             "stream": True}), headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        lines = resp.read().decode().splitlines()
        assert resp.status == 200
        return json.loads(lines[-1])
    finally:
        conn.close()


def _get_stats(api):
    conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
    try:
        conn.request("GET", "/v1/stats")
        return json.loads(conn.getresponse().read().decode())
    finally:
        conn.close()


def test_pool_copies_stay_zero_while_serving_after_a_warm_up(setup):
    eng = _engine(setup)
    for kind in ("decode", "chunk"):          # a benchmark-style warm-up
        fn, args, kw = _step_args(eng, kind)
        jax.block_until_ready(fn(*args, **kw)[0])
        for s in range(B):
            eng.kv.release(s)
    assert eng.stats["pool_copies"] == 2
    eng.reset_stats()
    assert eng.stats["pool_copies"] == 0
    reqs, out = _requests(4), [None] * 4
    with ApiServer(eng) as api:
        def worker(i):
            out[i] = _stream(api, reqs[i].prompt, reqs[i].max_new_tokens)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=300)
        assert not any(th.is_alive() for th in threads)
        engine = _get_stats(api)["engine"]
    assert all(o["done"] for o in out)
    assert engine["steps"] > 0 and engine["chunk_steps"] > 0
    assert engine["pool_copies"] == 0
