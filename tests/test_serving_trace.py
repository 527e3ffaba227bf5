"""Spans and counters inside the serving loop (``serving/trace.py``).

Pins what the benchmark's program counters read:

* ``phase`` adds each phase's wall time to ``host_s:<name>`` and the
  calling thread's CPU time, waits left out, to ``host_cpu_s``;
* a workload served over HTTP advances every phase counter,
  ``iterations``, ``chunk_steps`` and ``host_cpu_s``; the leaf phases fit
  inside the serve's wall time, and ``/v1/stats`` carries every counter,
  finite;
* the decode program's routed-experts count equals a host recount from
  the router's own ids -- on the fused ``decode`` path and on ``gmm``,
  with dead slots left out and, in a mixed-plan batch, the surplus
  routed slots past each request's budget left out;
* ``ModelRunner.decode``/``chunk_prefill`` and ``models.decode_fn``
  return what they returned before the count existed.
"""

import http.client
import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import get_config
from repro.models.moe import decode as decode_impl
from repro.models.moe import gmm as gmm_impl
from repro.models.opts import ModelOpts
from repro.serving import ApiServer, Engine, Request
from repro.serving.runner import ModelRunner
from repro.serving.trace import CPU_KEY, PHASES, WALL_PREFIX, phase

E, K = 8, 4


def moe_cfg():
    return get_config("olmoe-1b-7b").reduced().with_(
        num_layers=3, d_model=64, num_heads=2, num_kv_heads=2, head_dim=32,
        num_experts=E, moe_top_k=K, moe_d_ff=64, vocab_size=128,
        vocab_pad_multiple=16, dtype="float32", moe_impl="gmm")


@pytest.fixture(scope="module")
def setup():
    cfg = moe_cfg()
    return cfg, models.init_params(jax.random.PRNGKey(0), cfg)


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(0, 128, n).astype(np.int32)


# --------------------------------------------------------------------- #
# phase()
# --------------------------------------------------------------------- #
def test_phase_counts_wall_and_cpu_and_leaves_waits_out():
    stats = {}
    with phase(stats, "engine.decode.commit"):
        t = time.perf_counter()
        while time.perf_counter() - t < 0.02:      # on the CPU
            pass
    with phase(stats, "engine.decode.wait"):
        time.sleep(0.03)                             # off the CPU
    busy = stats[WALL_PREFIX + "engine.decode.commit"]
    assert busy >= 0.02
    assert stats[WALL_PREFIX + "engine.decode.wait"] >= 0.03
    # only the non-wait phase adds CPU time, and never more than its wall
    assert 0.0 < stats[CPU_KEY] <= busy


def test_phase_without_stats_is_a_span_only():
    with phase(None, "engine.step", step=3):
        pass
    stats = {"x": 1}
    with phase(stats, "server.retire"):
        with phase(None, "server.write", uid=7):
            pass
    assert set(stats) == {"x", WALL_PREFIX + "server.retire", CPU_KEY}


# --------------------------------------------------------------------- #
# A served workload
# --------------------------------------------------------------------- #
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


@pytest.fixture(scope="module")
def served(setup):
    """Six streamed requests over HTTP on a paged engine (chunk 4, so
    prompts take several chunk steps), with the fused decode path."""
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=4, max_len=64, prefill_chunk=4,
                 use_kernel=True, use_moe_decode=True)
    before = dict(eng.stats)
    out = [None] * 6
    t0 = time.perf_counter()
    with ApiServer(eng) as api:

        def worker(i):
            out[i] = _stream(api, _prompt(5 + 3 * i, i), 6)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        with api.lock:      # every phase so far ran inside [t0, now]
            after = dict(eng.stats)
            wall = time.perf_counter() - t0
        scraped = _get_stats(api)
    return eng, before, after, wall, scraped, out


def test_served_workload_advances_every_counter(served):
    eng, before, after, _, _, out = served
    assert all(o["done"] and len(o["result"]["tokens"]) == 6 for o in out)
    for p in PHASES:
        key = WALL_PREFIX + p
        assert before[key] == 0.0
        assert after[key] > 0.0, key
    for key in ("iterations", "chunk_steps", "steps", CPU_KEY):
        assert after[key] > before[key], key
    assert after["iterations"] >= after["steps"]
    assert after["iterations"] >= after["chunk_steps"]


def test_phases_fit_inside_the_serve(served):
    _, _, after, wall, _, _ = served
    leaves = {p: after[WALL_PREFIX + p] for p in PHASES}
    assert sum(leaves.values()) <= wall
    busy = sum(v for p, v in leaves.items() if not p.endswith(".wait"))
    assert after[CPU_KEY] <= busy


def test_stats_endpoint_carries_every_counter_finite(served):
    eng, _, _, _, scraped, _ = served
    engine = scraped["engine"]
    for key in eng._fresh_stats():
        assert key in engine, key
        assert isinstance(engine[key], (int, float)), key
        assert math.isfinite(engine[key]), key
    assert engine["pool_copies"] == 0       # the engine hands its pool over
    routed = [k for k in engine if k.startswith("experts_routed:l")]
    assert len(routed) == 3                 # one per MoE layer
    assert all(engine[k] > 0 for k in routed)


def test_routed_counters_stay_within_the_possible(served):
    eng, _, after, _, _, _ = served
    for i in range(3):
        n = after[f"experts_routed:l{i}"]
        # at least one expert per step, at most E or B*k per step
        assert after["steps"] <= n <= after["steps"] * min(E, 4 * K)


def test_fresh_stats_has_every_counter(setup):
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=2, max_len=32)
    eng.serve([Request(uid=0, prompt=_prompt(5, 0), max_new_tokens=3)])
    eng.reset_stats()
    for key in ("chunk_steps", "iterations", "pool_copies", CPU_KEY,
                *(WALL_PREFIX + p for p in PHASES),
                *(f"experts_routed:l{i}" for i in range(3))):
        assert eng.stats[key] == 0, key


# --------------------------------------------------------------------- #
# The routed-experts count
# --------------------------------------------------------------------- #
def _spy_route(monkeypatch):
    """Record the ids each MoE layer's own router call returns."""
    seen = []
    for mod in (decode_impl, gmm_impl):
        real = mod.route

        def spy(*a, _real=real, **kw):
            out = _real(*a, **kw)
            seen.append(np.asarray(out[1]))
            return out
        monkeypatch.setattr(mod, "route", spy)
    return seen


def _host_count(ids, live, budgets):
    return len({int(e) for b in np.flatnonzero(live)
                for e in ids[b, :budgets[b]]})


SCENARIOS = {
    # pos per slot (-1 = dead), per-slot per-layer budgets or None
    "all_live": ([3, 5, 2, 7], None),
    "dead_slots": ([3, -1, 6, -1], None),
    "mixed_plan": ([4, 2, -1, 5], [[1, 2, 4], [4, 4, 4], [1, 1, 1],
                                   [2, 1, 3]]),
}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
@pytest.mark.parametrize("path", ["decode", "gmm"])
def test_routed_count_matches_router_ids(setup, monkeypatch, path,
                                         scenario):
    cfg, params = setup
    runner = ModelRunner(cfg, params)
    scfg = runner.cfg_for("base")
    pos, budgets = SCENARIOS[scenario]
    pos = jnp.asarray(pos, jnp.int32)
    b = pos.shape[0]
    caches = models.init_caches(scfg, b, 16)
    tokens = jnp.asarray(_prompt(b, 1))
    opts = ModelOpts(moe_impl="gmm", use_moe_decode_kernel=path == "decode")
    kb = None if budgets is None else jnp.asarray(budgets, jnp.int32)
    seen = _spy_route(monkeypatch)
    # eager: the spy sees the concrete ids each layer routed on
    logits, _, routed = models.decode_fn(params=runner.params, cfg=scfg,
                                         tokens=tokens, pos=pos,
                                         caches=caches, opts=opts,
                                         k_budgets=kb, count_routed=True)
    assert len(seen) == 3 and logits.shape[0] == b
    live = np.asarray(pos) >= 0
    for layer, ids in enumerate(seen):
        bud = (np.full(b, K) if budgets is None
               else np.asarray(budgets)[:, layer])
        assert ids.shape == (b, K)
        assert int(routed[layer]) == _host_count(ids, live, bud), layer
    if budgets is not None:         # the surplus slots would count more
        full = [_host_count(ids, live, np.full(b, K)) for ids in seen]
        assert any(f > int(r) for f, r in zip(full, routed))


def test_runner_keeps_the_count_and_returns_two(setup):
    cfg, params = setup
    eng = Engine(cfg, params, max_batch=4, max_len=32, prefill_chunk=4,
                 use_kernel=True, use_moe_decode=True)
    r, kv = eng.runner, eng.kv
    pos = np.array([3, -1, 5, 2], np.int32)
    for s, p in enumerate(pos):
        if p >= 0:
            assert kv.allocate(s, int(p) + 1)
    tokens = jnp.asarray(_prompt(4, 2))
    out = r.decode(tokens, jnp.asarray(pos), kv.caches, kv.block_tables(),
                   use_kernel=True, kernel_blocks=1, moe_decode=True)
    assert len(out) == 2
    assert r.routed.shape == (3,) and r.routed.dtype == jnp.int32
    eager = models.decode_fn(r.params, r.cfg_for("base"), tokens,
                             jnp.asarray(pos), kv.caches,
                             block_tables=kv.block_tables(),
                             opts=ModelOpts(moe_impl="gmm",
                                            use_paged_kernel=True,
                                            use_moe_decode_kernel=True),
                             kernel_blocks=1, count_routed=True)
    np.testing.assert_array_equal(np.asarray(r.routed),
                                  np.asarray(eager[2]))
    chunk = r.chunk_prefill(jnp.zeros((4, 4), jnp.int32),
                            jnp.full((4, 4), -1, jnp.int32),
                            jnp.zeros(4, jnp.int32), kv.caches,
                            kv.block_tables())
    assert len(chunk) == 2
    # the program is named for the device trace
    assert any(k[1] == "decode" for k in r.compiled_specializations())
    assert r._jit[next(k for k in r._jit if k[1] == "decode")
                  ].__name__ == "decode_step"
    assert r._jit[next(k for k in r._jit if k[1] == "chunk")
                  ].__name__ == "chunk_step"


def test_decode_fn_returns_as_before_unless_asked(setup):
    cfg, params = setup
    caches = models.init_caches(cfg, 2, 16)
    tokens, pos = jnp.zeros(2, jnp.int32), jnp.asarray([0, -1], jnp.int32)
    assert len(models.decode_fn(params, cfg, tokens, pos, caches)) == 2
    split = ModelRunner(cfg, params)
    out = models.decode_fn(split.params, split.cfg_for("base"), tokens, pos,
                           models.init_caches(split.cfg_for("base"), 2, 16),
                           count_routed=True)
    assert len(out) == 3 and out[2].shape == (3,)
    # one live slot at k=4 routes exactly 4 distinct experts per layer
    assert np.all(np.asarray(out[2]) == K)


def test_counting_needs_single_layer_groups(setup):
    cfg, params = setup                      # 3 layers in one group
    caches = models.init_caches(cfg, 2, 16)
    with pytest.raises(ValueError, match="count_routed"):
        models.decode_fn(params, cfg, jnp.zeros(2, jnp.int32),
                         jnp.zeros(2, jnp.int32), caches, count_routed=True)
