#!/usr/bin/env python3
"""Bring-up smoke: serve OLMoE-1B-7B at its published widths on one TPU chip.

    python chip_smoke.py [--seed N]

Drives the production serving path once, through the entry points a user
calls.  An ``Engine`` (paged KV, chunked prefill, on-demand pages, dropless
``gmm`` MoE with every Pallas kernel on) sits behind ``ApiServer`` and
answers 8 completions over a real socket: prompts of 128-1024 tokens drawn
from the seed, 32 new tokens each, half of them on a fixed heterogeneous
LExI plan (so the mixed-plan bucketed-k graphs compile and run), one
streamed.  Then, on the same weights and caches, it compares one decode
step's logits from the kernel path against the path with every kernel off,
for each layer's attention and experts on their own (``LOGIT_RTOL`` says
why), and checks that the
compiled decode and chunk graphs call Mosaic kernels (``tpu_custom_call``).

Every width is OLMoE-1B-7B's; only depth is cut, to 8 of 16 layers (all
layers are of one kind), because all 16 in bf16 (~13.8 GB) leave a 16 GB
chip no room for a KV pool.  Weights are random from ``--seed``.

It refuses to run without a TPU, and exits non-zero if any phase fails.
Every number it prints is a set-up fact of this run, not a benchmark
metric.  The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: the cut: OLMoE-1B-7B's published widths at 8 of its 16 layers
NUM_LAYERS = 8
#: fixed heterogeneous per-layer top-k, registered as "lexi" (no search)
LEXI_PLAN = (8, 6, 4, 4, 4, 4, 6, 8)
#: kernel path vs kernels-off path, one decode step of one layer, same
#: caches, as a fraction of max|logit|.  Both read the same bf16 weights
#: and KV and round at the same points: every dot takes bf16 operands and
#: accumulates in f32, SwiGLU and softmax run in f32, experts combine by
#: an f32 multiply-add, activations are rounded to bf16.  They differ in
#: f32 summation order and in how exp and silu are evaluated, which now
#: and then moves a bf16 rounding (2^-8 of the value) by one ulp; through
#: one layer and the head that stays within a few ulps of the largest
#: logit, and 0.02 allows about five.
#:
#: Top-k routing is not continuous: when two router scores are closer
#: than that noise, the two paths pick different experts and a row's
#: logits move by a sizeable fraction of their range with no kernel at
#: fault (on the chip this happened through all 8 layers, and within a
#: single layer, in a few rows of the 64).  So each layer is checked in
#: two parts, each with the other part's output zeroed exactly: the
#: attention part (experts add 0, so no choice of experts can move the
#: logits) and the experts part (attention adds 0, so both paths route
#: the same embeddings and pick the same experts).  A kernel reading a
#: wrong page or a wrong expert moves a part's logits by far more (the
#: script prints it beside each part's figure).
LOGIT_RTOL = 0.02


def require_tpu():
    """The devices, or exit: this smoke never continues on the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (jax.devices()[0].platform is "
            f"{devs[0].platform!r}); this smoke runs only on the chip")
    return devs


class CompileClock:
    """Sums JAX's tracing, lowering and backend-compile durations."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def build_engine(cfg, *, seed: int, max_batch: int, max_len: int,
                 page_size: int, chunk: int, plan, log=print):
    """The production path: paged, chunked, on-demand, dropless, kernels."""
    import jax

    from repro.models.opts import ModelOpts
    from repro.serving import Engine
    from repro.serving.runner import init_serving_params

    opts = ModelOpts(moe_impl="gmm", use_moe_kernel=True,
                     use_paged_kernel=True, use_moe_decode_kernel=True)
    params = init_serving_params(jax.random.PRNGKey(seed), cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    eng = Engine(cfg, params, max_batch=max_batch, max_len=max_len,
                 page_size=page_size, prefill_chunk=chunk, opts=opts,
                 seed=seed)
    del params      # the runner holds the only reference to the weights
    eng.add_plan("lexi", plan)
    pool = sum(x.nbytes for x in jax.tree.leaves(eng.kv.caches))
    log(f"params: {n_params:,} ({n_bytes / 1e9:.3f} GB); KV pool: "
        f"{eng.kv.num_pages} pages of {page_size} ({pool / 1e9:.3f} GB)")
    return eng, n_bytes, pool


def _post(api, body, timeout):
    conn = http.client.HTTPConnection(api.host, api.port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=json.dumps(body))
        resp = conn.getresponse()
        raw = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {raw[:200]}")
    finally:
        conn.close()
    if not body.get("stream"):
        return json.loads(raw)
    lines = [json.loads(ln) for ln in raw.splitlines()]
    deltas = [ev["delta"] for ev in lines if "delta" in ev]
    final = lines[-1]
    if not final.get("done"):
        raise RuntimeError("stream ended without a done event")
    res = final["result"]
    if "".join(deltas) != res["text"]:
        raise RuntimeError("streamed deltas do not concatenate to the text")
    return res


def serve_over_http(eng, prompts, plans, *, max_new: int, streamed: int,
                    timeout: float = 900.0, log=print):
    """Answer every prompt through ``ApiServer`` over a real socket, all
    in flight at once.  Raises on a failed request or a dead pump."""
    from repro.serving import ApiServer

    results = [None] * len(prompts)
    errors = []

    def client(i):
        body = {"prompt": [int(t) for t in prompts[i]],
                "max_new_tokens": max_new, "stream": i == streamed}
        if plans[i] is not None:
            body["plan"] = plans[i]
        try:
            results[i] = _post(api, body, timeout)
        except Exception as e:      # re-raised in the main thread below
            errors.append((i, e))

    with ApiServer(eng) as api:
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(len(prompts))]
        for th in threads:
            th.start()
        deadline = t0 + timeout
        for th in threads:
            while th.is_alive():
                th.join(timeout=1.0)
                if not api._pump_thread.is_alive():
                    raise RuntimeError("engine pump thread died")
                if time.perf_counter() > deadline:
                    raise TimeoutError("requests still open at the deadline")
        wall = time.perf_counter() - t0
        if errors:
            i, e = errors[0]
            raise RuntimeError(f"request {i} failed: {e!r}") from e
        conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
        conn.request("GET", "/v1/stats")
        stats = json.loads(conn.getresponse().read())
        conn.close()

    for i, res in enumerate(results):
        if res["finished_reason"] != "length" or len(res["tokens"]) != max_new:
            raise RuntimeError(f"request {i}: {res['finished_reason']!r}, "
                               f"{len(res['tokens'])} tokens")
        want = plans[i] or "base"
        if res["served_plan"] != want:
            raise RuntimeError(f"request {i} served on {res['served_plan']!r}"
                               f", asked for {want!r}")
    eng_stats = stats["engine"]
    log(f"served {len(results)} requests ({sum(p is not None for p in plans)}"
        f" on plan lexi, 1 streamed) in {wall:.2f} s wall: prefill_tokens="
        f"{eng_stats['prefill_tokens']} decode_tokens="
        f"{eng_stats['decode_tokens']} mixed_plan_steps="
        f"{eng_stats['mixed_plan_steps']}")
    return results, wall, eng_stats


def fill_to_decode(eng, prompts, plans, *, max_new: int, uid0: int):
    """Admit ``prompts`` straight into the engine and step until every one
    is decoding; returns the live requests and the (tokens, pos) of the
    next decode step, built as ``Engine._decode_step`` builds them."""
    import numpy as np

    from repro.serving import Request
    from repro.serving.scheduler import DECODE

    for i, (p, plan) in enumerate(zip(prompts, plans)):
        eng.submit(Request(uid=uid0 + i, prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new, plan=plan))
    while eng.sched.waiting or len(eng.sched.in_state(DECODE)) < len(prompts):
        eng.step()
        if eng.sched.finished:
            raise RuntimeError("a check request finished before every "
                               "prompt was filled; raise max_new")
    live = eng.sched.in_state(DECODE)
    tokens = np.zeros(eng.max_batch, np.int32)
    pos = np.full(eng.max_batch, -1, np.int32)
    for t in live:
        tokens[t.slot] = eng.slot_last[t.slot]
        pos[t.slot] = eng.slot_pos[t.slot]
        if not eng.kv.allocate_append(t.slot, int(pos[t.slot]) + 1):
            raise RuntimeError("KV pool too small for the check batch")
    return live, tokens, pos


def plan_step_kwargs(runner, live, batch: int):
    """The plan arguments of a decode step over ``live`` on ``runner``, as
    the engine builds them: one plan's graph, or for mixed plans the
    bucketed-k graph with per-slot budgets."""
    import numpy as np

    from repro.serving.runner import BASE_PLAN

    names = {t.served_plan for t in live}
    if len(names) == 1:
        return {"plan": names.pop()}
    ks = runner.plan_ks
    maxk = tuple(max(ks[t.served_plan][l] for t in live)
                 for l in range(len(ks[BASE_PLAN])))
    bucket = runner.bucket_for(maxk)
    budgets = np.tile(np.asarray(bucket, np.int32), (batch, 1))
    for t in live:
        budgets[t.slot] = ks[t.served_plan]
    return {"plan": BASE_PLAN, "bucket": bucket, "k_budgets": budgets}


def layer_view(runner, layer: int, opts):
    """Layer ``layer`` of ``runner``'s model on its own: the embedding,
    that layer's weights, the final norm and the head, with that layer's
    entry of every plan, as a one-layer runner.  ``set_layer_weights``
    swaps edited copies of the layer in (same shapes, same graphs)."""
    from repro.serving.runner import BASE_PLAN, ModelRunner

    group = runner.params["stack"]["groups"][layer]
    one = ModelRunner(runner.base_cfg.with_(num_layers=1),
                      dict(runner.params, stack={"groups": [group]}),
                      opts=opts)
    for name, ks in runner.plan_ks.items():
        if name != BASE_PLAN:
            one.add_plan(name, (ks[layer],))
    return one


def set_layer_weights(one, group):
    one.params = dict(one.params, stack={"groups": [group]})


def _edited(tree, **edits):
    """``tree`` with ``edits[name]`` applied to every leaf called ``name``;
    every other leaf is shared."""
    import jax

    def edit(path, leaf):
        fn = edits.get(getattr(path[-1], "key", None))
        return leaf if fn is None else fn(leaf)

    return jax.tree_util.tree_map_with_path(edit, tree)


def check_decode_logits(eng, prompts, plans, *, max_new: int,
                        rtol: float = LOGIT_RTOL, log=print):
    """One decode step, kernel path vs every kernel off, layer by layer and
    part by part (``LOGIT_RTOL`` says why): a one-layer view of each served
    layer attends that layer's pages of the served caches, once with its
    experts' output zeroed (the attention part) and once with its
    attention output zeroed (the experts part).  Returns the facts; raises
    on a mismatch, or if a wrong page or a wrong expert would pass."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models.opts import ModelOpts

    live, tokens, pos = fill_to_decode(eng, prompts, plans, max_new=max_new,
                                       uid0=10_000)
    runner = eng.runner
    caches, bt = eng.kv.caches, eng.kv.block_tables()
    kb = eng.kv.live_blocks(pos)
    rows = np.asarray(sorted(t.slot for t in live))
    on = {"use_kernel": True, "kernel_blocks": kb, "moe_decode": True}
    off = {"use_kernel": False, "moe_decode": False}
    parts = []
    for layer in range(runner.base_cfg.num_layers):
        kern_run = layer_view(runner, layer, runner.opts)
        ref_run = layer_view(runner, layer, ModelOpts(moe_impl="gmm"))
        step_kw = plan_step_kwargs(kern_run, live, eng.max_batch)

        def logits(run, weights, table, **kw):
            set_layer_weights(run, weights)
            out, _ = run.decode(jnp.asarray(tokens), jnp.asarray(pos),
                                [caches[layer]], table, **step_kw, **kw)
            return np.asarray(out, np.float32)[rows]

        group = runner.params["stack"]["groups"][layer]
        attn = _edited(group, w2=jnp.zeros_like)
        experts = _edited(group, wo=jnp.zeros_like)
        # each part's wrong read: every sequence attends the next slot's
        # pages (block table rows rotated by one); every token's top-k
        # lands on the neighbouring experts (router columns rotated by one)
        wrong = {
            "attention": ("pages", logits(kern_run, attn,
                                          jnp.roll(bt, 1, axis=0), **on)),
            "experts": ("experts", logits(
                kern_run, _edited(experts, router=lambda r: jnp.roll(
                    r, 1, axis=-1)), bt, **on)),
        }
        for part, weights in (("attention", attn), ("experts", experts)):
            kern = logits(kern_run, weights, bt, **on)
            ref = logits(ref_run, weights, bt, **off)
            what, bad = wrong[part]
            tol = rtol * float(np.max(np.abs(ref)))
            err, err_wrong = (float(np.max(np.abs(x - ref)))
                              for x in (kern, bad))
            log(f"logit check, layer {layer} {part} ({len(rows)} slots, "
                f"{'mixed' if 'bucket' in step_kw else step_kw['plan']} "
                f"plan): max|kernel - kernels off| = {err:.5f}, tolerance "
                f"{tol:.5f}; wrong {what} give {err_wrong:.5f}")
            if not (np.all(np.isfinite(kern)) and err <= tol):
                raise RuntimeError(f"layer {layer} {part}: kernel logits "
                                   f"differ from the kernels-off path: "
                                   f"{err} > {tol}")
            if err_wrong <= tol:
                raise RuntimeError(f"layer {layer} {part}: the check cannot "
                                   f"tell wrong {what} apart")
            parts.append({"layer": layer, "part": part, "err": err,
                          "tol": tol, "wrong": what, "err_wrong": err_wrong})
    step_kw = plan_step_kwargs(runner, live, eng.max_batch)
    for t in live:
        eng.cancel(t.req.uid)
    eng.pop_finished()
    return {"parts": parts, "step": (tokens, pos, step_kw, kb)}


def assert_kernels_in_graphs(eng, step, *, chunk: int, log=print):
    """The compiled decode and chunk graphs, lowered with live arguments,
    must call Mosaic kernels: catches a silent interpret or jnp route."""
    import jax.numpy as jnp
    import numpy as np

    tokens, pos, step_kw, kb = step
    runner = eng.runner
    caches, bt = eng.kv.caches, eng.kv.block_tables()
    decode = runner.compiled_text("decode", jnp.asarray(tokens),
                                  jnp.asarray(pos), caches, bt,
                                  use_kernel=True, kernel_blocks=kb,
                                  moe_decode=True, **step_kw)
    b = eng.max_batch
    chunk_txt = runner.compiled_text(
        "chunk_prefill", jnp.zeros((b, chunk), jnp.int32),
        jnp.asarray(np.full((b, chunk), -1, np.int32)),
        jnp.zeros((b,), jnp.int32), caches, bt, **step_kw)
    for name, txt in (("decode", decode), ("chunk", chunk_txt)):
        n = txt.count("tpu_custom_call")
        if not n:
            raise RuntimeError(f"the compiled {name} graph calls no Mosaic "
                               "kernel")
        log(f"{name} graph: {n} tpu_custom_call sites")


def run_smoke(cfg, *, seed: int = 0, max_batch: int = 8, max_len: int = 2048,
              page_size: int = 16, chunk: int = 128, n_requests: int = 8,
              prompt_lens=(128, 1024), max_new: int = 32, plan=LEXI_PLAN,
              log=print):
    """Build the engine, serve over HTTP, check logits.  Returns the
    engine and the facts; raises if any phase fails."""
    import numpy as np

    eng, weight_bytes, pool_bytes = build_engine(
        cfg, seed=seed, max_batch=max_batch, max_len=max_len,
        page_size=page_size, chunk=chunk, plan=plan, log=log)
    peak_built = peak_bytes()
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in rng.integers(lo, hi + 1, n_requests)]
    plans = [("lexi" if i % 2 else None) for i in range(n_requests)]
    results, wall, stats = serve_over_http(eng, prompts, plans,
                                           max_new=max_new, streamed=1,
                                           log=log)
    check = check_decode_logits(eng, prompts, plans, max_new=max_new,
                                log=log)
    return eng, {"weight_bytes": weight_bytes, "pool_bytes": pool_bytes,
                 "peak_after_build": peak_built, "results": results,
                 "serve_wall_s": wall, "stats": stats, **check}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = require_tpu()
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"device: {devs[0].device_kind} x {len(devs)}; compile cache: "
          f"{enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config("olmoe-1b-7b").with_(num_layers=NUM_LAYERS)
    print(f"config: {cfg.name} cut to {cfg.num_layers} of 16 layers; "
          f"d_model={cfg.d_model} heads={cfg.num_heads}x{cfg.head_dim_} "
          f"experts={cfg.num_experts} top-{cfg.moe_top_k} "
          f"moe_d_ff={cfg.moe_d_ff} vocab={cfg.vocab_size} {cfg.dtype}; "
          f"lexi plan {LEXI_PLAN}")
    eng, facts = run_smoke(cfg, seed=args.seed)
    limit = 1.1 * (facts["weight_bytes"] + facts["pool_bytes"])
    print(f"peak device memory after construction: "
          f"{facts['peak_after_build'] / 1e9:.3f} GB (weights + pool + 10% "
          f"= {limit / 1e9:.3f} GB)")
    if facts["peak_after_build"] > limit:
        raise RuntimeError("engine construction holds more than one copy "
                           "of the weights")
    assert_kernels_in_graphs(eng, facts["step"], chunk=eng.prefill_chunk)
    print(f"peak device memory after serving: {peak_bytes() / 1e9:.3f} GB")
    print(f"compiling (set-up): {clock.seconds:.1f} s over {clock.compiles} "
          f"backend compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
