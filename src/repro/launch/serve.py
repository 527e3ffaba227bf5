"""Serving launcher: batched generation with an optional LExI plan.

    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --reduced \
        --requests 16 --max-new 32 --lexi-budget-frac 0.5 --save-plan plan.json

    # reuse a searched plan without re-running the optimizer
    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --reduced \
        --requests 16 --plan plan.json

    # pressure-adaptive degradation: declare the ladder (expensive ->
    # cheap) and let admissions under pool/queue pressure walk requests
    # one rung down at the prefill boundary (DESIGN.md §10)
    PYTHONPATH=src python -m repro.launch.serve --arch olmoe-1b-7b --reduced \
        --requests 16 --max-batch 2 --lexi-budget-frac 0.5 \
        --plan-ladder base,lexi --degrade-under-pressure

Baseline and plan are served from ONE engine (one runner, one set of
weights): the plan is registered as a named specialization and selected
per workload, which is the paper's deployment story end to end.  The
plan is a *per-request* attribute (``Request.plan``) -- ``serve(plan=)``
just stamps it on the wave -- so heterogeneous-plan batches share a
step through the bucketed-k graphs, and the report breaks requests and
decode tokens down per served plan.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.opts import ModelOpts
from repro.serving import Engine, Request
from repro.serving.runner import BASE_PLAN, init_serving_params


def serving_opts(args) -> ModelOpts:
    """The MoE path the launchers serve: ``--moe-impl`` (dropless ``gmm``
    by default, never the capacity-dropping ``dense`` by accident) with
    the Pallas kernels ``--use-kernel`` / ``--use-moe-decode`` turn on."""
    return ModelOpts(moe_impl=args.moe_impl, use_moe_kernel=args.use_kernel,
                     use_paged_kernel=args.use_kernel,
                     use_moe_decode_kernel=args.use_moe_decode)


def synth_requests(n: int, vocab: int, *, lo: int = 8, hi: int = 48,
                   max_new: int = 32, seed: int = 0, temperature: float = 0.0,
                   top_k: int = 0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, vocab, rng.integers(lo, hi)).astype(np.int32),
                    max_new_tokens=max_new, temperature=temperature,
                    top_k=top_k)
            for i in range(n)]


def _report(tag: str, eng: Engine) -> float:
    tput = eng.throughput()
    s = eng.stats
    pre = (f"preempt={s['preemptions']} recompute={s['recompute_tokens']} "
           if s.get("preemptions") else "")
    if s.get("prefix_hit_tokens"):
        pre += (f"prefix_hit={s['prefix_hit_tokens']} "
                f"({s['prefix_hit_rate']:.0%}) cow={s['cow_copies']} ")
    print(f"{tag}: {tput:,.1f} tok/s  "
          f"(prefill={s['prefill_tokens']} decode={s['decode_tokens']} "
          f"steps={s['steps']} {pre}"
          f"ttft_p50={s.get('ttft_p50_s', float('nan')) * 1e3:.0f}ms "
          f"ttft_p95={s.get('ttft_p95_s', float('nan')) * 1e3:.0f}ms "
          f"decode_tps_p50={s.get('decode_tps_p50', float('nan')):.1f})")
    # per-plan breakdown, straight off the flat stats counters
    per_plan = eng.plan_stats()
    if len(per_plan) > 1 or s.get("plan_degradations"):
        for name, d in sorted(per_plan.items()):
            print(f"  plan {name:<10} requests="
                  f"{int(d.get('plan_requests', 0)):3d}  decode_tokens="
                  f"{int(d.get('plan_decode_tokens', 0))}")
        if s.get("mixed_plan_steps"):
            print(f"  mixed-plan steps (bucketed-k graphs): "
                  f"{int(s['mixed_plan_steps'])}")
        if s.get("plan_degradations"):
            print(f"  plan degradations: {int(s['plan_degradations'])} "
                  f"(rung moves, always at the prefill boundary)")
    return tput


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--cache-layout", choices=["paged", "contiguous"],
                    default=None)
    ap.add_argument("--num-pages", type=int, default=None,
                    help="KV pool size in pages (default: worst-case "
                         "max_batch x max_len; smaller pools admit on "
                         "demand and preempt under pressure)")
    ap.add_argument("--preemption", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="on-demand page allocation + preempt-and-recompute "
                         "(default: on for the paged layout); "
                         "--no-preemption reserves prompt+max_new pages for "
                         "a request's whole lifetime at admission")
    ap.add_argument("--moe-impl", choices=["gmm", "dense"], default="gmm",
                    help="MoE dispatch: dropless sort-based gmm, or the "
                         "capacity-buffer dense reference (drops tokens "
                         "past capacity)")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas kernels: paged decode attends pages "
                         "in-kernel (block-table-native flash-decode) "
                         "instead of gathering, and gmm runs the grouped "
                         "expert matmul kernel")
    ap.add_argument("--use-moe-decode", action="store_true",
                    help="decode steps run MoE through the fused "
                         "routed-expert path (no sort plan) instead of the "
                         "gmm dispatch")
    ap.add_argument("--expert-dtype", choices=["bf16", "int8", "int4"],
                    default="bf16",
                    help="storage dtype for routed expert tiles; int8/int4 "
                         "quantize at load and dequantize in-kernel "
                         "(gmm/decode MoE impls only)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="hash-cons full KV pages so requests sharing a "
                         "prompt prefix reuse already-computed pages "
                         "(refcounted, copy-on-write at the boundary; "
                         "paged layout + preemption only)")
    ap.add_argument("--router-lookahead", action="store_true",
                    help="decode steps predict each layer's expert ids from "
                         "the previous layer's hidden state and stage "
                         "weight loads early (numerically exact)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="per-request top-k sampling cap (0 = no cap; only "
                         "matters with a temperature > 0)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--scheduler", choices=["fifo", "sjf"], default="fifo")
    ap.add_argument("--admission", default="headroom",
                    help="admission gate for on-demand paged pools: headroom "
                         "(1 free page per decoding slot), watermark (static "
                         "free-page reserve), lookahead (exact pages decoding "
                         "slots claim within the next page worth of steps), "
                         "or greedy (no gate; thrash baseline)")
    ap.add_argument("--open-loop-rate", type=float, default=0.0,
                    help="offered load in requests/s: requests arrive on a "
                         "Poisson process at this rate instead of all at "
                         "t=0, and the engine admits them mid-flight "
                         "(0 = closed loop). Reported tok/s then includes "
                         "arrival gaps -- it is goodput, not capacity")
    ap.add_argument("--lexi-budget-frac", type=float, default=None,
                    help="search a plan inline at this active-expert budget")
    ap.add_argument("--plan", default=None,
                    help="path to a saved LexiPlan JSON to serve")
    ap.add_argument("--save-plan", default=None,
                    help="write the searched plan here for later --plan runs")
    ap.add_argument("--plan-ladder", default=None, metavar="NAME,NAME,...",
                    help="degradation ladder over registered plans, most "
                         "expensive rung first (e.g. base,lexi with "
                         "--lexi-budget-frac or --plan); adds a ladder "
                         "serve where every request asks for base but "
                         "admissions under KV-pool/queue pressure move "
                         "non-priority requests one rung down, always at "
                         "the prefill boundary (DESIGN.md §10)")
    ap.add_argument("--degrade-under-pressure", action="store_true",
                    help="enable the ladder policy (without it the ladder "
                         "is declared but inert)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    req_kw = dict(max_new=args.max_new, seed=args.seed,
                  temperature=args.temperature, top_k=args.top_k)
    reqs = synth_requests(args.requests, cfg.vocab_size, **req_kw)

    # weights made in the runner's split layout: the engine holds the
    # only copy (no regrouped duplicate, no launcher-held original)
    eng = Engine(cfg, init_serving_params(jax.random.PRNGKey(args.seed), cfg),
                 max_batch=args.max_batch, max_len=args.max_len,
                 prefill_chunk=args.prefill_chunk,
                 cache_layout=args.cache_layout,
                 num_pages=args.num_pages,
                 preemption=args.preemption,
                 expert_dtype=args.expert_dtype,
                 router_lookahead=args.router_lookahead or None,
                 prefix_cache=args.prefix_cache,
                 scheduler=args.scheduler,
                 admission=args.admission,
                 degrade_under_pressure=args.degrade_under_pressure,
                 opts=serving_opts(args))

    def arrivals():
        if args.open_loop_rate <= 0:
            return None
        rng = np.random.default_rng(args.seed + 1)
        return list(np.cumsum(rng.exponential(1.0 / args.open_loop_rate,
                                              args.requests)))

    serve_kw = {}
    arr = arrivals()
    if arr is not None:
        serve_kw["arrival_times"] = arr
        print(f"open loop: Poisson arrivals at {args.open_loop_rate:g} "
              f"req/s over {arr[-1]:.2f}s")

    print(f"arch={cfg.name} baseline top-k={cfg.moe_top_k or 'n/a'} "
          f"layout={eng.kv.layout} chunk={eng.prefill_chunk or 'whole'} "
          f"moe={args.moe_impl} experts={args.expert_dtype}")
    eng.serve(reqs, **serve_kw)
    tput = _report("baseline", eng)

    plan = None
    if args.plan is not None:
        from repro.core import LexiPlan
        plan = LexiPlan.load(args.plan)
    elif (args.lexi_budget_frac is not None and cfg.is_moe
          and cfg.moe_top_k > 1):
        from repro.core import optimize
        n = cfg.num_moe_layers
        budget = max(n, int(round(args.lexi_budget_frac * n * cfg.moe_top_k)))
        plan = optimize(eng.runner.params, eng.runner.cfg_for(BASE_PLAN),
                        budget, method="dp", n_iter=4, profile_batch=2,
                        profile_seq=32)
        if args.save_plan:
            plan.save(args.save_plan)
            print(f"saved plan -> {args.save_plan}")

    if plan is not None:
        eng.add_plan("lexi", plan)      # same runner, same weights
        print(f"LExI plan (B={plan.budget}): {plan.plan}")
        reqs = synth_requests(args.requests, cfg.vocab_size, **req_kw)
        eng.serve(reqs, plan="lexi", **serve_kw)
        tput2 = _report("LExI", eng)
        print(f"speedup: {tput2 / tput:.2f}x at "
              f"{plan.active_fraction():.0%} active experts")

    if args.plan_ladder:
        ladder = args.plan_ladder.split(",")
        eng.set_plan_ladder(ladder)     # raises on unregistered names
        reqs = synth_requests(args.requests, cfg.vocab_size, **req_kw)
        eng.serve(reqs, **serve_kw)     # every request asks for base
        _report(f"ladder {'->'.join(ladder)}"
                + ("" if args.degrade_under_pressure else " (inert)"), eng)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
