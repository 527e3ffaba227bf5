"""HTTP API server launcher: the continuous engine loop behind a port.

    PYTHONPATH=src python -m repro.launch.api_server --arch olmoe-1b-7b \
        --reduced --port 8080

    # then, from any HTTP client:
    curl -s localhost:8080/health
    curl -s localhost:8080/v1/stats
    curl -s -X POST localhost:8080/v1/completions -d \
        '{"prompt": [1, 2, 3], "max_new_tokens": 8}'
    curl -sN -X POST localhost:8080/v1/completions -d \
        '{"prompt": [1, 2, 3], "max_new_tokens": 8, "stream": true}'

One engine, one pump thread, many connections (DESIGN.md §11).  A LExI
plan searched or loaded at startup is registered under the name
``"lexi"`` and selectable per request via ``"plan": "lexi"`` in the
completion body -- the paper's layer-adaptive budget as a per-request
serving knob over one set of weights.

``--smoke`` starts the server in-process, runs one non-streamed and one
streamed completion plus a stats scrape through ``http.client``,
verifies the streamed deltas concatenate to the final text, shuts down
cleanly, and exits -- the CI bench-smoke cell.
"""

from __future__ import annotations

import argparse
import http.client
import json

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import serving_opts
from repro.serving import ApiServer, Engine
from repro.serving.runner import BASE_PLAN, init_serving_params


def build_engine(args) -> Engine:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    # split-layout weights: the engine holds the only copy
    eng = Engine(cfg, init_serving_params(jax.random.PRNGKey(args.seed), cfg),
                 max_batch=args.max_batch, max_len=args.max_len,
                 num_pages=args.num_pages,
                 expert_dtype=args.expert_dtype,
                 prefix_cache=args.prefix_cache,
                 scheduler=args.scheduler,
                 admission=args.admission,
                 opts=serving_opts(args))
    if args.plan is not None:
        from repro.core import LexiPlan
        eng.add_plan("lexi", LexiPlan.load(args.plan))
    elif (args.lexi_budget_frac is not None and cfg.is_moe
          and cfg.moe_top_k > 1):
        from repro.core import optimize
        n = cfg.num_moe_layers
        budget = max(n, int(round(args.lexi_budget_frac * n * cfg.moe_top_k)))
        eng.add_plan("lexi", optimize(eng.runner.params,
                                      eng.runner.cfg_for(BASE_PLAN), budget,
                                      method="dp", n_iter=4, profile_batch=2,
                                      profile_seq=32))
    return eng


def _smoke(api: ApiServer, vocab: int) -> None:
    """One of everything through a real socket; raises on any mismatch."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, 12).tolist()

    conn = http.client.HTTPConnection(api.host, api.port, timeout=60)
    conn.request("GET", "/health")
    assert json.loads(conn.getresponse().read())["ok"] is True

    body = json.dumps({"prompt": prompt, "max_new_tokens": 8})
    conn.request("POST", "/v1/completions", body=body)
    res = json.loads(conn.getresponse().read())
    assert res["finished_reason"] == "length" and len(res["tokens"]) == 8
    print(f"smoke non-streamed: uid={res['uid']} text={res['text']!r}")

    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_new_tokens": 8,
                                  "stream": True}))
    lines = [json.loads(ln) for ln in
             conn.getresponse().read().decode().splitlines()]
    deltas = [ev["delta"] for ev in lines if "delta" in ev]
    final = lines[-1]
    assert final.get("done") and "".join(deltas) == final["result"]["text"]
    # deterministic greedy decode: the streamed run must match the
    # non-streamed one token for token
    assert final["result"]["tokens"] == res["tokens"]
    print(f"smoke streamed: {len(deltas)} deltas, "
          f"text={final['result']['text']!r}")

    conn.request("GET", "/v1/stats")
    stats = json.loads(conn.getresponse().read())
    assert stats["server"]["requests_total"] == 2
    assert stats["server"]["open_completions"] == 0
    print(f"smoke stats: decode_tokens={stats['engine']['decode_tokens']} "
          f"tput={stats['throughput_tok_per_s']:.1f} tok/s")
    conn.close()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--num-pages", type=int, default=None)
    ap.add_argument("--moe-impl", choices=["gmm", "dense"], default="gmm")
    ap.add_argument("--use-kernel", action="store_true",
                    help="Pallas kernels: paged flash-decode and the gmm "
                         "grouped expert matmul")
    ap.add_argument("--use-moe-decode", action="store_true",
                    help="fused routed-expert MoE on decode steps")
    ap.add_argument("--expert-dtype", choices=["bf16", "int8", "int4"],
                    default="bf16")
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--scheduler", choices=["fifo", "sjf"], default="fifo")
    ap.add_argument("--admission", default="headroom")
    ap.add_argument("--lexi-budget-frac", type=float, default=None,
                    help="search a plan at startup; serve it per request "
                         "with plan=lexi")
    ap.add_argument("--plan", default=None,
                    help="path to a saved LexiPlan JSON (registered as lexi)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080,
                    help="0 binds an ephemeral port")
    ap.add_argument("--smoke", action="store_true",
                    help="start, run one streamed + one non-streamed "
                         "completion in-process, shut down, exit")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    eng = build_engine(args)
    vocab = eng.cfg.vocab_size
    with ApiServer(eng, host=args.host, port=args.port,
                   verbose=not args.smoke) as api:
        print(f"serving {eng.cfg.name} at {api.url} "
              f"(plans: {sorted(eng.runner.plans)})")
        if args.smoke:
            _smoke(api, vocab)
            print("smoke ok")
            return 0
        try:
            while True:
                api._http_thread.join(timeout=3600)
        except KeyboardInterrupt:
            print("\nshutting down")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
