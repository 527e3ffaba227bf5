#!/usr/bin/env python3
"""Sweep an open-loop cell's arrival rate on the chip, to find its knee.

    python bench/sweep.py --workload olmoe8.chat --seed <n> \
        --rates 0.6,0.7,0.8 --repeats 2 --seconds 45 [--out sweep.jsonl]

One process builds the cell's engine once, as ``bench/run.py`` does, and
drives one window of ``--seconds`` per rate and repeat through the same
load generator.  Every window sends the same requests -- the mix's sizes,
plans and sampling modes, ``--requests`` of them (default: the highest
rate times ``warmup_s + seconds + 15`` s) -- so the rates differ in
their arrival times alone; repeat ``i`` draws its tokens from seed
``seed + i``.  Between windows the server is left to drain.

Each window prints one JSON line: the requests due in it, how many got a
first token before it closed, the cell's readers' ``ttft_p90_ms``,
``output_tok_s``, ``decode_batch`` and ``preemptions``, and the
inter-token gaps' p50/p95/p99.  The last line is the knee by ``knee()``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import arch as archs  # noqa: E402
from bench import measure, run, traffic  # noqa: E402

#: share of the requests due in a window that must get a first token
#: before it closes
FIRST_SHARE = 0.95
#: a rate's TTFT p90 may be at most this many times the lowest rate's
TTFT_GROWTH = 2.0
#: the cell runs at this share of the knee
CELL_SHARE = 0.8


def window_row(rate: float, repeat: int, r: Dict) -> Dict:
    """What one window of the sweep reads."""
    due = measure.due_in_window(r)
    t1 = r["window"][1]
    first = [x for x in due if x["first"] is not None and x["first"] <= t1]
    gaps = measure.token_gaps(r)

    def gap_ms(q):
        p = measure.pct(gaps, q)
        return None if p is None else 1e3 * p

    row = {"rate": rate, "repeat": repeat, "due": len(due),
           "first_by_close": len(first),
           "share_first_by_close": len(first) / len(due) if due else None,
           "itl_p50_ms": gap_ms(50), "itl_p95_ms": gap_ms(95),
           "itl_p99_ms": gap_ms(99)}
    for name in ("ttft_p90_ms", "output_tok_s", "decode_batch",
                 "preemptions"):
        row[name] = run.reader(name)(r)
    return row


def knee(rows: List[Dict]) -> Optional[Dict]:
    """The highest rate at which, at it and at every lower rate, every
    window gave a first token before its close to ``FIRST_SHARE`` of the
    requests due in it, and the median TTFT p90 stayed within
    ``TTFT_GROWTH`` times the lowest rate's.  None where the lowest rate
    already fails."""
    rates = sorted({x["rate"] for x in rows})
    med = {q: statistics.median(x["ttft_p90_ms"] for x in rows
                                if x["rate"] == q) for q in rates}
    best = None
    for q in rates:
        mine = [x for x in rows if x["rate"] == q]
        ok = (all((x["share_first_by_close"] or 0.0) >= FIRST_SHARE
                  for x in mine)
              and med[q] <= TTFT_GROWTH * med[rates[0]])
        if not ok:
            break
        best = q
    if best is None:
        return None
    return {"knee_rps": best, "cell_rps": CELL_SHARE * best,
            "ttft_p90_ms_by_rate": med}


def _idle(api, timeout_s: float = 120.0) -> None:
    """Wait until the server holds no request."""
    end = time.perf_counter() + timeout_s
    while time.perf_counter() < end:
        s = api.stats()["server"]
        if not (s["live_requests"] or s["queued_requests"]
                or s["pending_arrivals"] or s["open_completions"]):
            return
        time.sleep(0.2)
    raise RuntimeError("the server did not drain between windows")


def sweep(name: str, seed: int, rates: List[float], repeats: int,
          seconds: float, *, requests: Optional[int] = None,
          require_chip: bool = True, root: str = ROOT,
          bench: Optional[Dict] = None, traffic_dir: Optional[str] = None,
          emit=print) -> Dict:
    """Every window's row (each also passed to ``emit``) and the knee."""
    bench = bench or run.load_benchmark(root)
    cell, config, mix = run.find_cell(bench, name, root, traffic_dir)
    if mix["loop"] != "open":
        raise ValueError(f"{name}: the mix is not an open loop")
    n = requests or int(max(rates) * (mix["warmup_s"] + seconds + 15))
    arch = archs.of(config)
    run.open_device(int(cell["chips"]), require_chip, root)
    from repro.serving import ApiServer

    counts = run.CompileCount()
    e = config["engine"]
    eng = run.build_engine(config, run.program_config(config, arch), seed,
                           arch)

    def make(rate: float, s: int) -> List[Dict]:
        return traffic.generate(dict(mix, rate_rps=rate, requests=n), seed=s,
                                vocab_size=config["vocab_size"],
                                max_len=e["max_len"])

    run.warm_up(eng, mix, make(rates[0], seed))
    api = ApiServer(eng)
    api.start()
    print(f"set-up {time.perf_counter() - T_PROCESS:.1f} s, "
          f"{counts.compiles} compiles, {n} requests a window",
          file=sys.stderr, flush=True)
    rows = []
    try:
        for rate in rates:
            for i in range(repeats):
                c0 = counts.compiles
                r = run._drive(api, dict(mix, rate_rps=rate),
                               make(rate, seed + i), seconds, None)
                row = window_row(rate, i, r)
                row["compiles_in_window"] = counts.compiles - c0
                rows.append(row)
                emit(row)
                _idle(api)
    finally:
        api.close()
    return {"rows": rows, "knee": knee(rows)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: BENCHMARK.json's)")
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--out", default=None, help="also append rows here")
    args = ap.parse_args(argv)
    out = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seconds = args.seconds or float(run.load_benchmark()["run_seconds"])
    try:
        res = sweep(args.workload, args.seed,
                    [float(x) for x in args.rates.split(",")], args.repeats,
                    seconds, requests=args.requests, emit=emit)
        emit({"knee": res["knee"]})
    except run.NoChip as e:
        print(f"bench/sweep.py: {e}", file=sys.stderr)
        return 3
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
