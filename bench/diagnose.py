#!/usr/bin/env python3
"""A run of one cell that also keeps what its result line sums up (not a
benchmark run: for finding where a metric's spread comes from, and, with
``--control``, for the readings the limit of ``correct`` is set from).

    python3 bench/diagnose.py run --workload <cell> --seed <n> --seconds <s>
        --out <file.json.gz> [--trace 1] [--control] [--steps]
        [--extra-control <dense>,<experts>]
    python3 bench/diagnose.py summary <file.json.gz> ...

``run`` goes through ``run.run_cell`` as ``bench/run.py`` does and
prints the same result line.  It also writes every request's record
(token times included), every per-token gap the comparison read, and
with ``--steps`` every engine step's start, end and whether it ran a
prefill chunk (a timer around ``Engine.step``, off by default).
``--extra-control`` reads one more lower-precision control on the same
tokens (e.g. ``fp8,fp8``).  ``summary`` prints, per file, the window's
output rate, gap percentiles and, where recorded, the share of engine
steps that ran a chunk and the decode-only step times.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import measure, reference, run  # noqa: E402


def _run(args) -> int:
    import numpy as np

    kept = {"run": None, "steps": [],
            "gaps": {"served": [], "control": [], "extra": []}}
    drive = run._drive

    def keep_drive(*a, **kw):
        kept["run"] = drive(*a, **kw)
        return kept["run"]

    run._drive = keep_drive
    if args.steps:
        init = run.Instruments.__init__

        def timed_init(self, eng, api):
            init(self, eng, api)
            step = eng.step

            def timed():
                t = time.perf_counter()
                nc = len(self.chunk_calls)
                out = step()
                kept["steps"].append((t, time.perf_counter(),
                                      len(self.chunk_calls) - nc))
                return out
            eng.step = timed
        run.Instruments.__init__ = timed_init
    served_gaps = reference.served_gaps

    def keep_gaps(weights, model, prompt, served, ks, **kw):
        g = served_gaps(weights, model, prompt, served, ks, **kw)
        kept["gaps"]["served"].append(g["served"].tolist())
        if "control" in g:
            kept["gaps"]["control"].append(g["control"].tolist())
            if args.extra_control:
                dense, experts = args.extra_control.split(",")
                x = served_gaps(weights, model, prompt, served, ks,
                                expert_dtype=kw.get("expert_dtype"),
                                control_dense=dense, control_experts=experts,
                                arch=kw["arch"])
                kept["gaps"]["extra"].append(x["control"].tolist())
        return g

    reference.served_gaps = keep_gaps
    out = run.run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control)
    if kept["gaps"]["extra"]:
        out["readings"]["extra"] = run.readings(
            [np.asarray(g) for g in kept["gaps"]["extra"]])
    r = kept["run"]
    with gzip.open(args.out, "wt") as f:
        json.dump({"out": out, "window": r["window"], "gaps": kept["gaps"],
                   "steps": kept["steps"], "records": r["records"]}, f)
    print(json.dumps(out), flush=True)
    return 0


def _summary(paths) -> int:
    for p in paths:
        with gzip.open(p, "rt") as f:
            d = json.load(f)
        run_ = {"window": tuple(d["window"]), "records": d["records"]}
        t0, t1 = run_["window"]
        gaps = [1e3 * g for g in measure.token_gaps(run_)]
        line = (f"{os.path.basename(p)}: "
                f"{len(measure.token_times(run_)) / (t1 - t0):.2f} tokens/s; "
                + " ".join(f"p{q} {measure.pct(gaps, q):.1f}"
                           for q in (50, 90, 95, 99)) + " ms")
        steps = [s for s in d["steps"] if t0 <= s[0] <= t1]
        if steps:
            dec = [1e3 * (b - a) for a, b, c in steps if c == 0]
            line += (f"; {len(steps)} steps, "
                     f"{sum(c > 0 for *_, c in steps) / len(steps):.2%} ran "
                     f"a chunk; decode-only p50 {measure.pct(dec, 50):.1f} "
                     f"ms, p99 {measure.pct(dec, 99):.1f} ms")
        print(line)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="what", required=True)
    r = sub.add_parser("run")
    r.add_argument("--workload", required=True)
    r.add_argument("--seed", type=int, required=True)
    r.add_argument("--seconds", type=float, required=True)
    r.add_argument("--out", required=True)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--control", action="store_true")
    r.add_argument("--steps", action="store_true")
    r.add_argument("--extra-control", default=None)
    s = sub.add_parser("summary")
    s.add_argument("paths", nargs="+")
    args = ap.parse_args(argv)
    return _run(args) if args.what == "run" else _summary(args.paths)


if __name__ == "__main__":
    raise SystemExit(main())
