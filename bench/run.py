#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip.  It makes the configuration's weights on the
device from the seed, builds the serving ``Engine`` with every kernel on,
registers the configuration's plans, warms up every program the cell's
window will run, and starts ``ApiServer``.  A load generator in a child
process (``bench/loadgen.py``, no JAX) drives the window over loopback
HTTP with the cell's traffic mix.  Once the window has closed, the
results of a sample of finished greedy requests are compared with the
plain reference (``bench/reference.py``), and the run prints one JSON
line: the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics, read from a profiler trace of part of the window (``--trace 1``).

Everything that belongs to one cell is found by name: the cell and its
metrics in ``BENCHMARK.json``, the configuration in its file, its
architecture in ``bench/arch/<arch>.py``, the traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<name>.py``, each measured kernel's work in
``bench/roofline/kernels/<kernel>.py``.  A run that finds no TPU exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import arch as archs  # noqa: E402
from bench import measure, traffic  # noqa: E402

#: seconds of the window the traced run records, starting this far in
TRACE_SECONDS = 6.0
TRACE_DELAY = 1.0
#: how long the generator waits, after the window, for the first token of
#: a request that was due in it
WAIT_FIRST_S = 60.0


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------- #
# Finding things by name
# --------------------------------------------------------------------------- #


def load_benchmark(root: str = ROOT) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: Dict, name: str, root: str = ROOT,
              traffic_dir: Optional[str] = None):
    """-> (cell, configuration, traffic mix) of workload ``name``."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    tdir = traffic_dir or os.path.join(HERE, "traffic")
    with open(os.path.join(tdir, cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    traffic.validate(mix)
    return cell, config, mix


def metrics_for(bench: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metric entries a run of ``cell`` reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str, metrics_dir: Optional[str] = None):
    """The ``read(run)`` function of metric ``name``."""
    path = os.path.join(metrics_dir or os.path.join(HERE, "metrics"),
                        name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# --------------------------------------------------------------------------- #
# The system under test
# --------------------------------------------------------------------------- #


def program_config(config: Dict, arch):
    """The program's model configuration, checked against the file: every
    key the architecture (``arch``) reads from the program must equal the
    file's."""
    from repro.configs import get_config

    prog = config["program"]
    cfg = get_config(prog["registry"]).with_(**prog.get("overrides", {}))
    if prog.get("split_pattern"):
        # every layer declared apart, as the runner serves them
        from repro.serving.runner import split_pattern
        cfg = cfg.with_(block_pattern=split_pattern(cfg))
    diff = {k: (config.get(k), v) for k, v in arch.program_keys(cfg).items()
            if config.get(k) != v}
    if diff:
        raise ValueError(f"the program's {prog['registry']} differs from "
                         f"the configuration file (file, program): {diff}")
    return cfg


def build_engine(config: Dict, cfg, seed: int, arch):
    """The production path, as the bring-up smoke builds it: paged KV,
    chunked prefill, on-demand pages, dropless gmm MoE, every kernel on."""
    from repro.models.opts import ModelOpts
    from repro.serving import Engine

    from bench.weights import make_weights

    e = config["engine"]
    opts = ModelOpts(moe_impl="gmm", use_moe_kernel=True,
                     use_paged_kernel=True, use_moe_decode_kernel=True)
    params = make_weights(config, seed, arch)
    eng = Engine(cfg, params, max_batch=e["max_batch"], max_len=e["max_len"],
                 page_size=e["page_size"], prefill_chunk=e["prefill_chunk"],
                 num_pages=e.get("num_pages"), opts=opts,
                 expert_dtype=e["expert_dtype"], seed=seed % (1 << 31))
    del params      # the runner holds the only reference to the weights
    for name, ks in config["plans"].items():
        eng.add_plan(name, ks)
    return eng


class Instruments:
    """Host spans and step records the benchmark wraps around the engine
    and the runner on the instance (the program is not edited).

    Each decode and chunk step's call is numbered; its span is
    ``bench.decode.<n>`` / ``bench.chunk.<n>`` and ``decode_calls[n]`` /
    ``chunk_calls[n]`` say what it worked on.  The engine's phases get
    ``bench.engine.*`` spans, so an idle gap on the device can be put down
    to what the host was doing."""

    def __init__(self, eng, api):
        from jax.profiler import TraceAnnotation
        from repro.serving.scheduler import DECODE

        self.decode_calls: List[Dict] = []
        self.chunk_calls: List[Dict] = []
        self._pending_chunk: Optional[Dict] = None
        runner = eng.runner

        def spanned(fn, name):
            def wrapped(*a, **kw):
                with TraceAnnotation(name):
                    return fn(*a, **kw)
            return wrapped

        decode, chunk = runner.decode, runner.chunk_prefill

        def runner_decode(*a, **kw):
            n = len(self.decode_calls)
            live = eng.sched.in_state(DECODE)
            self.decode_calls.append({
                "plans": [t.served_plan for t in live],
                "ctx": [int(eng.slot_pos[t.slot]) + 1 for t in live]})
            with TraceAnnotation(f"bench.decode.{n}"):
                return decode(*a, **kw)

        def runner_chunk(*a, **kw):
            n = len(self.chunk_calls)
            self.chunk_calls.append(self._pending_chunk)
            with TraceAnnotation(f"bench.chunk.{n}"):
                return chunk(*a, **kw)

        step = eng._chunk_prefill_step

        def chunk_step(prefilling):
            c = eng.prefill_chunk
            self._pending_chunk = {
                "plans": [t.served_plan for t in prefilling],
                "starts": [int(t.consumed) for t in prefilling],
                "tokens": [int(min(c, t.fill_len - t.consumed))
                           for t in prefilling]}
            with TraceAnnotation("bench.engine.chunk_step"):
                return step(prefilling)

        runner.decode, runner.chunk_prefill = runner_decode, runner_chunk
        eng._chunk_prefill_step = chunk_step
        eng._decode_step = spanned(eng._decode_step,
                                   "bench.engine.decode_step")
        eng._admit = spanned(eng._admit, "bench.engine.admit")
        eng.step = spanned(eng.step, "bench.engine.step")
        api._retire = spanned(api._retire, "bench.server.retire")


class CompileCount:
    """Counts programs traced and compiled (kept from the bring-up smoke's
    ``CompileClock``): a new shape in the window shows here."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.traces = self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == self.EVENTS[0]:
            self.traces += 1
        elif event == self.EVENTS[1]:
            self.compiles += 1
            self.seconds += duration


# --------------------------------------------------------------------------- #
# Warm-up: every program the window runs, and no other
# --------------------------------------------------------------------------- #


def nb_buckets(lo_pages: int, blocks: int) -> List[int]:
    """The decode walk bounds ``KVCache.live_blocks`` can return when every
    live context spans at least ``lo_pages`` pages: powers of two up to the
    table width, which caps them."""
    out, b = [], 1
    while b < lo_pages:
        b *= 2
    while b < blocks:
        out.append(b)
        b *= 2
    return out + [blocks]


def step_heads(eng, mix: Dict):
    """(plan, bucket) of every step program the mix's plans can run: each
    plan alone, and the bucketed-k program of each mixture of them."""
    from itertools import combinations

    from repro.serving.runner import BASE_PLAN

    plans = sorted({p for p, s in mix["plans"] if s > 0})
    ks = eng.runner.plan_ks
    heads = [(p, None) for p in plans]
    buckets = set()
    for r in range(2, len(plans) + 1):
        for combo in combinations(plans, r):
            maxk = tuple(max(ks[p][l] for p in combo)
                         for l in range(len(ks[BASE_PLAN])))
            buckets.add(eng.runner.bucket_for(maxk))
    heads += [(BASE_PLAN, b) for b in sorted(buckets)]
    return heads


def warm_up(eng, mix: Dict, requests: List[Dict]) -> None:
    """Run every decode ``n_blocks`` bucket and chunk program of every plan
    head the mix reaches, every page-reset width, and the sampler in each
    mode the mix uses; then serve a few short requests of the mix's kinds
    through the engine."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import Request
    from repro.serving.sampling import sample_per_slot

    b, c = eng.max_batch, eng.prefill_chunk
    kv = eng.kv
    caches, bt = kv.caches, kv.block_tables()
    page = kv.page_size
    shortest = min(len(r["prompt"]) for r in requests)
    nbs = nb_buckets(-(-shortest // page), kv.blocks_per_slot)
    for plan, bucket in step_heads(eng, mix):
        kw = {"plan": plan}
        if bucket is not None:
            kw.update(bucket=bucket,
                      k_budgets=np.tile(np.asarray(bucket, np.int32),
                                        (b, 1)))
        for nb in nbs:
            pos = np.full(b, -1, np.int32)
            pos[0] = nb * page - 1
            # each call returns a second pool: drop it before the next
            jax.block_until_ready(eng.runner.decode(
                jnp.zeros(b, jnp.int32), jnp.asarray(pos), caches, bt,
                use_kernel=eng.use_kernel, kernel_blocks=nb,
                moe_decode=eng.use_moe_decode, **kw)[0])
        jax.block_until_ready(eng.runner.chunk_prefill(
            jnp.zeros((b, c), jnp.int32), jnp.full((b, c), -1, jnp.int32),
            jnp.zeros(b, jnp.int32), caches, bt, **kw)[0])
    del caches
    # the sampler's eager programs, per mode the mix's batches can mix
    logits = jnp.zeros((b, eng.runner.base_cfg.padded_vocab), jnp.float32)
    key = jax.random.PRNGKey(0)
    top_ks = sorted({m["top_k"] for m in mix["sampling"]
                     if m["temperature"] > 0 and m["top_k"] > 0})
    temps = np.zeros(b, np.float32)
    jax.block_until_ready(sample_per_slot(logits, key, jnp.asarray(temps)))
    for k in top_ks:
        t = temps.copy()
        t[0] = 1.0
        tk = np.zeros(b, np.int32)
        tk[0] = k
        jax.block_until_ready(sample_per_slot(logits, key, jnp.asarray(t),
                                              jnp.asarray(tk)))
    # releasing a request resets its pages' positions: one eager program
    # per page count
    free = list(range(1, kv.blocks_per_slot + 1))
    for n in range(1, kv.blocks_per_slot + 1):
        kv._reset_pages(free[:n])
    jax.block_until_ready(kv.caches)
    # a short serve through the engine's own loop, one request per kind
    kinds = {(r["plan"], r["temperature"], r["top_k"]) for r in requests}
    rng = np.random.default_rng(0)
    vocab = eng.runner.base_cfg.vocab_size
    for i, (plan, temp, top_k) in enumerate(sorted(kinds)):
        eng.submit(Request(uid=-1 - i, prompt=rng.integers(
            0, vocab, c + 3).astype(np.int32), max_new_tokens=4, plan=plan,
            temperature=temp, top_k=top_k, detok=True))
    eng.drain()
    eng.reset_stats()


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #


def _device(require_chip: bool, chips: int):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}; this benchmark runs only on "
                     "the chip")
    if require_chip and len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs


def open_device(chips: int, require_chip: bool, root: str = ROOT,
                log=None):
    """The devices, past the look for a chip; on the chip, with every
    program kept in the compile cache at one fixed path inside the
    checkout (which the program takes from ``JAX_COMPILATION_CACHE_DIR``)."""
    if require_chip:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                               ".jax_cache")
    devs = _device(require_chip, chips)
    if require_chip:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
        cache = enable_compile_cache()
        jax.config.update("jax_compilation_cache_dir", cache)
        if log:
            log(f"compile cache: {cache}")
        # every program is worth caching: the window must compile nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devs


def _engine_stats(api) -> Dict:
    return dict(api.stats()["engine"])


def _drive(api, mix, requests, seconds, trace_dir):
    """Start the load, let ``warmup_s`` pass, run the window (tracing part
    of it when ``trace_dir`` is set), stop the load.  Returns the window,
    the generator's records and the counters at both ends."""
    import jax

    gen = subprocess.Popen([sys.executable, os.path.join(HERE, "loadgen.py")],
                           stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                           text=True)
    try:
        t_start = time.perf_counter() + 0.2
        spec = {"host": api.host, "port": api.port, "loop": mix["loop"],
                "clients": mix.get("clients", 0), "t_start": t_start,
                "requests": requests}
        gen.stdin.write(json.dumps(spec) + "\n")
        gen.stdin.flush()
        t0 = t_start + float(mix["warmup_s"])
        time.sleep(max(0.0, t0 - time.perf_counter()))
        if gen.poll() is not None:
            raise RuntimeError("the load generator exited early")
        stats0 = _engine_stats(api)
        t1 = t0 + seconds
        if trace_dir is not None:
            time.sleep(max(0.0, t0 + TRACE_DELAY - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(TRACE_SECONDS)
            jax.profiler.stop_trace()
        time.sleep(max(0.0, t1 - time.perf_counter()))
        stats1 = _engine_stats(api)
        wait = WAIT_FIRST_S if mix["loop"] == "open" else 0.0
        gen.stdin.write(json.dumps({"stop": t1, "wait_first_s": wait}) + "\n")
        gen.stdin.flush()
        out, _ = gen.communicate(timeout=wait + 120)
        if gen.returncode != 0:
            raise RuntimeError(f"load generator exited {gen.returncode}")
        records = json.loads(out.strip().splitlines()[-1])
    finally:
        if gen.poll() is None:
            gen.kill()
            gen.wait()
    return {"window": (t0, t1), "records": records, "stats0": stats0,
            "stats1": stats1}


def tally(run: Dict, waited: bool):
    """(attempted, failed) requests of the window: those due in it or in
    flight when it opened.  A request fails on an error of its own; where
    the generator waited for first tokens after the window (``waited``),
    also when it was due in the window and never got one."""
    t0 = run["window"][0]
    attempted = [r for r in run["records"] if measure.in_window(run, r["due"])
                 or (r["sent"] is not None and r["sent"] <= t0
                     and (r["done"] is None or r["done"] >= t0))]
    failed = [r for r in attempted
              if r["status"] not in ("ok", "closed at the window's end")
              or (waited and measure.in_window(run, r["due"])
                  and r["first"] is None)]
    return attempted, failed


def sample_requests(records: List[Dict], requests: List[Dict], n: int,
                    seed: int) -> List[Dict]:
    """Up to ``n`` finished greedy requests: the longest, and the rest
    drawn from the seed."""
    import numpy as np

    by_id = {r["id"]: r for r in requests}
    greedy = sorted((r for r in records if r["status"] == "ok"
                     and by_id[r["id"]]["temperature"] == 0.0),
                    key=lambda r: (-len(r["result"]["tokens"]), r["id"]))
    rest = greedy[1:]
    draw = np.random.default_rng(seed).choice(
        len(rest), min(n - 1, len(rest)), replace=False)
    return greedy[:1] + [rest[i] for i in sorted(draw)]


def check_outputs(config: Dict, seed: int, records: List[Dict],
                  requests: List[Dict], mix: Dict, *, arch,
                  control: bool = False):
    """Compare a sample of finished greedy requests with the reference.

    The sample is drawn from the seed and always holds the longest
    finished greedy request.  The number compared is the mean gap (the
    widest gap of a sound run and of the lower-precision control
    overlap), held to the configuration's ``check.limit``.  Returns the
    numbers compared, each with its limit, and (with ``control``) the
    control's readings on the same tokens."""
    from bench import reference
    from bench.weights import make_weights

    by_id = {r["id"]: r for r in requests}
    done = [r for r in records if r["status"] == "ok"]
    wrong_len = [r["id"] for r in done
                 if len(r["result"]["tokens"]) != by_id[r["id"]]["max_new"]]
    pick = sample_requests(records, requests, int(mix["check_requests"]),
                           seed)
    model, e = config, config["engine"]
    weights = make_weights(model, seed, arch)
    served, ctl_gaps = [], []
    ctl = config["check"]["control"] if control else {}
    base = [model["num_experts_per_tok"]] * len(arch.moe_layers(model))
    for r in pick:
        res = r["result"]
        ks = config["plans"].get(res["served_plan"], base)
        g = reference.served_gaps(
            weights, model, by_id[r["id"]]["prompt"], res["tokens"], ks,
            expert_dtype=e["expert_dtype"],
            control_dense=ctl.get("dense"),
            control_experts=ctl.get("experts"), arch=arch)
        served.append(g["served"])
        if control:
            ctl_gaps.append(g["control"])
    del weights
    read = readings(served)
    chk = config["check"]
    compared = {
        "mean_gap": {"value": read["mean_gap"], "limit": chk["limit"]},
        "tokens_compared": {"value": read["tokens"],
                            "limit": chk["min_tokens"]},
        "wrong_length": {"value": len(wrong_len), "limit": 0},
    }
    ok = (len(pick) > 0 and read["mean_gap"] <= chk["limit"]
          and read["tokens"] >= chk["min_tokens"] and not wrong_len)
    return ok, compared, {"served": read,
                          "control": readings(ctl_gaps) if control else None}


def readings(gaps: List) -> Dict:
    """What the comparison can read from per-token gaps: the widest gap,
    the share of tokens that are not the reference's best, the mean gap."""
    import numpy as np

    g = np.concatenate(gaps) if gaps else np.zeros(0)
    if not g.size:          # nothing compared: fails on the token count
        return {"max_gap": 0.0, "flip_share": 0.0, "mean_gap": 0.0,
                "tokens": 0}
    return {"max_gap": float(g.max()), "flip_share": float(np.mean(g > 0)),
            "mean_gap": float(g.mean()), "tokens": int(g.size)}


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, root: str = ROOT,
             bench: Optional[Dict] = None, traffic_dir: Optional[str] = None,
             arch_dir: Optional[str] = None, control: bool = False,
             save_trace: Optional[str] = None, log=None) -> Dict:
    """One run of cell ``name``; returns the result line's object."""
    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    bench = bench or load_benchmark(root)
    cell, config, mix = find_cell(bench, name, root, traffic_dir)
    arch = archs.of(config, arch_dir)
    devs = open_device(int(cell["chips"]), require_chip, root, log)
    import jax

    from bench.roofline import work
    from repro.serving import ApiServer

    peaks = work.peaks(devs[0].device_kind) if require_chip else None
    counts = CompileCount()
    cfg = program_config(config, arch)
    eng = build_engine(config, cfg, seed, arch)
    e = config["engine"]
    requests = traffic.generate(mix, seed=seed,
                                vocab_size=config["vocab_size"],
                                max_len=e["max_len"])
    warm_up(eng, mix, requests)
    api = ApiServer(eng)
    instr = Instruments(eng, api)
    api.start()
    setup_s = time.perf_counter() - T_PROCESS
    log(f"set-up {setup_s:.3f} s: {counts.compiles} compiles "
        f"({counts.seconds:.3f} s), {counts.traces} traces")
    traces0, compiles0 = counts.traces, counts.compiles
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        run = _drive(api, mix, requests, seconds, trace_dir)
        in_window = (counts.traces - traces0, counts.compiles - compiles0)
        api.close()
        stats = jax.devices()[0].memory_stats() or {}
        peak = int(stats.get("peak_bytes_in_use", 0))
        plan_ks = dict(eng.runner.plan_ks)
        del api, eng
        red = None
        if trace:
            from bench import trace as tr
            red = tr.load(trace_dir)
            if save_trace:
                import gzip
                with gzip.open(save_trace, "wt") as f:
                    json.dump({"reduced": red, "run": {
                        k: run[k] for k in ("window",)},
                        "decode_calls": instr.decode_calls,
                        "chunk_calls": instr.chunk_calls,
                        "plan_ks": plan_ks}, f)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    gc.collect()
    log(f"device memory: peak {peak} bytes; in use once the engine is freed:"
        f" {(jax.devices()[0].memory_stats() or {}).get('bytes_in_use')}")
    run.update(setup_s=setup_s, trace=red, plan_ks=plan_ks,
               decode_calls=instr.decode_calls,
               chunk_calls=instr.chunk_calls, model=config, arch=arch,
               expert_dtype=e["expert_dtype"], peaks=peaks)
    log(f"window {seconds} s: {len(measure.token_gaps(run))} token gaps, "
        f"{len(measure.due_in_window(run))} requests due, "
        f"{in_window[0]} traces and {in_window[1]} compiles inside")
    late = [r["sent"] - r["due"] for r in run["records"]
            if r["sent"] is not None and measure.in_window(run, r["due"])]
    if late:
        log(f"generator lateness: median {measure.pct(late, 50):.6f} s, "
            f"max {max(late):.6f} s over {len(late)} sends")
    attempted, failed = tally(run, waited=mix["loop"] == "open")
    for r in failed[:5]:
        log(f"request {r['id']} failed: {r['status']}")

    metrics = {}
    for m in metrics_for(bench, name, trace):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": False, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if trace:
        from bench import trace as tr
        t0, t1 = tr.window(red)
        device["busy_s"] = tr.busy_ns(red, t0, t1) * 1e-9
        device["window_s"] = (t1 - t0) * 1e-9
        out["breakdown"] = {"device_ops": tr.top_ops(red, t0, t1),
                            "idle_gaps": tr.idle_gaps(red, t0, t1)}
    t0, t1 = run["window"]
    due = measure.due_in_window(run)
    out["info"] = {
        "output_tok_s": len(measure.token_times(run)) / (t1 - t0),
        "requests_due": len(due),
        "backlog_at_close": sum(1 for r in due
                                if r["first"] is None or r["first"] > t1),
        "token_gaps": len(measure.token_gaps(run)),
        "itl_ms": {f"p{q}": 1e3 * (measure.pct(measure.token_gaps(run), q)
                                   or 0.0) for q in (50, 90, 95, 99)},
        "ttft_ms": {f"p{q}": 1e3 * (measure.pct(
            [r["first"] - r["due"] for r in due if r["first"] is not None],
            q) or 0.0) for q in (50, 75, 90)},
        "compiles_in_window": in_window[1], "traces_in_window": in_window[0],
    }
    ok, compared, read = check_outputs(config, seed, run["records"],
                                       requests, mix, control=control,
                                       arch=arch)
    out["correct"] = bool(ok and not failed)
    out["readings"] = read
    out["compared"] = compared
    for key, c in compared.items():
        log(f"compared {key}: {c['value']!r} (limit {c['limit']!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the lower-precision control's gap on the "
                         "same tokens (limit setting; not a benchmark run)")
    ap.add_argument("--save-trace", default=None,
                    help="write the reduced trace to this .json.gz")
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), control=args.control,
                       save_trace=args.save_trace)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
