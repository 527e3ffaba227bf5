"""The trace reduction: busy union, idle share, per-program and per-kernel
time, on a trace recorded on a TPU v5e and on a hand-made one whose event
names have the form the chip gives them (``XLA Ops`` events named by their
HLO instruction)."""

import gzip
import json
import os

import numpy as np
import pytest

from bench import arch, measure, run
from bench import trace as tr
from bench.roofline import work

#: 12 decode steps of OLMoE-1B-7B (8 layers, B=16 on the lexi plan) traced
#: on one TPU v5e, reduced by ``trace.load``; the window spans the 12
#: ``bench.step`` host spans of the script that drove them
RECORDED = os.path.join(run.HERE, "testdata", "probe_decode_trace.json.gz")

MOE = ("%moe_decode.3 = bf16[16,2048]{1,0} custom-call(s32[16,8]{1,0} "
       "%a, f32[16,8]{1,0} %b), custom_call_target=\"tpu_custom_call\"")
ATT = ("%flash_decode_paged.1 = bf16[16,16,128]{2,1,0} custom-call("
       "bf16[16,16,128]{2,1,0} %q)")
GMM = "%moe_gmm.2 = bf16[2048,2048]{1,0} custom-call(bf16[2048,2048] %x)"
FUS = "%fusion.7 = bf16[16,2048]{1,0} fusion(bf16[16,2048]{1,0} %y)"


def _hand():
    # window 0-100; decode program 10-40 (attention 10-15, experts 15-35,
    # a fusion 35-40 overlapping nothing); chunk program 60-90 (gmm
    # 60-80); a sampler program 95-97 with one op
    return {
        "ops": [[ATT, 10, 5], [MOE, 15, 20], [FUS, 35, 5], [GMM, 60, 20],
                ["%argmax.1 = s32[16] reduce(f32[16,50304] %l)", 95, 2]],
        "modules": [["jit__lambda(1)", 10, 30], ["jit__lambda(2)", 60, 30],
                    ["jit__argmax(3)", 95, 2]],
        "host": [["bench.window", 0, 100], ["bench.engine.step", 1, 50],
                 ["bench.decode.0", 5, 3], ["bench.engine.step", 52, 45],
                 ["bench.chunk.0", 55, 2]],
    }


def test_union_and_busy():
    assert tr.union([["a", 0, 10], ["b", 5, 10], ["c", 20, 1],
                     ["z", 30, 0]]) == [(0, 15), (20, 21)]
    red = _hand()
    assert tr.window(red) == (0, 100)
    assert tr.busy_ns(red, 0, 100) == 5 + 20 + 5 + 20 + 2
    assert tr.busy_ns(red, 20, 70) == 15 + 5 + 10


def test_ops_parse():
    op = tr.parse_op(MOE)
    assert op["base"] == "moe_decode" and op["opcode"] == "custom-call"
    assert op["args"].startswith("s32[16,8]")
    assert tr.kernel_of(MOE) == "moe_decode"
    assert tr.kernel_of(GMM) == "moe_gmm"
    assert tr.kernel_of(FUS) is None
    assert tr.base_name(FUS) == "fusion"


def test_steps_by_their_kernels():
    red = _hand()
    steps = tr.steps(red, 0, 100)
    assert [(s["kind"], s["dur"], s["span"]) for s in steps] == [
        ("decode", 30, "bench.decode.0"), ("chunk", 30, "bench.chunk.0")]
    assert [c[0] for c in steps[0]["kernels"]] == ["flash_decode_paged",
                                                   "moe_decode"]


def test_breakdown():
    red = _hand()
    top = tr.top_ops(red, 0, 100)
    assert top[0][0] in ("moe_decode", "moe_gmm")
    assert dict(top)["fusion"] == pytest.approx(5e-9)
    gaps = dict(tr.idle_gaps(red, 0, 100))
    # idle: 0-10 and 40-50 inside step 1 (but 5-8 is the decode call),
    # 50-52 between steps, 52-60 and 80-95, 97-100 inside step 2
    assert sum(gaps.values()) == pytest.approx(48e-9)
    assert gaps["bench.engine.step"] > 0


def test_roofline_and_mfu_join_steps_to_their_calls():
    # one decode step of a one-layer model, 16 live slots at k=8 with 100
    # positions each: the kernel needs 64 tiles in 2 ms, attention the KV
    red = {"ops": [[ATT, 1e6, 0.5e6], [MOE, 1.5e6, 2e6]],
           "modules": [["jit__lambda(1)", 1e6, 3e6]],
           "host": [["bench.window", 0, 10e6], ["bench.decode.0", 0.5e6, 1e5]]}
    model = {"arch": "olmoe", "num_hidden_layers": 1,
             "hidden_size": 2048, "intermediate_size": 1024,
             "num_attention_heads": 16, "num_key_value_heads": 16,
             "head_dim": 128, "num_experts": 64, "vocab_size": 50304}
    run = {"trace": red, "decode_calls": [{"plans": ["base"] * 16,
                                           "ctx": [100] * 16}],
           "chunk_calls": [], "plan_ks": {"base": [8]}, "model": model,
           "arch": arch.of(model),
           "expert_dtype": "bf16", "peaks": work.peaks("TPU v5 lite")}
    assert measure.mean_step_ms(run, "decode") == pytest.approx(3.0)
    need = 64 * 3 * 2048 * 1024 * 2 + 2 * 16 * 2048 * 2
    assert measure.roofline_pct(run, "moe_decode") == pytest.approx(
        100 * need / 819e9 / 2e-3)
    assert measure.roofline_pct(run, "moe_gmm") is None
    flops = 16 * arch.load("olmoe").model_flops(model, [8], 100)
    assert measure.mfu_pct(run, "decode") == pytest.approx(
        100 * flops / (10e-3 * 197e12))


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_busy_against_a_time_grid(recorded):
    # the union of op intervals against 100 ns bins, marked op by op
    t0, t1 = tr.window(recorded)
    grid = np.zeros(int((t1 - t0) // 100) + 1, bool)
    for _, s, d in recorded["ops"]:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            grid[int((a - t0) // 100):int(np.ceil((b - t0) / 100))] = True
    busy = tr.busy_ns(recorded, t0, t1)
    assert busy == pytest.approx(grid.sum() * 100, rel=0.01)
    idle = run.reader("idle_share")({"trace": recorded})
    assert idle == pytest.approx(100 * (1 - busy / (t1 - t0)))
    assert 5 < idle < 40


def test_recorded_steps_and_kernels(recorded):
    t0, t1 = tr.window(recorded)
    steps = tr.steps(recorded, t0, t1)
    assert [s["kind"] for s in steps] == ["decode"] * 12
    for s in steps:
        kernels = [c[0] for c in s["kernels"]]
        assert kernels.count("moe_decode") == 8
        assert kernels.count("flash_decode_paged") == 8
    # per-program time: the decode programs' own XLA Modules events
    lam = [m for m in tr.clip(recorded["modules"], t0, t1)
           if m[0].startswith("jit__lambda")]
    assert [s["dur"] for s in steps] == [m[2] for m in lam]
    # per-kernel time: every op event of the kernel, found by its name
    moe = sum(o[2] for o in tr.clip(recorded["ops"], t0, t1)
              if o[0].startswith("%moe_decode"))
    assert sum(c[2] for s in steps for c in s["kernels"]
               if c[0] == "moe_decode") == pytest.approx(moe)
    top = dict(tr.top_ops(recorded, t0, t1))
    assert top["moe_decode"] == pytest.approx(moe * 1e-9)
    assert all(tr.parse_op(o[0]) for o in recorded["ops"])


#: 0.45 s of a traced run of olmoe8-int8.decode (int8 expert tiles, base
#: plan, B=16) on one TPU v5e, reduced by ``trace.load``: 8 decode and 3
#: chunk steps with the benchmark's own host spans and each step's record
HARNESS = os.path.join(run.HERE, "testdata", "int8_decode_window.json.gz")


@pytest.fixture(scope="module")
def harness():
    with gzip.open(HARNESS, "rt") as f:
        d = json.load(f)
    with open(os.path.join(run.ROOT, "bench", "configs",
                           "olmoe-1b-7b-l8-int8.json")) as f:
        model = json.load(f)
    return {"trace": d["reduced"],
            "decode_calls": {int(k): v for k, v in d["decode_calls"].items()},
            "chunk_calls": {int(k): v for k, v in d["chunk_calls"].items()},
            "plan_ks": d["plan_ks"], "model": model, "arch": arch.of(model),
            "expert_dtype": "int8",
            "peaks": work.peaks("TPU v5 lite")}


def test_harness_trace_joins_every_step_to_its_call(harness):
    t0, t1 = tr.window(harness["trace"])
    steps = tr.steps(harness["trace"], t0, t1)
    assert sorted(s["kind"] for s in steps) == ["chunk"] * 3 + ["decode"] * 8
    assert all(s["span"] is not None for s in steps)
    layers = len(harness["plan_ks"]["base"])
    assert len(measure.kernel_work(harness, "moe_decode")) == 8 * layers
    assert len(measure.kernel_work(harness, "flash_decode_paged")) == \
        8 * layers
    assert len(measure.kernel_work(harness, "moe_gmm")) == 3 * layers


#: what each trace reader reads on the recorded window (and on the recorded
#: probe trace, which has no step records: only the idle share)
PINNED = {
    "decode_step_ms": (18.653329125, None),
    "moe_decode_roofline": (43.25909804625043, None),
    "moe_decode_tiles": (64.0, None),
    "flash_decode_paged_roofline": (27.835414192180416, None),
    "idle_share": (17.404437555555553, 17.95599583163937),
    "mfu.decode": (0.18532922852566272, None),
}


@pytest.mark.parametrize("name", [
    "decode_step_ms", "moe_decode_roofline", "moe_decode_tiles",
    "flash_decode_paged_roofline", "idle_share", "mfu.decode"])
def test_harness_trace_metrics(harness, recorded, name):
    v = run.reader(name)(harness)
    assert v is not None and v > 0
    assert v == pytest.approx(PINNED[name][0], rel=1e-12)
    probe = dict(harness, trace=recorded, decode_calls={}, chunk_calls={})
    want = PINNED[name][1]
    got = run.reader(name)(probe)
    assert got == (None if want is None else pytest.approx(want, rel=1e-12))
    if name.endswith("_roofline") or name in ("idle_share", "mfu.decode"):
        assert v < 100
    if name == "decode_step_ms":
        assert v == pytest.approx(18.6, rel=0.02)
    if name == "moe_decode_tiles":
        assert v == 64             # min(16 slots x k=8, 64 experts)


def test_harness_trace_chunk_steps_and_breakdown(harness):
    # the chunk side of the recorded window, and the breakdown's names
    assert measure.mean_step_ms(harness, "chunk") == pytest.approx(
        73.92679933333332, rel=1e-12)
    assert measure.mfu_pct(harness, "chunk") == pytest.approx(
        0.4577018715397631, rel=1e-12)
    assert measure.roofline_pct(harness, "moe_gmm") == pytest.approx(
        24.301161417442245, rel=1e-12)
    t0, t1 = tr.window(harness["trace"])
    assert [n for n, _ in tr.top_ops(harness["trace"], t0, t1)] == [
        "fusion", "copy", "moe_decode_quant", "moe_gmm_quant",
        "broadcast_select_fusion", "flash_decode_paged",
        "convolution_convert_fusion", "select_add_fusion", "sort",
        "and_select_fusion"]
    gaps = tr.idle_gaps(harness["trace"], t0, t1)
    assert [n for n, _ in gaps] == [
        "bench.engine.decode_step", "bench.engine.chunk_step",
        "bench.decode", "bench.chunk"]
    assert gaps[0][1] == pytest.approx(0.072015699, rel=1e-9)


def test_kernel_joins_follow_the_architectures_layers():
    # a leading dense layer (the tests' own architecture): attention runs
    # in all 3 layers, the expert kernel in the 2 MoE layers, each with
    # the plan's k of its own MoE layer
    a = arch.load("olmoe_dense_first", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "data", "arch"))
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "data", "tiny-dense-first.json")) as f:
        model = json.load(f)
    ops, t = [], 1e6
    for layer in range(3):
        ops.append([ATT, t, 1e3])
        t += 2e3
        if layer >= 1:
            ops.append([MOE, t, 1e3])
            t += 2e3
    red = {"ops": ops, "modules": [["jit_decode_step(1)", 1e6, t - 1e6]],
           "host": [["bench.window", 0, 1e7], ["bench.decode.0", 5e5, 1e3]]}
    run_ = {"trace": red, "arch": a, "model": model,
            "decode_calls": [{"plans": ["lexi", "base"], "ctx": [10, 20]}],
            "chunk_calls": [], "plan_ks": {"base": [2, 2], "lexi": [2, 1]},
            "expert_dtype": "bf16", "peaks": work.peaks("TPU v5 lite")}
    att = measure.kernel_work(run_, "flash_decode_paged")
    moe = measure.kernel_work(run_, "moe_decode")
    assert [c["layer"] for c in att] == [0, 1, 2]
    assert [c["layer"] for c in moe] == [1, 2]
    assert [c["tiles"] for c in moe] == [4, 3]    # k 2+2, then 1+2
    d, f = model["hidden_size"], model["moe_intermediate_size"]
    assert [c["flops"] for c in moe] == [6.0 * d * f * 4, 6.0 * d * f * 3]
    lexi = a.model_flops(model, [2, 1], 10)
    base = a.model_flops(model, [2, 2], 20)
    assert measure.model_flops(run_, "decode") == pytest.approx(lexi + base)
    # the dense layer counts its MLP once, the MoE layers their k experts
    assert base - a.model_flops(model, [2, 1], 20) == 6.0 * d * f


@pytest.mark.parametrize("name, want", [
    ("chunk_step_ms", 73.92679933333332),
    ("moe_gmm_roofline", 24.301161417442245),
    ("mfu.chunk", 0.4577018715397631)])
def test_harness_trace_chunk_metrics(harness, recorded, name, want):
    assert run.reader(name)(harness) == pytest.approx(want, rel=1e-12)
    probe = dict(harness, trace=recorded, decode_calls={}, chunk_calls={})
    assert run.reader(name)(probe) is None     # no chunk step recorded


def test_ttft_p90_counts_a_missing_first_token_as_infinite():
    def rec(due, first):
        return {"due": due, "first": first}

    read = run.reader("ttft_p90_ms")
    ten = [rec(1.0 + i, 1.0 + i + 0.01 * (i + 1)) for i in range(10)]
    run_ = {"window": (1.0, 20.0), "records": ten + [rec(30.0, 30.5)]}
    assert read(run_) == pytest.approx(90.0)         # nearest rank: 9th of 10
    ten[3]["first"] = None
    assert read(run_) == pytest.approx(100.0)        # the 10th now is 9th
    ten[5]["first"] = None
    assert read(run_) == float("inf")
    assert read({"window": (1.0, 2.0), "records": []}) is None
