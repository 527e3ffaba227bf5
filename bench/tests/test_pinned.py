"""What the harness reads for OLMoE, pinned: the weight tree a seed makes,
the reference's logits, and the operations, bytes and model FLOPs the
kernel joins count at OLMoE-1B-7B's widths.  The numbers were taken from
the harness before it took the model's layer from an architecture module
(``bench/arch/``); a change that moves any of them changes what the
benchmark's OLMoE cells read."""

import hashlib
import json
import os

import numpy as np
import pytest

from bench import arch, measure, run
from bench.roofline import work
from bench.weights import make_weights

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIGS = os.path.join(run.HERE, "configs")


def _load(path):
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------------------- #
# weights and reference
# --------------------------------------------------------------------------- #


def test_weight_tree_digest():
    import jax

    model = _load(os.path.join(DATA, "tiny.json"))
    w = make_weights(model, 2**31 + 7, arch.of(model))
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(w)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == (
        "d2119ca6fa72e03152f72283295c34c92f2d4dbe94d019f2a5aea07c28f05a4c")


#: (ks, expert dtype, dense dtype) -> (sum, sum of |x|, argmax per position,
#: logit [7, 11]) of the tiny model's reference logits over 40 tokens
LOGITS = {
    ((2, 2), None, None): (
        -125.9148941040039, 8312.0693359375,
        [103, 46, 17, 12, 17, 100, 17, 247, 57, 4, 125, 53, 42, 165, 59,
         218, 51, 59, 112, 78, 131, 133, 70, 133, 213, 51, 116, 253, 57,
         101, 77, 57, 49, 233, 127, 70, 217, 187, 48, 70],
        0.6505872011184692),
    ((2, 1), None, None): (
        -128.78829956054688, 8309.33203125,
        [103, 46, 17, 12, 17, 100, 17, 247, 60, 4, 125, 53, 42, 165, 59,
         218, 51, 59, 112, 129, 131, 133, 70, 253, 213, 51, 218, 253, 57,
         101, 77, 57, 78, 233, 127, 70, 217, 187, 48, 70],
        0.6816738843917847),
    ((2, 1), "int8", "int4"): (
        -74.15565490722656, 8326.5244140625,
        [103, 17, 17, 242, 17, 5, 17, 247, 60, 4, 125, 204, 42, 165, 59,
         218, 51, 59, 112, 166, 131, 193, 73, 115, 213, 51, 116, 253, 57,
         101, 77, 57, 159, 233, 218, 70, 217, 187, 48, 70],
        0.5954564809799194),
}


@pytest.fixture(scope="module")
def tiny_weights():
    model = _load(os.path.join(DATA, "tiny.json"))
    return model, make_weights(model, 2**31 + 7, arch.of(model))


@pytest.mark.parametrize("key", list(LOGITS), ids=lambda k: "-".join(
    [str(x) for x in k[0]] + [str(k[1]), str(k[2])]))
def test_reference_logits(tiny_weights, key):
    model, w = tiny_weights
    ks, experts, dense = key
    toks = np.random.default_rng(0).integers(0, 256, 40).tolist()
    lg = arch.of(model).logits(w, model, toks, list(ks),
                               expert_dtype=experts, dense_dtype=dense,
                               pad_to=64)
    want = LOGITS[key]
    assert lg.shape == (40, 256)
    got = (float(lg.sum()), float(np.abs(lg).sum()),
           lg.argmax(-1).tolist(), float(lg[7, 11]))
    assert got[0] == pytest.approx(want[0], rel=1e-6, abs=1e-3)
    assert got[1] == pytest.approx(want[1], rel=1e-6)
    assert got[2] == want[2]
    assert got[3] == pytest.approx(want[3], rel=1e-6)


# --------------------------------------------------------------------------- #
# kernel work and model FLOPs at OLMoE-1B-7B's widths
# --------------------------------------------------------------------------- #

FUS = "%fusion.7 = bf16[16,2048]{1,0} fusion(bf16[16,2048]{1,0} %y)"


def _op(base, n):
    return (f"%{base}.{n} = bf16[16,2048]{{1,0}} custom-call(s32[16,8]{{1,0}}"
            f" %a), custom_call_target=\"tpu_custom_call\"")


def olmoe_run(expert_dtype="bf16"):
    """A hand-made trace of two decode steps and one chunk step of the
    8-layer OLMoE configuration, mixed plans, each kernel call with a
    duration of its own, joined to the steps' records."""
    config = _load(os.path.join(CONFIGS, "olmoe-1b-7b-l8.json"))
    moe = "moe_decode" if expert_dtype == "bf16" else "moe_decode_quant"
    gmm = "moe_gmm" if expert_dtype == "bf16" else "moe_gmm_quant"
    ops, modules, t = [], [], 1e6
    host = [["bench.window", 0.0, 100e6]]
    for step in range(2):
        host.append([f"bench.decode.{step}", t - 0.5e6, 0.1e6])
        start = t
        for layer in range(8):
            ops.append([_op("flash_decode_paged", 2 * layer), t,
                        40e3 + 1e3 * layer + 7e3 * step])
            t += 50e3
            ops.append([_op(moe, 2 * layer + 1), t,
                        900e3 + 10e3 * layer + 3e3 * step])
            t += 950e3
            ops.append([FUS, t, 5e3])
            t += 10e3
        modules.append([f"jit_decode_step({step})", start, t - start])
        t += 2e6
    host.append(["bench.chunk.0", t - 0.5e6, 0.1e6])
    start = t
    for layer in range(8):
        ops.append([_op(gmm, layer), t, 5e6 + 1e5 * layer])
        t += 6e6
    modules.append(["jit_chunk_step(2)", start, t - start])
    return {
        "trace": {"ops": ops, "modules": modules, "host": host},
        "decode_calls": [
            {"plans": ["lexi"] * 10 + ["base"] * 6,
             "ctx": [37 * i + 5 for i in range(16)]},
            {"plans": ["base"] * 3, "ctx": [100, 2000, 37]}],
        "chunk_calls": [{"plans": ["lexi", "base"], "starts": [0, 256],
                         "tokens": [128, 77]}],
        "plan_ks": {"base": [8] * 8, "lexi": config["plans"]["lexi"]},
        "model": config, "arch": arch.of(config),
        "expert_dtype": expert_dtype,
        "peaks": work.peaks("TPU v5 lite")}


#: kernel -> (calls, Σ operations, Σ bytes, roofline %), per expert dtype
KERNEL_WORK = {
    "bf16": {
        "moe_decode": (16, 12784238592.0, 8859615232.0, 72.1943474097238),
        "flash_decode_paged": (16, 436273152.0, 437518336.0,
                               71.03862000883277),
        "moe_gmm": (8, 132875550720.0, 6528958464.0, 18.6258557392763)},
    "int8": {
        "moe_decode": (16, 12784238592.0, 4439080960.0, 36.17273940391933),
        "flash_decode_paged": (16, 436273152.0, 437518336.0,
                               71.03862000883277),
        "moe_gmm": (8, 132875550720.0, 3314024448.0, 9.454270788401628)},
}
#: kind -> (model FLOPs, mfu %, mean step ms)
MODEL_FLOPS = {"decode": (22275489792.0, 0.11307355224365483, 8.08),
               "chunk": (232603713536.0, 1.1807295103350255, 48.0)}


@pytest.mark.parametrize("dtype", ["bf16", "int8"])
@pytest.mark.parametrize("kernel", ["moe_decode", "flash_decode_paged",
                                    "moe_gmm"])
def test_kernel_work_at_olmoe_widths(dtype, kernel):
    r = olmoe_run(dtype)
    calls = measure.kernel_work(r, kernel)
    n, flops, nbytes, pct = KERNEL_WORK[dtype][kernel]
    assert len(calls) == n
    assert [c["layer"] for c in calls] == list(range(8)) * (n // 8)
    assert sum(c["flops"] for c in calls) == pytest.approx(flops, rel=1e-12)
    assert sum(c["bytes"] for c in calls) == pytest.approx(nbytes, rel=1e-12)
    assert measure.roofline_pct(r, kernel) == pytest.approx(pct, rel=1e-12)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_model_flops_at_olmoe_widths(kind):
    r = olmoe_run()
    flops, mfu, step_ms = MODEL_FLOPS[kind]
    assert measure.model_flops(r, kind) == pytest.approx(flops, rel=1e-12)
    assert measure.mfu_pct(r, kind) == pytest.approx(mfu, rel=1e-12)
    assert measure.mean_step_ms(r, kind) == pytest.approx(step_ms,
                                                          rel=1e-12)


def test_decode_tiles_at_olmoe_widths():
    r = olmoe_run()
    tiles = [c["tiles"] for c in measure.kernel_work(r, "moe_decode")]
    # step 0: 10 lexi slots and 6 base ones, capped at 64; step 1: 3 x 8
    lexi = r["plan_ks"]["lexi"]
    assert tiles == [min(10 * k + 6 * 8, 64) for k in lexi] + [24] * 8
    assert run.reader("moe_decode_tiles")(r) == pytest.approx(
        sum(tiles) / len(tiles))
