"""A whole run of the harness on the CPU at a tiny size, past its look for
a chip: the engine behind ``ApiServer``, the load generator, the window,
the metrics and the comparison with the reference.

The sound run must come out correct; the lower-precision control, read on
the same tokens, must not; and so must not a run whose engine alters the
tokens it samples, whose decode step drops its KV writes, or whose decode
step gives half of the batch the other half's logits.  An architecture
that only the tests' own files define (a leading dense layer) runs
through the same harness, correct when sound and not when its dense
layer's output is zeroed.
"""

import functools
import json
import os

import numpy as np
import pytest

from bench import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: architectures of the tests' own (``olmoe_dense_first``)
ARCH = os.path.join(DATA, "arch")
TINY = {
    "configs": [{"name": "tiny", "file": "bench/tests/data/tiny.json"},
                {"name": "tiny-int8",
                 "file": "bench/tests/data/tiny-int8.json"},
                {"name": "tiny-dense-first",
                 "file": "bench/tests/data/tiny-dense-first.json"}],
    "workloads": [
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny_closed",
         "chips": 1},
        {"name": "tiny.open", "config": "tiny", "traffic": "tiny_open",
         "chips": 1},
        {"name": "tiny.closed-all", "config": "tiny",
         "traffic": "tiny_closed_all", "chips": 1},
        {"name": "tiny-int8.closed", "config": "tiny-int8",
         "traffic": "tiny_closed_base", "chips": 1},
        {"name": "tiny-dense-first.closed", "config": "tiny-dense-first",
         "traffic": "tiny_closed", "chips": 1}],
    "end_to_end": [
        {"name": "output_tok_s", "unit": "tokens/s"},
        {"name": "itl_p95_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def _run(cell, seed, **kw):
    return run.run_cell(cell, seed, 2.0, False, require_chip=False,
                        bench=TINY, traffic_dir=os.path.join(DATA, "traffic"),
                        log=lambda *a: None, **kw)


@pytest.fixture(scope="module")
def sound():
    return _run("tiny.closed", 2**31 + 7, control=True)


def test_sound_run_is_correct(sound):
    assert sound["correct"] is True
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert set(sound["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in sound["metrics"].values())
    assert list(sound)[-1] == "compared"        # the compared numbers last
    c = sound["compared"]
    assert c["mean_gap"]["value"] <= c["mean_gap"]["limit"]
    assert c["tokens_compared"]["value"] >= c["tokens_compared"]["limit"]
    assert sound["device"]["platform"] == "cpu"


def test_lower_precision_control_is_not_correct(sound):
    limit = sound["compared"]["mean_gap"]["limit"]
    assert sound["readings"]["control"]["mean_gap"] > 3 * limit


def test_int8_experts_run_and_compare():
    # expert tiles quantized at load, layers declared apart; the reference
    # quantizes its own copy of the weights the same way
    out = _run("tiny-int8.closed", 5, control=True)
    assert out["correct"] is True
    limit = out["compared"]["mean_gap"]["limit"]
    assert out["readings"]["control"]["mean_gap"] > 3 * limit


def test_a_new_architecture_runs_from_its_files_alone():
    # a leading dense layer: 3 layers, 2 of them MoE, the plan's k per MoE
    # layer; the harness takes the architecture from its module alone
    out = _run("tiny-dense-first.closed", 2**31 + 11, arch_dir=ARCH,
               control=True)
    assert out["correct"] is True
    limit = out["compared"]["mean_gap"]["limit"]
    assert out["readings"]["control"]["mean_gap"] > 3 * limit


def test_a_new_architecture_with_its_dense_layer_zeroed_is_caught(
        monkeypatch):
    import jax.numpy as jnp
    import repro.models.blocks as blocks

    monkeypatch.setattr(blocks, "mlp", lambda params, x: jnp.zeros_like(x))
    out = _run("tiny-dense-first.closed", 2**31 + 11, arch_dir=ARCH)
    assert out["correct"] is False
    assert out["compared"]["mean_gap"]["value"] > \
        out["compared"]["mean_gap"]["limit"]


def test_altered_tokens_are_caught(monkeypatch):
    import repro.serving.engine as engine_mod

    real = engine_mod.sample_per_slot

    def altered(logits, *a, **kw):
        tok = real(logits, *a, **kw)
        return (tok + 1) % logits.shape[-1]     # the next token id instead

    monkeypatch.setattr(engine_mod, "sample_per_slot", altered)
    out = _run("tiny.open", 11)
    assert out["correct"] is False
    assert out["compared"]["mean_gap"]["value"] > \
        out["compared"]["mean_gap"]["limit"]
    assert set(out["metrics"]) == {"output_tok_s", "itl_p95_ms", "setup_s"}


def _state_unchanged(step, tokens, pos, caches, *a, **kw):
    # the step runs on a copy of the pool (the engine's is not donated)
    # and its KV writes are dropped: the engine keeps the pool it had
    logits, _ = step(tokens, pos, caches, *a, **dict(kw, donate=False))
    return logits, caches


def _half_batch(step, *a, **kw):
    logits, new_caches = step(*a, **kw)
    half = logits.shape[0] // 2     # the upper slots get the lower ones'
    return logits.at[half:].set(logits[:half]), new_caches


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_decode_step_is_caught(monkeypatch, fault):
    from repro.serving.runner import ModelRunner

    real = ModelRunner.decode

    def broken(self, *a, **kw):
        return fault(functools.partial(real, self), *a, **kw)

    monkeypatch.setattr(ModelRunner, "decode", broken)
    # every finished request is compared: a fault that alters only the
    # upper slots' tokens shows whichever requests the seed's sample holds
    out = _run("tiny.closed-all", 13)
    assert out["correct"] is False
    assert out["compared"]["mean_gap"]["value"] > \
        out["compared"]["mean_gap"]["limit"]


def test_cpu_is_refused(capsys):
    assert run.main(["--workload", "olmoe8.decode", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_sample_holds_the_longest_greedy_request():
    with open(os.path.join(DATA, "tiny.json")) as f:
        config = json.load(f)
    reqs = [{"id": i, "max_new": n, "temperature": t, "prompt": [1, 2]}
            for i, (n, t) in enumerate([(3, 0.0), (9, 0.7), (5, 0.0),
                                        (4, 0.0)])]
    recs = [{"id": r["id"], "status": "ok", "result": {
        "tokens": list(range(r["max_new"])), "served_plan": "base"}}
        for r in reqs]
    picked = run.sample_requests(recs, reqs, n=2, seed=1)
    assert picked[0]["id"] == 2                 # longest greedy, not id 1
    assert {r["id"] for r in picked} <= {0, 2, 3}
    assert len(picked) == 2
    assert config["check"]["limit"] > 0 and np.isfinite(
        config["check"]["limit"])
