"""The readers of the serving loop's program counters: each reads window
deltas of ``Engine.stats`` and reads None where the program has no such
counters (a program older than them)."""

import pytest

from bench import run

PHASES = ("engine.admit", "engine.chunk.prepare", "engine.chunk.dispatch",
          "engine.chunk.sample", "engine.chunk.wait", "engine.chunk.commit",
          "engine.decode.prepare", "engine.decode.dispatch",
          "engine.decode.sample", "engine.decode.wait",
          "engine.decode.commit", "server.retire", "server.handoff")


def _stats(scale, **extra):
    s = {"steps": 100 * scale, "chunk_steps": 6 * scale,
         "iterations": 104 * scale, "decode_tokens": 1600 * scale,
         "host_cpu_s": 0.8 * scale}
    for i, p in enumerate(PHASES):
        s["host_s:" + p] = 0.01 * (i + 1) * scale
    for i in range(8):
        s[f"experts_routed:l{i}"] = (40 + i) * 100 * scale
    s.update(extra)
    return s


def _run(stats0, stats1):
    return {"window": (0.0, 10.0), "records": [], "stats0": stats0,
            "stats1": stats1, "trace": None}


SYNTH = _run(_stats(1), _stats(3))      # deltas are twice the first


def _wall(p):
    return 0.02 * (PHASES.index(p) + 1)     # the delta of host_s:<p>


EXPECTED = {
    "decode_host_ms": 1e3 * sum(_wall("engine.decode." + x) for x in (
        "prepare", "dispatch", "sample", "commit")) / 200,
    "chunk_host_ms": 1e3 * sum(_wall("engine.chunk." + x) for x in (
        "prepare", "dispatch", "sample", "commit")) / 12,
    "loop_host_ms": 1e3 * sum(_wall(p) for p in (
        "engine.admit", "server.retire", "server.handoff")) / 208,
    "pump_cpu_share": 100.0 * 1.6 / sum(
        _wall(p) for p in PHASES if not p.endswith(".wait")),
    "moe_decode_experts": sum((40 + i) * 200 for i in range(8)) / (200 * 8),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reads_the_window_deltas(name):
    assert run.reader(name)(SYNTH) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_absent_counters_read_none(name):
    old = {"steps": 10, "decode_tokens": 160, "preemptions": 0}
    new = {"steps": 30, "decode_tokens": 480, "preemptions": 0}
    assert run.reader(name)(_run(old, new)) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_an_empty_window_reads_none(name):
    same = _stats(1)                # no step and no phase time in it
    assert run.reader(name)(_run(same, dict(same))) is None


def test_the_new_metrics_are_declared_for_both_cells():
    bench = run.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in EXPECTED:
        m = by_name[name]
        assert m["source"] == "program_counter"
        assert m["workloads"][:2] == ["olmoe8.decode", "olmoe8-int8.decode"]
