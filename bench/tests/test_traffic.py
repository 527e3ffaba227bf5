"""Traffic drawn from a seed: deterministic, clipped as its file says, and
the same work for every seed."""

import json
import os
from collections import Counter

import pytest

from bench import run, traffic

BIG_SEED = 2**31 + 12345


DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "traffic")


def _mix(name):
    """A mix of the benchmark's, or one of the tests' own (``tiny_*``)."""
    where = DATA if name.startswith("tiny_") else os.path.join(run.HERE,
                                                               "traffic")
    with open(os.path.join(where, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["decode", "decode_base", "tiny_open"])
def test_same_seed_same_requests(name):
    mix = _mix(name)
    a = traffic.generate(mix, seed=BIG_SEED, vocab_size=50304, max_len=2048)
    b = traffic.generate(mix, seed=BIG_SEED, vocab_size=50304, max_len=2048)
    assert a == b
    c = traffic.generate(mix, seed=BIG_SEED + 1, vocab_size=50304,
                         max_len=2048)
    assert a != c


@pytest.mark.parametrize("name", ["decode", "tiny_open"])
def test_every_seed_gets_the_same_sizes(name):
    mix = _mix(name)
    runs = [traffic.generate(mix, seed=s, vocab_size=50304, max_len=2048)
            for s in (1, 7, BIG_SEED)]
    for key in ("plan", "temperature"):
        assert len({frozenset(Counter(r[key] for r in reqs).items())
                    for reqs in runs}) == 1
    prompts = {tuple(sorted(len(r["prompt"]) for r in reqs))
               for reqs in runs}
    assert len(prompts) == 1
    if mix["loop"] == "open":
        last = {round(reqs[-1]["due"], 9) for reqs in runs}
        assert len(last) == 1       # the same total span of arrivals


@pytest.mark.parametrize("name", ["decode", "decode_base"])
def test_closed_loop_clients_run_the_same_sequences_for_every_seed(name):
    mix = _mix(name)

    def sequences(seed):
        reqs = traffic.generate(mix, seed=seed, vocab_size=50304,
                                max_len=2048)
        by_client = {}
        for r in reqs:
            by_client.setdefault(r["client"], []).append(
                (len(r["prompt"]), r["max_new"], r["plan"],
                 r["temperature"]))
        return by_client

    a, b = sequences(1), sequences(BIG_SEED)
    assert sorted(a.values()) == sorted(b.values())
    assert a != b               # the seed deals them to other clients


@pytest.mark.parametrize("name", ["decode", "decode_base", "tiny_open"])
def test_clipped_as_the_file_says(name):
    mix = _mix(name)
    reqs = traffic.generate(mix, seed=3, vocab_size=50304, max_len=2048)
    assert len(reqs) == mix["requests"]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert all(lo <= len(r["prompt"]) <= hi for r in reqs)
    omax = mix["output_len"]["max"]
    assert all(1 <= r["max_new"] <= omax for r in reqs)
    assert all(len(r["prompt"]) + r["max_new"] <= 2047 for r in reqs)
    assert all(0 <= t < 50304 for r in reqs for t in r["prompt"])
    if mix["loop"] == "open":
        dues = [r["due"] for r in reqs]
        assert dues == sorted(dues)
    else:
        assert {r["client"] for r in reqs} == set(range(mix["clients"]))


def test_stagger_keeps_a_share_of_the_first_outputs():
    mix = _mix("decode")
    flat = dict(mix, stagger_first=False)
    a = traffic.generate(mix, seed=5, vocab_size=50304, max_len=2048)
    b = traffic.generate(flat, seed=5, vocab_size=50304, max_len=2048)
    n = mix["clients"]
    for i in range(n):
        assert a[i]["max_new"] == max(1, round(b[i]["max_new"] * (i + 1) / n))
    assert [r["max_new"] for r in a[n:]] == [r["max_new"] for r in b[n:]]


def test_median_and_quantiles():
    sizes = traffic.lognormal_set({"median": 100, "sigma": 0.5, "min": 1,
                                   "max": 10**6}, 101)
    assert sorted(sizes)[50] == 100
    assert traffic.exact_counts([0.5, 0.5], 7) in ([4, 3], [3, 4])
    assert sum(traffic.exact_counts([0.75, 0.25], 110)) == 110


def test_bad_mix_is_refused():
    mix = _mix("tiny_open")
    with pytest.raises(ValueError, match="unknown traffic keys"):
        traffic.validate(dict(mix, burst=3))
    with pytest.raises(ValueError, match="rate_rps"):
        traffic.validate(dict(mix, rate_rps=0))


def test_open_loop_sends_the_same_schedule_for_every_seed():
    # the window holds a few tens of requests: the seed must not choose
    # which sizes fall in it
    mix = _mix("tiny_open")

    def schedule(seed):
        return [(round(r["due"], 9), len(r["prompt"]), r["max_new"],
                 r["plan"], r["temperature"])
                for r in traffic.generate(mix, seed=seed, vocab_size=50304,
                                          max_len=2048)]

    assert schedule(1) == schedule(BIG_SEED)
