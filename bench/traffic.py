"""One general generator for every traffic mix: data file in, requests out.

A mix is a JSON file under ``bench/traffic/`` (keys below).  Every seed
gets the same multiset of sizes, plans, sampling modes and arrival gaps --
drawn as fixed quantiles of the mix's distributions -- and its own prompt
tokens.  So two seeds do the same work, and a seed's runs repeat exactly.

Only part of the set falls in the window, so an order drawn from the seed
would change the work there: in a closed loop, the first few requests of
each client; in an open loop, the requests due in it (a few tens at a
chat rate, whose heavy-tailed sizes then swing the window's tokens and
tails from seed to seed).  So the order is the same for every seed.  In a
closed loop each client's sequence of sizes, plans and modes is fixed and
the seed decides which client runs which sequence (the clients are
alike); an open loop sends the fixed schedule.  The seed draws the tokens.

Keys of a mix:

* ``loop``: ``"closed"`` (``clients`` callers, each sends its next request
  when the previous one completes) or ``"open"`` (``rate_rps`` Poisson
  arrivals, sent on schedule whatever the server does).
* ``requests``: size of the fixed set (a closed loop deals it round-robin
  to its clients; an open loop sends it in arrival order).
* ``warmup_s`` (open): seconds of arrivals before the window opens.
* ``prompt_len`` / ``output_len``: ``{"median", "sigma", "min", "max"}``
  of a lognormal, clipped.  An output is also cut so that prompt plus
  output stays below the configuration's ``max_len``.
* ``plans``: ``[[name, share], ...]``; ``sampling``: ``[{"share",
  "temperature", "top_k"}, ...]``.  Shares are turned into exact counts.
* ``stream``: stream every response.
* ``stagger_first`` (closed): the first request of sequence ``i`` keeps
  ``(i + 1) / clients`` of its output, so completions are spread over
  the window from the start.
* ``check_requests``: how many finished greedy requests the comparison
  with the reference takes (the longest among them).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

#: the seed of the order of sizes and arrivals, the same for every run
FIXED_ORDER = 0

_KEYS = {"loop", "clients", "rate_rps", "requests", "warmup_s", "prompt_len",
         "output_len", "plans", "sampling", "stream", "stagger_first",
         "check_requests", "why"}


def validate(mix: Dict) -> None:
    unknown = set(mix) - _KEYS
    if unknown:
        raise ValueError(f"unknown traffic keys {sorted(unknown)}")
    if mix["loop"] not in ("closed", "open"):
        raise ValueError(f"loop must be closed or open, got {mix['loop']!r}")
    if mix["loop"] == "closed" and mix.get("clients", 0) < 1:
        raise ValueError("a closed loop needs clients >= 1")
    if mix["loop"] == "open" and not mix.get("rate_rps", 0) > 0:
        raise ValueError("an open loop needs rate_rps > 0")
    for key in ("prompt_len", "output_len"):
        d = mix[key]
        if not 1 <= d["min"] <= d["median"] <= d["max"]:
            raise ValueError(f"{key}: want 1 <= min <= median <= max")


def quantiles(n: int) -> np.ndarray:
    """The ``n`` mid-point quantile levels ``(i + 0.5) / n``."""
    return (np.arange(n) + 0.5) / n


def lognormal_set(dist: Dict, n: int) -> np.ndarray:
    """``n`` integer sizes at fixed quantiles of a clipped lognormal."""
    inv = NormalDist().inv_cdf
    z = np.array([inv(u) for u in quantiles(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exact_counts(shares: List[float], n: int) -> List[int]:
    """Largest-remainder rounding of ``shares`` (normalized) to sum ``n``."""
    w = np.asarray(shares, np.float64)
    w = w / w.sum() * n
    counts = np.floor(w).astype(int)
    for i in np.argsort(-(w - counts))[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def _dealt(labels_counts, n: int, rng) -> list:
    out = []
    for label, c in labels_counts:
        out += [label] * c
    assert len(out) == n
    return [out[i] for i in rng.permutation(n)]


def generate(mix: Dict, *, seed: int, vocab_size: int,
             max_len: int) -> List[Dict]:
    """The mix's requests for ``seed``, in sending order.

    Each request: ``id``, ``prompt`` (token ids), ``max_new``, ``plan``,
    ``temperature``, ``top_k``, and ``client`` (closed loop) or ``due``
    (open loop: seconds after the schedule starts).
    """
    validate(mix)
    n = int(mix["requests"])
    rng = np.random.default_rng(seed)
    closed = mix["loop"] == "closed"
    order = np.random.default_rng(FIXED_ORDER)
    prompt = lognormal_set(mix["prompt_len"], n)[order.permutation(n)]
    output = lognormal_set(mix["output_len"], n)[order.permutation(n)]
    plans = _dealt(zip([p for p, _ in mix["plans"]],
                       exact_counts([s for _, s in mix["plans"]], n)), n,
                   order)
    modes = _dealt(zip(range(len(mix["sampling"])),
                       exact_counts([m["share"] for m in mix["sampling"]],
                                    n)), n, order)
    if closed:
        client_of = rng.permutation(int(mix["clients"]))
    if mix["loop"] == "open":
        gaps = -np.log1p(-quantiles(n)) / float(mix["rate_rps"])
        due = np.cumsum(gaps[order.permutation(n)])
    reqs = []
    for i in range(n):
        p = int(min(prompt[i], max_len - 2))
        out = int(min(output[i], max_len - 1 - p))
        mode = mix["sampling"][modes[i]]
        req = {"id": i,
               "prompt": rng.integers(0, vocab_size, p).tolist(),
               "max_new": out, "plan": plans[i],
               "temperature": float(mode["temperature"]),
               "top_k": int(mode["top_k"])}
        if closed:
            c = int(mix["clients"])
            req["client"] = int(client_of[i % c])
            if mix.get("stagger_first") and i < c:
                req["max_new"] = max(1, round(out * (i + 1) / c))
        else:
            req["due"] = float(due[i])
        reqs.append(req)
    return reqs
