"""The rate sweep that finds an open-loop cell's knee: its rule, and one
sweep on the CPU at a tiny size, past its look for a chip."""

import math
import os

import pytest

from bench import sweep

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TINY = {
    "configs": [{"name": "tiny", "file": "bench/tests/data/tiny.json"}],
    "workloads": [{"name": "tiny.open", "config": "tiny",
                   "traffic": "tiny_open", "chips": 1},
                  {"name": "tiny.closed", "config": "tiny",
                   "traffic": "tiny_closed", "chips": 1}],
    "end_to_end": [], "per_layer": [],
}


def _row(rate, share, ttft):
    return {"rate": rate, "share_first_by_close": share,
            "ttft_p90_ms": ttft}


@pytest.mark.parametrize("rows,want", [
    # every rate passes: the highest
    ([_row(1.0, 1.0, 500), _row(1.0, 1.0, 520), _row(2.0, 0.96, 900),
      _row(2.0, 1.0, 950)], 2.0),
    # one window of 2.0 misses its first tokens
    ([_row(1.0, 1.0, 500), _row(2.0, 0.9, 600), _row(2.0, 1.0, 600),
      _row(3.0, 1.0, 600)], 1.0),
    # 2.0's median TTFT p90 is over twice 1.0's; 3.0 does not count again
    ([_row(1.0, 1.0, 500), _row(2.0, 1.0, 1100), _row(3.0, 1.0, 700)],
     1.0),
    # a request that never got one reads infinite
    ([_row(1.0, 1.0, 500), _row(1.5, 1.0, math.inf)], 1.0),
    ([_row(1.0, 0.5, 500)], None),
])
def test_knee_rule(rows, want):
    k = sweep.knee(rows)
    if want is None:
        assert k is None
    else:
        assert k["knee_rps"] == want
        assert k["cell_rps"] == pytest.approx(sweep.CELL_SHARE * want)


def test_a_sweep_runs_on_the_cpu():
    rows = []
    res = sweep.sweep("tiny.open", 2**31 + 5, [2.0, 4.0], 1, 2.0,
                      require_chip=False, bench=TINY,
                      traffic_dir=os.path.join(DATA, "traffic"),
                      emit=rows.append)
    assert res["rows"] == rows and [r["rate"] for r in rows] == [2.0, 4.0]
    for r in rows:
        assert r["due"] > 0 and r["first_by_close"] <= r["due"]
        assert r["output_tok_s"] > 0 and r["itl_p50_ms"] > 0
    # both rates send the same requests: the window at 4/s holds more
    assert rows[1]["due"] > rows[0]["due"]
    assert res["knee"] is None or res["knee"]["knee_rps"] in (2.0, 4.0)


def test_a_closed_loop_is_refused():
    with pytest.raises(ValueError, match="not an open loop"):
        sweep.sweep("tiny.closed", 1, [1.0], 1, 1.0, require_chip=False,
                    bench=TINY, traffic_dir=os.path.join(DATA, "traffic"))
