"""The diagnostic summary reads what a kept run holds: the window's rate,
gap percentiles and the share of engine steps that ran a chunk."""

import gzip
import json

from bench import diagnose


def test_summary_of_a_kept_run(tmp_path, capsys):
    # two requests over a 1 s window: 10 tokens each, 0.1 s apart; four
    # steps in the window, one of which ran a chunk
    recs = [{"tokens_t": [0.05 + 0.1 * i for i in range(10)]},
            {"tokens_t": [0.06 + 0.1 * i for i in range(10)]}]
    steps = [[0.0, 0.03, 0], [0.1, 0.2, 1], [0.2, 0.23, 0], [0.3, 0.34, 0],
             [1.5, 1.6, 1]]
    path = tmp_path / "run.json.gz"
    with gzip.open(path, "wt") as f:
        json.dump({"window": [0.0, 1.0], "records": recs, "steps": steps},
                  f)
    assert diagnose.main(["summary", str(path)]) == 0
    line = capsys.readouterr().out
    assert "20.00 tokens/s" in line
    assert "p50 100.0" in line and "p99 100.0" in line
    assert "4 steps, 25.00% ran a chunk" in line
    assert "decode-only p50 30.0 ms, p99 40.0 ms" in line
