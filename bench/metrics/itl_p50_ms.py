"""Median (nearest-rank 50th percentile) of every gap between consecutive
streamed tokens of a request in the window, in ms."""

from bench import measure


def read(run):
    p = measure.pct(measure.token_gaps(run), 50)
    return None if p is None else 1e3 * p
