"""Model operations of every prompt token the traced chunked-prefill steps
worked on, at each request's plan k, over the traced window times the
chip's bf16 peak, %."""

from bench import measure


def read(run):
    return measure.mfu_pct(run, "chunk")
