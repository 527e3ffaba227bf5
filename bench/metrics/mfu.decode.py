"""Model operations of every token the traced decode steps produced, at
each request's plan k, over the traced window times the chip's bf16
peak, %."""

from bench import measure


def read(run):
    return measure.mfu_pct(run, "decode")
