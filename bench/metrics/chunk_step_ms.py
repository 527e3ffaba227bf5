"""Mean device time of one chunked-prefill step program in the traced
window, ms."""

from bench import measure


def read(run):
    return measure.mean_step_ms(run, "chunk")
