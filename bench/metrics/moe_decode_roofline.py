"""Share of its roofline that the ``moe_decode`` kernel reached in the traced
window, %: the least time its needed work takes at the chip's peaks
(``bench/roofline/kernels/moe_decode.py``) over its measured device time."""

from bench import measure


def read(run):
    return measure.roofline_pct(run, "moe_decode")
