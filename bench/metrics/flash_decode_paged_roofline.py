"""Share of its roofline that the ``flash_decode_paged`` kernel reached in
the traced window, %: the least time its needed work takes at the chip's
peaks (``bench/roofline/kernels/flash_decode_paged.py``) over its
measured device time."""

from bench import measure


def read(run):
    return measure.roofline_pct(run, "flash_decode_paged")
