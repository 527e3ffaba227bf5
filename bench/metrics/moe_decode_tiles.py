"""Expert tiles per MoE layer and decode step that ``moe_decode_roofline``
takes as the need, averaged over the traced calls.  It is an upper bound
(``bench/roofline/kernels/moe_decode.py``): the benchmark cannot see how
many distinct experts the live slots routed to, so read the roofline
share beside it."""

from bench import measure


def read(run):
    calls = measure.kernel_work(run, "moe_decode")
    if not calls:
        return None
    return sum(c["tiles"] for c in calls) / len(calls)
