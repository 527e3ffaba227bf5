"""Distinct experts the live slots routed to, per MoE layer and decode
step, in the window: counted by the decode program from the router's
ids, within each request's k budget (program counters).  The expert
kernel's real need, beside the upper bound ``moe_decode_tiles``."""

from bench import host_phases


def read(run):
    return host_phases.experts_per_layer_step(run)
