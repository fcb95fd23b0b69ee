"""Kernels, gated DeltaNet: device time in copy / dynamic-slice /
dynamic-update-slice ops under the linear mixer's `state_io` scope (a
sequence's slot read out of and written into the state arena), over device
busy time: the second arena must not move through the period scan either."""

from cellbench import hosttrace
from cellbench.metrics.gdn_rule_roofline import gdn_reduced


def read(ctx: dict):
    got = gdn_reduced(ctx)
    return got and hosttrace.share(got["move_s"], got["busy_s"])
