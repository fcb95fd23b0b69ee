"""Kernels, Kimi delta attention: device time in copy / dynamic-slice /
dynamic-update-slice ops under the KDA mixer's `state_io` scope (a
sequence's slot read out of and written into the state arena), over device
busy time: the state arena must not move through the period scan, neither
where the scan is cut into two runs of periods. None for a program without
the KDA scopes."""

from cellbench import hosttrace
from cellbench.metrics.kda_rule_roofline import kda_reduced


def read(ctx: dict):
    got = kda_reduced(ctx)
    return got and hosttrace.share(got["move_s"], got["busy_s"])
