"""Kernels: device time in copy / dynamic-slice / dynamic-update-slice ops
under the mixer's `state_io` scope (a sequence's slot read out of and written
into the recurrent-state arena), over device busy time: the state arena must
not move through the step's scan (PR 28's lesson, for the second arena)."""

from cellbench import hosttrace, ssmtrace


def read(ctx: dict):
    got = ssmtrace.reduced(ctx)
    return got and hosttrace.share(got["state_io_move_s"], got["busy_s"])
