"""Kernels, latent attention: device time in copy / dynamic-slice /
dynamic-update-slice ops under `latent_io` (the latent page written into and
gathered out of the arena), over device busy time: the latent arena must not
move through the step's scan (PR 28's lesson, for a third kind of arena)."""

from cellbench import hosttrace, mlatrace


def read(ctx: dict):
    got = mlatrace.reduced(ctx)
    return got and hosttrace.share(got["latent_io_move_s"], got["busy_s"])
