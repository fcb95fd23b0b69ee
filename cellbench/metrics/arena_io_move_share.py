"""Kernels: device time in copy / dynamic-slice / dynamic-update-slice ops inside
the layer's `arena_write` / `arena_gather` scopes, over device busy time."""

from cellbench import hosttrace


def read(ctx: dict):
    device = (hosttrace.reduced(ctx) or {}).get("device")
    return device and hosttrace.share(
        device["arena_io_move_s"], device["busy_s"])
