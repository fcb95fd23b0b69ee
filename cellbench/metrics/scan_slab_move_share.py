"""Kernels: device time in copy / dynamic-slice / dynamic-update-slice ops of a
span-step program that carry NO layer scope (what the step's `lax.scan` and
`lax.cond` emit to slice and restack the arena), over device busy time."""

from cellbench import hosttrace


def read(ctx: dict):
    device = (hosttrace.reduced(ctx) or {}).get("device")
    return device and hosttrace.share(
        device["scan_slab_move_s"], device["busy_s"])
