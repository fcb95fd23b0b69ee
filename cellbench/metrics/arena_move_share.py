"""Kernels: device time in copy / dynamic-slice / dynamic-update-slice ops over
device busy time in the trace."""

from cellbench import stats


def read(ctx: dict):
    return (ctx.get("trace") or {}).get("arena_move_share")
