"""Executor and jitted step: of the window's launches of a span-step program
(`SpanExecutor._dispatch`) in the tasks the program reads in full (one of 32),
all kinds, those that found the device IDLE: the
arena the step was about to donate, the last program's output, was ready
(`launches_on_idle` / `launches` of `rpc_info["memory"]["host_path"]`,
info1 - info0), %. Every such launch is a bubble the host made; near 0 the
device's queue never ran dry. None for a program without the account or a
window with no launch."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.total(ctx)
    return rec and hostpath.share(rec["launches_on_idle"], rec["launches"])
