"""Executor and jitted step: what one CHUNK task (a prompt chunk alone: 512
rows packed and put on the device) held the compute thread, wall ms, the mean
over all the window's such tasks
(`rpc_info["memory"]["host_path"]["chunk"]`: `wall_ms` / `n`, info1 - info0;
cellbench/hostpath.py). None for a program without the account or a window
with no such task."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.kind(ctx, "chunk")
    return rec and rec["wall_ms"] / rec["n"]
