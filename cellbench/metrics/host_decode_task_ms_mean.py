"""Executor and jitted step: what one DECODE task (a lone decode step, or a
group of sessions' decode rows: a page table and one row a session) held the
compute thread, wall ms, the mean over ALL the window's such tasks
(`rpc_info["memory"]["host_path"]["decode"]`: `wall_ms` / `n`, info1 - info0;
cellbench/hostpath.py). None for a program without the account or a window
with no such task."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.kind(ctx, "decode")
    return rec and rec["wall_ms"] / rec["n"]
