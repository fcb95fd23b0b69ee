"""Executor and jitted step: of the wall time of the window's tasks on the
compute thread, all kinds, what lies under NO span but `bbtpu.task` and
`bbtpu.dispatch` themselves (the leg `unnamed` of
`rpc_info["memory"]["host_path"]`, info1 - info0), %. What this share holds
no other host metric can name. None for a program without the account."""

from cellbench import hostpath


def read(ctx: dict):
    rec = hostpath.total(ctx)
    return rec and hostpath.share(
        (rec["legs"].get("unnamed") or {}).get("wall_ms", 0.0),
        rec["wall_ms"])
