"""Compute queue: p95 wait of decode steps (rpc_info.queue_wait_ms.decode, the
server's recent-sample window, read at the end of the window)."""

from cellbench import stats


def read(ctx: dict):
    return ((ctx["info1"].get("queue_wait_ms") or {}).get("decode") or {}).get("p95")
