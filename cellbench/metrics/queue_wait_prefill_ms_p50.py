"""Compute queue: median wait of prefill chunks (rpc_info.queue_wait_ms.prefill,
read at the end of the window)."""

from cellbench import stats


def read(ctx: dict):
    return ((ctx["info1"].get("queue_wait_ms") or {}).get("prefill") or {}).get("p50")
