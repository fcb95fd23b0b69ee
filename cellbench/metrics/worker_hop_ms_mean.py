"""Compute queue: mean time a task existed before the compute thread started it
(event-loop turn, gather window, `run_in_executor`), over the traced tasks:
the `hop_us` the worker stamps on every `bbtpu.task` span (the per-task
increment of `rpc_info["worker"]["hop_ms"]`)."""

from cellbench import hosttrace


def read(ctx: dict):
    worker = (hosttrace.reduced(ctx) or {}).get("worker")
    return worker and 1e3 * worker["hop_s"] / (worker["tasks"] - 1)
