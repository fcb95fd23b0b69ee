"""Compute queue: share of the worker's wall time in which no task existed
(`starved`: from the previous task's end to the next task's enqueue), over the
traced tasks. Read from what the worker stamps on every `bbtpu.task` span
(`starved_us`: the per-task increment of `rpc_info["worker"]["starved_ms"]`)."""

from cellbench import hosttrace


def read(ctx: dict):
    worker = (hosttrace.reduced(ctx) or {}).get("worker")
    return worker and hosttrace.share(worker["starved_s"], worker["wall_s"])
