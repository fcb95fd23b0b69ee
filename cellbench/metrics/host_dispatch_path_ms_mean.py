"""Executor and jitted step: mean duration of a `bbtpu.task` span over the traced
tasks: the serialised host cost of one dispatch (pack, h2d, the jit call,
commit, slicing), to hold against `server_step_ms_p50`."""

from cellbench import hosttrace


def read(ctx: dict):
    worker = (hosttrace.reduced(ctx) or {}).get("worker")
    return worker and 1e3 * worker["busy_s"] / worker["tasks"]
