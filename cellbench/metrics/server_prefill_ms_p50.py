"""Executor: median device duration of one solo PREFILL CHUNK program in the
trace: the runs of `span_step_packed` that did not execute the paged decode
kernel."""

from cellbench import stats


def read(ctx: dict):
    return (ctx.get("trace") or {}).get("server_prefill_ms_p50")
