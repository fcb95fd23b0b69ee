"""Executor: median device duration of one DECODE group step in the trace: the
runs of `span_step_packed` that executed the paged decode kernel (a solo
prefill chunk runs the same jitted function and is reduced apart, as
`server_prefill_ms_p50`)."""

from cellbench import stats


def read(ctx: dict):
    return (ctx.get("trace") or {}).get("server_step_ms_p50")
