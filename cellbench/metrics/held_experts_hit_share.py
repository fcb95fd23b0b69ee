"""Kernels, the sparse layer: of the experts the server HOLDS
(`experts_held` of the configuration), the share a full prefill chunk's rows
reached: distinct held experts some row's pair chose / experts held, median
over the sparse layers of every full-chunk step in the trace. The program
counts it on the device in every step and stamps it on a `bbtpu.moe_reach`
span when the counters are read (`cellbench/mlatrace.py`). A chunk that
reaches few experts flatters the expert layer: `chunk_needs` counts the
experts uniform routing would reach."""

import statistics

from cellbench import mlatrace


def read(ctx: dict):
    got = mlatrace.reduced(ctx)
    held = ctx["config"].get("experts_held")
    if not got or not held:
        return None
    hits = [hit for step in mlatrace.traced_steps(
        got, "chunk", ctx["prefill_chunk"], "reach")
        for hit in step["held_hit"]]
    return 100.0 * statistics.median(hits) / held[1] if hits else None
