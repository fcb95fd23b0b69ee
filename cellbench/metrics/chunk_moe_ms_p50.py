"""Kernels, a sparse layer beside shared experts: per solo prefill-chunk run
of `span_step_packed`, the summed self time of the ops under `moe_router`,
`moe_shared` and `moe_experts` (`cellbench/mlatrace.py`, which reads nothing
for a program without latent attention's scopes); median."""

from cellbench import mlatrace


def read(ctx: dict):
    got = mlatrace.reduced(ctx)
    return got and got["chunk_moe_ms_p50"]
