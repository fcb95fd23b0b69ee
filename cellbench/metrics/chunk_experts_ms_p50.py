"""Kernels, the sparse layer of any family with experts: per solo
prefill-chunk run of `span_step_packed`, the summed self time of the ops
under `moe_router` (the router and the plan of a kernel form), `moe_shared`
and `moe_experts` (`cellbench/scopetrace.py`; `chunk_moe_ms_p50` reads the
same three scopes only beside latent attention); median."""

from cellbench import scopetrace

MOE_SCOPES = ("moe_router", "moe_shared", "moe_experts")


def read(ctx: dict):
    got = scopetrace.reduced(ctx, "moe", MOE_SCOPES, "moe_experts")
    return scopetrace.median_ms(got, "chunk", *MOE_SCOPES)
