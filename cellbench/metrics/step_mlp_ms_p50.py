"""Kernels: per decode run of `span_step_packed`, the summed self time of the
ops in the layer's `mlp` (or `moe_router` + `moe_experts`) scope; median."""

from cellbench import hosttrace


def read(ctx: dict):
    device = (hosttrace.reduced(ctx) or {}).get("device")
    return device and device["step_mlp_ms_p50"]
