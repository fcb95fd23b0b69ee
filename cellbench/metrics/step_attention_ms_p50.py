"""Kernels: per decode run of `span_step_packed` (`cellbench/trace.py::program_kind`),
the summed self time of the ops in the layer's `attention` scope; median."""

from cellbench import hosttrace


def read(ctx: dict):
    device = (hosttrace.reduced(ctx) or {}).get("device")
    return device and device["step_attention_ms_p50"]
