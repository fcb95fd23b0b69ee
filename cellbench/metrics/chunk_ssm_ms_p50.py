"""Kernels: per solo prefill-chunk run of `span_step_packed`, the summed self
time of the ops under the state-space mixer's scopes `ssm_proj`, `ssm_scan`
and `state_io` (`cellbench/ssmtrace.py`); median."""

from cellbench import ssmtrace


def read(ctx: dict):
    got = ssmtrace.reduced(ctx)
    return got and got["chunk_ssm_ms_p50"]
