"""Kernels: per decode run of `span_step_packed`, the summed self time of the
ops under the state-space mixer's scopes `ssm_proj`, `ssm_scan` and `state_io`
(`cellbench/ssmtrace.py`); median. None for a program without the scopes."""

from cellbench import ssmtrace


def read(ctx: dict):
    got = ssmtrace.reduced(ctx)
    return got and got["step_ssm_ms_p50"]
