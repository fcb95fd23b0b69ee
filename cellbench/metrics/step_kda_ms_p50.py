"""Kernels, Kimi delta attention: per decode run of `span_step_packed`, the
summed self time of the ops under the KDA mixer's scopes `kda_proj`,
`kda_conv`, `kda_rule` and `state_io` (`cellbench/scopetrace.py`); median.
None for a program without the scopes."""

from cellbench import scopetrace
from cellbench.metrics.kda_rule_roofline import KDA_SCOPES, kda_reduced


def read(ctx: dict):
    return scopetrace.median_ms(kda_reduced(ctx), "decode", *KDA_SCOPES)
