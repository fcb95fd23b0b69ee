"""Kernels, Kimi delta attention: per solo prefill-chunk run of
`span_step_packed`, the summed self time of the ops under the KDA mixer's
scopes `kda_proj`, `kda_conv`, `kda_rule` and `state_io`
(`cellbench/scopetrace.py`, reduced under the name and scopes of
`kda_rule_roofline.py`); median. None for a program without the scopes."""

from cellbench import scopetrace
from cellbench.metrics.kda_rule_roofline import KDA_SCOPES, kda_reduced


def read(ctx: dict):
    return scopetrace.median_ms(kda_reduced(ctx), "chunk", *KDA_SCOPES)
