"""Kernels, gated DeltaNet: per solo prefill-chunk run of `span_step_packed`,
the summed self time of the ops under the linear mixer's scopes `gdn_proj`,
`gdn_conv`, `gdn_rule` and `state_io` (`cellbench/scopetrace.py`, reduced
under the name and scopes of `gdn_rule_roofline.py`); median."""

from cellbench import scopetrace
from cellbench.metrics.gdn_rule_roofline import GDN_SCOPES, gdn_reduced


def read(ctx: dict):
    return scopetrace.median_ms(gdn_reduced(ctx), "chunk", *GDN_SCOPES)
