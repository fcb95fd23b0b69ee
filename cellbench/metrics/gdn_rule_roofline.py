"""Kernels, gated DeltaNet: least time at the chip's peaks for what the
gated delta rule NEEDS in one full prefill chunk (the family's
`gdn_rule_needs(config, chunk, "chunk")`: the convolution, the gates and the
recurrence over the configuration's linear layers, the sequence's state once
each way; no projection's weights) over the median device time of the scopes
`gdn_conv`, `gdn_rule` AND `state_io` in a chunk run
(`cellbench/scopetrace.py`). The needs count the convolution and the state's
bytes, so the time counts the scopes that do and move them: work moved from
one of the three into another leaves the share where it was."""

from cellbench import families, roofline, scopetrace

GDN_SCOPES = ("gdn_proj", "gdn_conv", "gdn_rule", "state_io")
# reduced in the same walk and kept in the run's `scopetrace.gdn.json` for
# the reader of a trace (PERF.md section 5): the layer's other scopes
OTHER_SCOPES = ("norm", "attn_proj", "attention", "arena_write",
                "arena_gather", "moe_router", "moe_shared", "moe_experts")


def gdn_reduced(ctx: dict):
    """The trace reduced over the linear mixer's scopes (once a run)."""
    return scopetrace.reduced(
        ctx, "gdn", GDN_SCOPES + OTHER_SCOPES, "state_io")


def read(ctx: dict):
    rule_ms = scopetrace.median_ms(
        gdn_reduced(ctx), "chunk", "gdn_conv", "gdn_rule", "state_io")
    needs = getattr(families.of(ctx["config"]), "gdn_rule_needs", None)
    if not rule_ms or needs is None:
        return None
    least_s, bound = roofline.least_seconds(
        needs(ctx["config"], ctx["prefill_chunk"], "chunk"),
        ctx["device_kind"])
    ctx.setdefault("notes", {})["gdn_rule_roofline_bound"] = bound
    return 100.0 * least_s / (rule_ms * 1e-3)
