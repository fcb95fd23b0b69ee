"""Kernels, Kimi delta attention: least time at the chip's peaks for what
the vector-decay delta rule NEEDS in one full prefill chunk (the family's
`kda_rule_needs(config, chunk, "chunk")`: the convolution, the gates and the
recurrence over the configuration's KDA layers, the sequence's state once
each way; no projection's weights) over the median device time of the scopes
`kda_conv`, `kda_rule` AND `state_io` in a chunk run
(`cellbench/scopetrace.py`). The needs count the convolution and the state's
bytes, so the time counts the scopes that do and move them, as
`gdn_rule_roofline` does for the scalar-decay rule. None for a program
without the scopes (the parent of the PR that brought them, another
family)."""

from cellbench import families, roofline, scopetrace

KDA_SCOPES = ("kda_proj", "kda_conv", "kda_rule", "state_io")
# reduced in the same walk and kept in the run's `scopetrace.kda.json` for
# the reader of a trace (PERF.md section 5): the layer's other scopes
OTHER_SCOPES = ("norm", "mla_q", "mla_kv", "mla_absorb", "mla_attention",
                "latent_io", "attn_proj", "moe_router", "moe_shared",
                "moe_experts", "mlp")


def kda_reduced(ctx: dict):
    """The trace reduced over the KDA mixer's scopes (once a run); None
    where no op carries one of the three that are KDA's alone (`state_io`
    is every recurrent family's)."""
    got = scopetrace.reduced(
        ctx, "kda", KDA_SCOPES + OTHER_SCOPES, "state_io")
    if not got or not any(s in got["seconds_by_scope"] for s in KDA_SCOPES[:3]):
        return None
    return got


def read(ctx: dict):
    rule_ms = scopetrace.median_ms(
        kda_reduced(ctx), "chunk", "kda_conv", "kda_rule", "state_io")
    needs = getattr(families.of(ctx["config"]), "kda_rule_needs", None)
    if not rule_ms or needs is None:
        return None
    least_s, bound = roofline.least_seconds(
        needs(ctx["config"], ctx["prefill_chunk"], "chunk"),
        ctx["device_kind"])
    ctx.setdefault("notes", {})["kda_rule_roofline_bound"] = bound
    return 100.0 * least_s / (rule_ms * 1e-3)
