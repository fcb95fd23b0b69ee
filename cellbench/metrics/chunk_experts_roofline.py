"""Kernels, an expert layer: least time at the chip's peaks for what the
experts NEED in one full prefill chunk (the family's `experts_needs(config,
chunk, "chunk")`: over the configuration's expert layers, the distinct held
experts the rows reach, each one's matrices at the PUBLISHED width once, the
shared expert's once, the rows in and out; the FLOPs of the rows' held pairs
and of the shared expert; no router) over the median device time of the
scopes `moe_shared` AND `moe_experts` in a solo chunk run
(`cellbench/scopetrace.py`, the reduction `chunk_experts_ms_p50` makes). A
stack a loader pads to whole lanes reads more bytes than the needs count,
and a form that multiplies rows with experts they did not choose more FLOPs:
both lower the share, neither can push it past 100. None for a family
without `experts_needs`, and for a program without the scopes."""

from cellbench import families, roofline, scopetrace

MOE_SCOPES = ("moe_router", "moe_shared", "moe_experts")


def read(ctx: dict):
    needs = getattr(families.of(ctx["config"]), "experts_needs", None)
    if needs is None:
        return None
    got = scopetrace.reduced(ctx, "moe", MOE_SCOPES, "moe_experts")
    experts_ms = scopetrace.median_ms(
        got, "chunk", "moe_shared", "moe_experts")
    if not experts_ms:
        return None
    least_s, bound = roofline.least_seconds(
        needs(ctx["config"], ctx["prefill_chunk"], "chunk"),
        ctx["device_kind"])
    ctx.setdefault("notes", {})["chunk_experts_roofline_bound"] = bound
    return 100.0 * least_s / (experts_ms * 1e-3)
