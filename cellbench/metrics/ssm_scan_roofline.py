"""Kernels: least time at the chip's peaks for what the chunked scan NEEDS in
one full prefill chunk (the family's `ssm_scan_needs(config, chunk, "chunk")`:
the convolution, dt and the recurrence over the configuration's layers, the
sequence's state once each way; no projection's weights) over the median
device time of the scopes `ssm_scan` AND `state_io` in a chunk run
(`cellbench/ssmtrace.py`). The state's bytes are two thirds of the needs, so
the time of the scope that moves them belongs under the line: work moved
from one scope into the other leaves the share where it was."""

from cellbench import families, roofline, ssmtrace


def read(ctx: dict):
    got = ssmtrace.reduced(ctx)
    scan_ms = got and got.get("chunk_scan_and_state_ms_p50")
    needs = getattr(families.of(ctx["config"]), "ssm_scan_needs", None)
    if not scan_ms or needs is None:
        return None
    least_s, bound = roofline.least_seconds(
        needs(ctx["config"], ctx["prefill_chunk"], "chunk"),
        ctx["device_kind"])
    ctx.setdefault("notes", {})["ssm_scan_roofline_bound"] = bound
    return 100.0 * least_s / (scan_ms * 1e-3)
