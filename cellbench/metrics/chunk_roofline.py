"""Kernels: least time for one full prefill chunk's NEEDED bytes and FLOPs at
the chip's peaks (the family's `chunk_needs`, cellbench/roofline.py) over the chunk
program's median device time in the trace (`server_prefill_ms_p50`; most
chunks are full ones, so the median run is a full chunk). The context is the
mean number of tokens already cached before a full chunk of the window's
prompts."""

from cellbench import families, roofline


def read(ctx: dict):
    chunk_ms = (ctx.get("trace") or {}).get("server_prefill_ms_p50")
    size = ctx["prefill_chunk"]
    before = [c * size for r in ctx["records"]
              if r["start"] is not None and r["start"] < ctx["window_s"]
              for c in range(r["prompt_tokens"] // size)]
    if not chunk_ms or not before:
        return None
    needs = families.of(ctx["config"]).chunk_needs(
        ctx["config"], size, sum(before) / len(before))
    least_s, bound = roofline.least_seconds(needs, ctx["device_kind"])
    ctx.setdefault("notes", {})["chunk_roofline_bound"] = bound
    return 100.0 * least_s / (chunk_ms * 1e-3)
