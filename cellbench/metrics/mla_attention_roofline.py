"""Kernels, latent attention: least time at the chip's peaks for what the
attention core NEEDS in one full prefill chunk (the family's
`mla_attention_needs(config, chunk, context, "chunk")`: absorbed scores and
values over the cached latent rows and the chunk's own lower triangle, the
latent rows read once and the chunk's written once; no projection's weights)
over the median device time of `mla_attention` AND `latent_io` in a chunk run
(`cellbench/mlatrace.py`). The context is the median of the traced full
chunks' own (`mlatrace.core_roofline`: the core's time follows the context,
and the trace holds some forty chunks of four requests); where the program
stamps none, the mean number of tokens cached before a full chunk of the
window's prompts, as `chunk_roofline` takes it."""

from cellbench import mlatrace


def read(ctx: dict):
    size = ctx["prefill_chunk"]
    before = [c * size for r in ctx["records"]
              if r["start"] is not None and r["start"] < ctx["window_s"]
              for c in range(r["prompt_tokens"] // size)]
    if not before:
        return None
    return mlatrace.core_roofline(
        ctx, "chunk", size, sum(before) / len(before))
