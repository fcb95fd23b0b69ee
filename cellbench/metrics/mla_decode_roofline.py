"""Kernels, latent attention: least time at the chip's peaks for what the
paged decode kernel NEEDS in one decode-group step (the family's
`mla_attention_needs(config, rows, context, "decode")`: every row's latent
pages once, absorbed scores and values over them) over the median device time
of `mla_attention` AND `latent_io` in a decode run (`cellbench/mlatrace.py`).
Rows and context are the traced decode steps' own (mean rows, median context:
`mlatrace.core_roofline`); where the program stamps none, as `step_roofline`
takes them: the window's mean group width and the mean live context of its
decode tokens."""

from cellbench import mlatrace, stats


def read(ctx: dict):
    steps = stats.delta(ctx, "batched_steps")
    groups = stats.delta(ctx, "batch_dispatches")
    contexts = [r["prompt_tokens"] + i for r in ctx["records"]
                for i, t in enumerate(r["token_times"])
                if 0.0 <= t < ctx["window_s"]]
    if not groups or not contexts:
        return None
    return mlatrace.core_roofline(
        ctx, "decode", steps / groups, sum(contexts) / len(contexts))
