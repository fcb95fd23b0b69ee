"""Server loop: span dispatches per token stepped, over the window."""

from cellbench import stats


def read(ctx: dict):
    d, t = stats.delta(ctx, "step_dispatches"), stats.delta(ctx, "step_tokens")
    return d / t if t else None
