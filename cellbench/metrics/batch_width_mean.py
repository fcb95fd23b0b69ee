"""Server loop: decode rows per grouped decode dispatch over the window."""

from cellbench import stats


def read(ctx: dict):
    steps, groups = stats.delta(ctx, "batched_steps"), stats.delta(ctx, "batch_dispatches")
    return steps / groups if groups else None
