"""Median time between output tokens of a request, client side."""

from cellbench import stats


def read(ctx: dict):
    return stats.percentile(stats.window_gaps_ms(ctx["records"], ctx["window_s"]), 50)
