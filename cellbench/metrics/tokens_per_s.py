"""Prompt plus generated tokens of requests, prorated over the window, per
second per chip."""

from cellbench import stats


def read(ctx: dict):
    tokens = stats.window_tokens(ctx["records"], ctx["window_s"])
    return tokens / ctx["window_s"] / ctx["chips"]
