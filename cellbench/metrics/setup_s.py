"""Process start to the window's start: checkpoint, load, warm-up, compiles."""

from cellbench import stats


def read(ctx: dict):
    return ctx["setup_s"]
