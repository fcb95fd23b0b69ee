"""Executor: every other XLA compile event in the server during the window:
the tiny per-shape programs the server's host code makes when it slices a
member's rows out of a group's output (jit_dynamic_slice, jit_reshape,
jit_squeeze: one per new combination of rows and length, 80-250 ms each on a
cold cache) and loads from the persistent cache. No warm-up can enumerate
them; a serving process meets them the same way."""

from cellbench import stats


def read(ctx: dict):
    every = stats.delta(ctx, "xla_compiles")
    steps = stats.delta(ctx, "steady_state_recompiles")
    if every is None or steps is None:
        return None
    return every - steps
