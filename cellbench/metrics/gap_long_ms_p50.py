"""gap_ms_p50 where it was not admitted as an end-to-end metric (a longdoc
cell whose gap is half the load generator's own vocabulary-wide head)."""

from cellbench import stats


def read(ctx: dict):
    return stats.percentile(stats.window_gaps_ms(ctx["records"], ctx["window_s"]), 50)
