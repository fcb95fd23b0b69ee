"""ttft_ms_p50 where it was not admitted as an end-to-end metric (longdoc
cells)."""

from cellbench import stats


def read(ctx: dict):
    return stats.percentile(stats.window_ttfts_ms(ctx["records"], ctx["window_s"]), 50)
