"""Wire layer: a decode step's client round trip minus the server's own time
for the same step (session.timings)."""

from cellbench import stats


def read(ctx: dict):
    return stats.percentile([w for r in ctx["records"] for w in r["wire_ms"]], 50)
