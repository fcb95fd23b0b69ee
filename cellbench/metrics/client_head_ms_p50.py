"""Client layer: embed + head per output token in the load generator (span
around the calls in cellbench/loadgen.py)."""

from cellbench import stats


def read(ctx: dict):
    per_token = [h + e for r in ctx["records"]
                 for h, e in zip(r["head_ms"], r["embed_ms"][1:] + [0.0])]
    return stats.percentile(per_token, 50)
