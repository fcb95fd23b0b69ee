"""Kernels: share of attention dispatches in the window that took a Pallas
kernel (flash + paged + ragged over all)."""

from cellbench import stats


def read(ctx: dict):
    paths = {k: stats.delta(ctx, "attn_dispatches", k) or 0
             for k in (ctx["info1"].get("attn_dispatches") or {})}
    total = sum(paths.values())
    if not total:
        return None
    return 100.0 * sum(paths.get(k, 0) for k in ("flash", "paged", "ragged")) / total
