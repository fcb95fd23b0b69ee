"""Device: 1 minus the union of device op intervals over the interval the
profiler ran (the server's clock around start_trace / stop_trace)."""

from cellbench import stats


def read(ctx: dict):
    return (ctx.get("trace") or {}).get("device_idle_share")
