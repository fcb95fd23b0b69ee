"""Kernels: least time for a decode step's NEEDED bytes and FLOPs at the
chip's peaks (the family's `decode_step_needs`, cellbench/roofline.py) over a
decode group step's median device time in the trace (`server_step_ms_p50`:
decode runs only). The rows are the window's mean decode-group width, the
context the mean live context of the window's decode tokens."""

from cellbench import families, roofline, stats


def read(ctx: dict):
    step_ms = (ctx.get("trace") or {}).get("server_step_ms_p50")
    steps = stats.delta(ctx, "batched_steps")
    groups = stats.delta(ctx, "batch_dispatches")
    contexts = [r["prompt_tokens"] + i for r in ctx["records"]
                for i, t in enumerate(r["token_times"])
                if 0.0 <= t < ctx["window_s"]]
    if not step_ms or not groups or not contexts:
        return None
    needs = families.of(ctx["config"]).decode_step_needs(
        ctx["config"], steps / groups, sum(contexts) / len(contexts))
    least_s, bound = roofline.least_seconds(needs, ctx["device_kind"])
    ctx.setdefault("notes", {})["step_roofline_bound"] = bound
    return 100.0 * least_s / (step_ms * 1e-3)
