"""Wire layer: bytes the server's codec moved (both directions) per token it
stepped, over the window (rpc_info transport counters)."""

from cellbench import stats


def read(ctx: dict):
    tokens = stats.delta(ctx, "step_tokens")
    moved = [stats.delta(ctx, "transport", d, "wire_bytes") for d in ("tx", "rx")]
    if not tokens or None in moved:
        return None
    return sum(moved) / tokens
