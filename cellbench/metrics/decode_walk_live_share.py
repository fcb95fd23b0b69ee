"""Kernels: of the turns (grid steps) the paged decode kernel's calls walked
in the window's decode dispatches, over the span's attending layers, those
that held a live page (`rpc_info["memory"]["kv_walk"]`: `live_turns` /
`turns`, bloombee_tpu/ops/pallas/paged_attention.py `walk_bounds`), %. A
turn below a window layer's window or past a row's context moves no byte and
still costs its block specs' turn; 100 where every row of every call spans
as many page groups as the call's longest. None for a program without the
counter (it walked the whole page bucket) or a window with no such
dispatch."""

from cellbench import stats


def read(ctx: dict):
    turns = stats.delta(ctx, "memory", "kv_walk", "turns")
    live = stats.delta(ctx, "memory", "kv_walk", "live_turns")
    if not turns or live is None:
        return None
    return 100.0 * live / turns
