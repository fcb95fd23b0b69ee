"""Executor: of the window's dispatches that held a sequence of more than
one row (a prompt chunk, alone or in a fused pack), those whose K/V rows went
into the arena one index a PAGE, over all of them
(`rpc_info["kv"]["chunk_page_writes"]` / `["chunk_row_writes"]`, which the
counter snapshot keeps under `memory`: `kv_writes`), %. 100 where every
chunk starts on a page boundary (bloombee_tpu/kv/arena.py `rows_fill_pages`);
a chunk that starts inside a page, an int4 arena and a `--tp` mesh write row
by row. None for a program without the counter."""

from cellbench import stats


def read(ctx: dict):
    pages = stats.delta(ctx, "memory", "kv_writes", "chunk_page_writes")
    rows = stats.delta(ctx, "memory", "kv_writes", "chunk_row_writes")
    if pages is None or rows is None or pages + rows == 0:
        return None
    return 100.0 * pages / (pages + rows)
