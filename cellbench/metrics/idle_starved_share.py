"""Device: idle while no task existed (outside any `bbtpu.task`, nothing
enqueued), over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, "starved")
