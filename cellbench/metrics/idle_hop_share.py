"""Device: idle while a task was enqueued and not started (outside any
`bbtpu.task`, after a `bbtpu.enqueue`), over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, "hop")
