"""Device: idle inside a `bbtpu.task` before its jit call (`bbtpu.pack`,
`bbtpu.h2d`, the rest), over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, "pre_dispatch")
