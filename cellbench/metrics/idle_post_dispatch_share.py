"""Device: idle inside a `bbtpu.task` after its jit call (`bbtpu.commit`,
`bbtpu.slice`, the rest), over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, "post_dispatch")
