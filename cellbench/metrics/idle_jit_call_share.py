"""Device: idle inside a `bbtpu.jit.*` span (the jitted call itself: argument
flattening, launch), over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, "jit_call")
