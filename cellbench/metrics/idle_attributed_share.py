"""Device: idle seconds (gaps between merged device-busy intervals) that a
`bbtpu.*` span or an enqueue puts a name on, over all idle seconds."""

from cellbench import hosttrace


def read(ctx: dict):
    return hosttrace.idle_share(ctx, *hosttrace.IDLE_CLASSES)
