"""Device: `starved` idle while a request that the server had read was not
yet submitted to the compute queue (`ingest`: the codec pool, the rx queue,
the event loop), over all idle seconds (`cellbench/turntrace.py`).

Read off one 5 s trace: it ranks the legs inside a run and swings up to
twofold between runs of one tree, so it is no yardstick between runs (the
`turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.starved_share(ctx, "ingest")
