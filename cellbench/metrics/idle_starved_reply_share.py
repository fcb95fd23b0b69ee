"""Device: `starved` idle while a step's output was being fetched (the tail of
`served` after its last task) or its reply encoded and handed to the socket
(`reply`), over all idle seconds (`cellbench/turntrace.py`).

Read off one 5 s trace: it ranks the legs inside a run and swings up to
twofold between runs of one tree, so it is no yardstick between runs (the
`turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.starved_share(ctx, "reply")
