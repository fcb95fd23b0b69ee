"""Device: `starved` idle while a session's turn was on the wire (a reply on its
way to the client, a request being written and read), over all idle seconds
(`cellbench/turntrace.py`).

Read off one 5 s trace: it ranks the legs inside a run and swings up to
twofold between runs of one tree, so it is no yardstick between runs (the
`turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.starved_share(ctx, "wire")
