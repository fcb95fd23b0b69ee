"""Device: the rest of `starved` idle: instants no session's leg covers
(between a request's last reply and the next session's first arrival less
`open`; a turn whose stamp fell after the trace stopped; a turn of a client
that sent no entry), over all idle seconds (`cellbench/turntrace.py`).

Read off one 5 s trace: it ranks the legs inside a run and swings up to
twofold between runs of one tree, so it is no yardstick between runs (the
`turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.starved_share(ctx, "uncovered")
