"""Wire: a request's first turn, the prompt's rows from the client's encoder to
the server's compute queue: `c_send` + `wire` + `ingest`
(`cellbench/turntrace.py`), median over the traced first turns.

Read off the 3-8 first turns one 5 s trace holds: it sizes the leg and
swings by a third between runs of one tree, so it is no yardstick between
runs (the `turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.p50_ms(ctx, "prefill_upload")
