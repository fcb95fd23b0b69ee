"""Client: a request's first turn, the client's own part up to the moment the
prompt's frame is packed: `open` + `c_other` + `c_embed` + `c_send`
(`__aenter__` -> the first request encoded; `cellbench/turntrace.py`), median
over the traced first turns.

Read off the 3-8 first turns one 5 s trace holds: it sizes the leg and
swings by a third between runs of one tree, so it is no yardstick between
runs (the `turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.p50_ms(ctx, "prefill_client")
