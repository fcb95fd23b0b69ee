"""Device: idle while no task existed (`starved`) and a session was in one of
the client's own legs of its turn (`c_recv`, `c_head`, `c_other`, `c_embed`,
`c_send`, `open`: `bloombee_tpu/wire/turn.py`), over all idle seconds: the
base of `idle_starved_share`, which the five `idle_starved_*_share` divide
(`cellbench/turntrace.py`).

Read off one 5 s trace: it ranks the legs inside a run and swings up to
twofold between runs of one tree, so it is no yardstick between runs (the
`turn_*_ms_p50` medians are)."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.starved_share(ctx, "client")
