"""Client: a decode turn's five client legs summed (`c_recv` + `c_head` +
`c_other` + `c_embed` + `c_send`, the client's own durations as the server's
`bbtpu.turn.arrive` carries them; `cellbench/turntrace.py`), median over the
traced decode turns."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.p50_ms(ctx, "turn_client")
