"""Wire: a decode turn's `wire`: the server's `away` (reply handed to the
socket -> next request's last byte read) less the client's own legs, both
directions together (`cellbench/turntrace.py`), median over the traced
decode turns."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.p50_ms(ctx, "turn_wire")
