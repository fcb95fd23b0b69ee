"""Server loop: a decode turn's `ingest` + `reply`: request read -> submitted
to the compute queue, and fetch done -> reply handed to the socket
(`cellbench/turntrace.py`), median over the traced decode turns."""

from cellbench import turntrace


def read(ctx: dict):
    return turntrace.p50_ms(ctx, "turn_server_edge")
