"""Executor and jitted step: the device idle while the innermost span open
on the compute thread was `bbtpu.pack` (the plan, the page table and the copy
of the step's rows into one host buffer), over all idle seconds of the traced
5 s (`hosttrace.json` `idle.by_span_s`), %."""

from cellbench import hostpath


def read(ctx: dict):
    return hostpath.idle_by_span_share(ctx, "bbtpu.pack")
